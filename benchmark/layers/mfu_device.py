"""Compiled step: model FLOPs of one step (benchmark/flops.py, from the
shapes) over the device time of one step and the chips' bf16 peak.  Idle
time between steps is not in it: that is ``device_idle_share``."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["device_step_s"]:
        return None
    step_s = sum(trace["device_step_s"]) / len(trace["device_step_s"])
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops_per_step"] / (step_s * peak)
