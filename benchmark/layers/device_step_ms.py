"""Compiled step: device milliseconds of one step, the union of the
operations inside each execution of the step program, mean over the traced
steps."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["device_step_s"]:
        return None
    steps = trace["device_step_s"]
    return 1e3 * sum(steps) / len(steps)
