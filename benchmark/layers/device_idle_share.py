"""Device: share of the traced window in which no operation ran on the
device, one minus busy over window, both the trace's own: the union of the
``XLA Ops`` from the start of the first counted execution of the step
program in the live loop to the end of the last."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
