"""Input feed: share of the loop's host time spent blocked in ``next(feed)``
(the ``input_wait`` phase of ``Solver.step``), over the fenced steps with the
live feed running."""


def read(run):
    fenced = run.get("fenced")
    if not fenced or not fenced["wall_s"]:
        return None
    return 100.0 * fenced["phases"].get("input_wait", 0.0) / fenced["wall_s"]
