"""Step loop: host milliseconds inside the ``compiled_step`` phase of
``Solver.step`` per step with the fence off, which is what the host needs to
enqueue one step."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"]:
        return None
    return 1e3 * probe["phases"].get("compiled_step", 0.0) / probe["steps"]
