"""Input feed: milliseconds a step that the H2D staging thread of
``data/prefetch.py`` spends blocked on its full queue, over the live loop
with the fence off: the slack of the feed.  About nothing where the feed sets
the pace, about a step where the device does."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"] or "feed.backpressure" not in probe["phases"]:
        return None
    return 1e3 * probe["phases"]["feed.backpressure"] / probe["steps"]
