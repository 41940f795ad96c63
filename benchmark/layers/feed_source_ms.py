"""Input feed: milliseconds a step that the H2D staging thread of
``data/prefetch.py`` spends inside ``next(source)``, over the live loop with
the fence off.  For the native loader that is the wait for a worker's batch
(the loader lends its buffer: nothing is copied out); for a python feed, the
python that builds a batch.  With ``feed_h2d_ms`` and ``feed_backpressure_ms`` it adds up
to the step: the thread is serial."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"] or "feed.source" not in probe["phases"]:
        return None
    return 1e3 * probe["phases"]["feed.source"] / probe["steps"]
