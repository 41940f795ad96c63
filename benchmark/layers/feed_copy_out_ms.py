"""Input feed: of ``feed_source_ms``, the milliseconds a step that
``sn_loader_next`` spends copying the batch out of the native loader's queue
into the caller's array, after the unlock (the C++ ``copy_ns`` counter).  One
thread, the staging thread's.  Nothing to read where the feed is not the
native loader."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"] or "feed.copy_out" not in probe["phases"]:
        return None
    return 1e3 * probe["phases"]["feed.copy_out"] / probe["steps"]
