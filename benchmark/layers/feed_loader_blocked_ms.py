"""Input feed: of ``feed_source_ms``, the milliseconds a step that the native
loader's consumer waits for a worker to finish the in-order batch (the C++
``get_wait_ns`` counter); the rest of ``feed_source_ms`` is the hand-over of
the lent buffer.  Nothing to read where the feed is not the native loader."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"] or "feed.loader_blocked" not in probe["phases"]:
        return None
    return 1e3 * probe["phases"]["feed.loader_blocked"] / probe["steps"]
