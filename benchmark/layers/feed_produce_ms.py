"""Input feed: worker-thread milliseconds a step inside the native loader's
``Loader::build`` (index, crop, mirror, mean, float32), all threads together
(the C++ ``build_ns`` counter).  Over the thread count it is the fastest the
loader can go.  Nothing to read where the feed is not the native loader."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"] or "feed.produce" not in probe["phases"]:
        return None
    return 1e3 * probe["phases"]["feed.produce"] / probe["steps"]
