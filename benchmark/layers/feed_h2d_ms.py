"""Input feed: milliseconds a step that the H2D staging thread of
``data/prefetch.py`` spends inside ``jax.device_put``, over the live loop
with the fence off."""


def read(run):
    probe = run.get("dispatch")
    if not probe or not probe["steps"] or "feed.h2d" not in probe["phases"]:
        return None
    return 1e3 * probe["phases"]["feed.h2d"] / probe["steps"]
