"""Where XLA's persistent compilation cache lives — one rule, one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
and no code sets another (jax reads the variable itself at import).
Where it is not, the cache is ``<checkout>/.jax_cache/`` (gitignored),
or ``root/`` when an operator names one (``--compile-cache``) — never a
path made from a temporary name, a pid or the time: a cache that moves
between runs never hits.

Every entry point (the apps, ``tools/caffe``, ``tools/serve``,
``serve/replica``, ``deploy/trainer``, ``chip_smoke.py``)
calls :func:`enable` first thing in ``main``; importing jax and updating
its config touches no device, so a parent that must stay off the chip
(the supervisor, the router) may call it too.
"""

from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache")
)


def resolve(root: Optional[str] = None, subdir: Optional[str] = None) -> str:
    """The directory the rule picks (nothing is touched)."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    path = os.path.abspath(root or REPO_CACHE_DIR)
    return os.path.join(path, subdir) if subdir else path


def enable(root: Optional[str] = None, subdir: Optional[str] = None) -> str:
    """Apply the placement rule; returns the directory in effect.

    Every compile is persisted (no time or size floor): a second run of
    the same command then compiles nothing, which is what lets a smoke
    run prove its own cache by counting entries."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax leaves an instruction's metadata (op_name, source line) out of
    # the cache's key by default, so a step that differs from a cached one
    # in its scopes alone is handed that executable, with the older
    # op_names in its text: Solver.step_scopes would read the scopes of a
    # program that is not this one (seen on the CPU and on the chip,
    # PERF.md section 6, PR 37).  With the metadata in the key an edit that
    # moves a traced line compiles once more.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = resolve(root, subdir)
    if not os.environ.get(ENV) and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax latches the cache object at first use; a directory chosen
        # after that (a replica's per-net subdir) needs the latch dropped
        cc.reset_cache()
    return path
