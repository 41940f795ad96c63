"""Persistent compile cache for serving — warm restarts skip AOT warmup.

A serving replica's startup cost is dominated by XLA compilation: one
AOT compile per (bucket, dtype) before the first request can be
answered inside its latency budget.  Replica restarts (crash respawn,
rolling hot-swap) and horizontal scale-out recompile the exact same
programs from scratch — pure waste.  This module wires ``jax``'s
persistent compilation cache to a **per-net directory** so a respawned
replica deserializes yesterday's executables instead of recompiling:

    root/<net-fingerprint>/   # jax cache entries for THIS net only

The directory is keyed by :func:`net_fingerprint` — a content hash of
the net's architecture (layer stack, blob shapes, param/state tree
structure + shapes/dtypes) and the compute dtype.  jax's own entry key
then covers the rest (bucket, backend, flags), so the effective key is
(net fingerprint, bucket, dtype) — exactly the
:class:`~sparknet_tpu.serve.engine.InferenceEngine` executable-cache
key.  Weights are NOT part of the fingerprint: the engine passes
params as executable *arguments*, so every weight hot-swap of the same
arch reuses both the in-memory and the on-disk cache; a different arch
gets a different directory and can never collide.

Placement follows the one rule in ``utils/compile_cache.py``: where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
the per-net subdirectory is skipped (jax's own entry key already covers
the program text, so nets cannot collide there either).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from typing import Any, Dict, Optional

# storage-fault degradation (docs/ROBUSTNESS.md): a cache whose
# directory cannot be created/read is disabled for the rest of the
# process — replicas recompile (slower warmup) instead of crashing.
# Module-global because jax's cache config is process-global too.
_io_disabled = False


def io_disabled() -> bool:
    """Whether the persistent cache was disabled by a storage fault."""
    return _io_disabled


def _reset_io_disabled() -> None:
    """Test hook: re-arm the cache after a fault-injection test."""
    global _io_disabled
    _io_disabled = False


def net_fingerprint(
    net, params: Any, state: Any, compute_dtype=None, layout=None,
    quant: Any = None,
) -> str:
    """16-hex content hash of the net's *architecture* — stable across
    processes and weight versions, different for any structural change.

    Covers: layer (name, type, tops, bottoms), blob shapes, input
    names, the param/state pytrees' paths + shapes + dtypes, the
    compute dtype, (when serving through a multi-device
    :class:`~sparknet_tpu.parallel.partition.Layout`) the layout
    fingerprint, and (quantized engines, ``serve/quantize.py``) the
    quantization mode — the same arch compiled under two different
    partition rule tables or precisions produces different
    executables, so their compile caches must never alias.  ``quant``
    is folded in only when set and non-f32, keeping pre-quantization
    fingerprints (and the persistent caches they key) stable.  Weight
    VALUES are deliberately excluded (see module docstring)."""
    import jax

    def tree_sig(tree):
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        return [
            (jax.tree_util.keystr(path), str(leaf.dtype), list(leaf.shape))
            for path, leaf in leaves
        ]

    doc = {
        "layers": [
            (l.name, l.type, list(l.top), list(l.bottom))
            for l in net.layers
        ],
        "blobs": {
            name: list(shape) for name, shape in net.blob_shapes.items()
        },
        "inputs": list(net.input_names),
        "params": tree_sig(params),
        "state": tree_sig(state),
        "dtype": (
            str(jax.numpy.dtype(compute_dtype))
            if compute_dtype is not None else None
        ),
    }
    if layout is not None:
        from ..parallel import partition

        doc["layout"] = partition.layout_fingerprint(layout)
    if quant is not None and str(quant) != "f32":
        doc["quant"] = str(quant)
    raw = json.dumps(doc, sort_keys=True).encode()
    return hashlib.sha256(raw).hexdigest()[:16]


def cache_entries(path: str) -> int:
    """How many cache entry files live under ``path`` (0 for a missing
    dir).  jax names entries ``jit_*``/hash blobs one file each, so a
    file count is an honest "did warmup hit or compile?" probe."""
    try:
        return sum(
            1 for name in os.listdir(path)
            if not name.startswith(".")
            and os.path.isfile(os.path.join(path, name))
        )
    except OSError:
        return 0


def enable_persistent_cache(
    root: str,
    fingerprint: Optional[str] = None,
) -> Optional[Dict[str, Any]]:
    """Point jax's persistent compilation cache at
    ``root[/fingerprint]`` for THIS process — unless
    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside, which
    wins (``utils/compile_cache.py`` holds the rule and the one config
    update).  Safe to call before or after backend init.  Returns
    ``{"dir", "entries"}`` — ``entries`` is the pre-warmup count, so
    callers can diff it after warmup to tell a cache-hit restart from a
    cold compile.

    Degradation: a storage fault here (cache root unwritable, disk
    full, injected ``io.*@site=compile_cache`` chaos) disables the
    persistent cache for the rest of the process and returns None —
    the replica warms up by compiling, exactly as if ``--compile-cache``
    had not been passed.  The fault is counted
    (``io_faults{site=compile_cache}``) and warned once."""
    global _io_disabled
    if _io_disabled:
        return None
    from ..utils import compile_cache as placement
    from ..utils import safeio

    path = placement.resolve(root, fingerprint)
    try:
        safeio.check_faults("compile_cache")
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        safeio.count_fault("compile_cache", safeio.classify(e))
        _io_disabled = True
        print(
            f"WARNING: persistent compile cache disabled for this run "
            f"({path}): {e}",
            file=sys.stderr, flush=True,
        )
        return None
    placement.enable(root, fingerprint)
    return {"dir": path, "entries": cache_entries(path)}
