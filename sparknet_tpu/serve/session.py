"""Session-aware serving — O(1) per-session decode-state caching.

The serving tier through PR 12 is stateless: a multi-step session
(autoregressive decode, interactive completion) recomputes its full
prefix from scratch on every request, so the per-request cost grows
O(prefix).  The compiler-first O(1) autoregressive-caching paper
(PAPERS.md, arXiv:2603.09555) points at the fix the engine already
uses for weights (PR 9) and quantized trees (PR 12): make the carried
state an executable **argument**.

Two pieces:

- :class:`DecodeStepper` — compiles a recurrent deploy net's
  *single-token step* ``step(params, state, carry, token) ->
  (output row, new carry)``.  The carry (the LSTM/RNN hidden state —
  the compressed prefix features) is a fixed-shape pytree passed as a
  donated argument, so the step compiles ONCE per (fingerprint, width)
  and a session step is O(1) instead of O(prefix).  The cold path
  replays the request's prefix through the SAME compiled step (one
  token at a time), which makes hit-vs-cold outputs **bit-identical by
  construction** — both paths run the same executable; the cache can
  only ever change latency, never answers.
- :class:`SessionCache` — the per-session carry store, keyed like
  PR 8's decoded-batch cache: ``(net fingerprint, session id)`` with a
  weights-**generation** tag.  A hot-swap bumps the generation; a
  cached entry whose gen no longer matches is dropped (counted
  ``stale_gen``) and the state is rebuilt from the request's prefix —
  stale-generation state is never served.  Entries are bounded
  LRU-by-hit under ``SPARKNET_SESSION_CACHE_MB``; the cache registers
  as the telemetry registry's ``"session_cache"`` source, so hits /
  misses / evictions / stale-gen ride ``/metrics``, ``/healthz`` and
  the ``/dash`` session panel.

Requests are **self-contained**: a session request always carries the
full token prefix, and the cache holds (tokens, carry, last output).
A hit steps only the suffix beyond the cached prefix; a miss (cold
replica, migrated session, evicted entry, stale generation, prefix
mismatch) replays everything — "rebuilt, not wrong" is structural,
which is what makes router-level session migration (a killed replica's
sessions landing on a peer) safe to do blindly.

``take``/``put`` follow the pointer-exchange discipline: ``take``
*removes* the entry (its carry buffers may be donated to the step
executable), ``put`` publishes the successor.  A request that dies
mid-step loses the entry — the next request rebuilds cold — and two
racing requests for one session serialize through the batcher's single
worker in the serving stack (direct engine callers race safely: last
put wins, both answers correct).

Disabled mode (``SPARKNET_SESSION_CACHE=0``): :data:`DISABLED` is a
shared no-op singleton — no entries, no registry source, zero
footprint (pinned by test).  Engines without a recurrent layer share
the same singleton.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..nets.layers import (
    ApplyCtx,
    DATA_LAYER_TYPES,
    LAYER_IMPLS,
)
from ..ops.matmul import mxu_dot
from ..telemetry.registry import REGISTRY

# layer types that carry decode state across steps
RECURRENT_TYPES = ("LSTM", "RNN")

# layer types that are safe to apply to a single (1, N, ...) time slice
# with the sequence net's own params: their math never mixes the
# leading (time) axis into the computation.  Everything per-element or
# contracting trailing axes qualifies; spatial layers (Convolution,
# Pooling, LRN) interpret dim 0 as batch-with-NHWC and do not.
STEP_SAFE_TYPES = {
    "Embed", "InnerProduct", "ReLU", "Sigmoid", "TanH", "AbsVal",
    "BNLL", "ELU", "Power", "Exp", "Log", "Dropout", "Softmax",
    "Eltwise", "Scale", "Bias", "Threshold", "Concat", "Split",
}


def _lstm_cell(lp, params, x, carry, cdt):
    """One LSTM step on a (1, N, ...) slice — the ``lax.scan`` body of
    ``nets/layers.LSTM.apply`` with ``cont=1`` (mid-sequence): gate
    order i, f, o, g, f32 carry.  A session's step 0 starts from the
    zero carry, where cont=0 and cont=1 are bitwise-equivalent
    (``0 * x == 0``)."""
    h_prev, c_prev = carry
    t, n = x.shape[:2]
    x2 = x.reshape(t, n, -1).astype(cdt)
    gx = mxu_dot(x2, params["weight"].astype(cdt)) + params["bias"]
    gates = gx[0] + mxu_dot(
        h_prev.astype(cdt), params["hidden_weight"].astype(cdt)
    )
    i, f, o, g = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    o = jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c = f * c_prev + i * g
    h = o * jnp.tanh(c)
    return h[None].astype(cdt), (h, c)


def _rnn_cell(lp, params, x, carry, cdt):
    """One vanilla-RNN step (``nets/layers.RNN``): h = tanh(Wx x + b +
    Wh h_prev), o = tanh(Wo h + bo)."""
    (h_prev,) = carry
    t, n = x.shape[:2]
    x2 = x.reshape(t, n, -1).astype(cdt)
    gx = mxu_dot(x2, params["weight"].astype(cdt)) + params["bias"]
    h = jnp.tanh(gx[0] + mxu_dot(
        h_prev.astype(cdt), params["hidden_weight"].astype(cdt)
    ))
    o = jnp.tanh(
        mxu_dot(h.astype(cdt), params["out_weight"].astype(cdt))
        + params["out_bias"]
    )
    return o[None].astype(cdt), (h,)


_CELLS = {"LSTM": _lstm_cell, "RNN": _rnn_cell}


class DecodeStepper:
    """A recurrent deploy net's single-token decode step as one pure,
    jit-able function with the carry as an explicit argument.

    Works on any ``XLANet`` whose non-recurrent layers are all
    time-distributed (:data:`STEP_SAFE_TYPES`) — e.g. the char-level
    decoder ``models/prototxt/char_rnn_deploy.prototxt`` (Embed ->
    LSTM -> InnerProduct(axis=2) -> Softmax(axis=2)).  Blobs stay
    time-major ``(1, N, ...)`` through the step so the sequence net's
    axis-sensitive layers (IP/Softmax over axis 2) apply unchanged;
    recurrent layers run their cell math directly with the carry.

    The net's ``cont`` sequence-continuation inputs (any net input
    consumed as a recurrent layer's second bottom) are supplied
    internally as ones — a session is one unbroken sequence, and the
    zero initial carry makes step 0's cont irrelevant bitwise."""

    def __init__(self, net, output: str, compute_dtype: Any = jnp.float32):
        self.net = net
        self.output = output
        self.compute_dtype = compute_dtype
        recurrents = [
            lp for lp in net.layers if lp.type in RECURRENT_TYPES
        ]
        if not recurrents:
            raise ValueError(
                "DecodeStepper: net has no recurrent (LSTM/RNN) layer"
            )
        bad = [
            f"{lp.name}({lp.type})" for lp in net.layers
            if lp.type not in RECURRENT_TYPES
            and lp.type not in DATA_LAYER_TYPES
            and lp.type not in STEP_SAFE_TYPES
        ]
        if bad:
            raise ValueError(
                f"DecodeStepper: layers not step-safe for per-token "
                f"decode: {', '.join(bad)} (want {sorted(STEP_SAFE_TYPES)})"
            )
        self._recurrents = recurrents
        # cont markers: net inputs consumed as recurrent bottoms[1:]
        self.cont_inputs = {
            b for lp in recurrents for b in lp.bottom[1:]
            if b in net.input_names
        }
        primaries = [
            n for n in net.input_names if n not in self.cont_inputs
        ]
        if not primaries:
            raise ValueError("DecodeStepper: no primary token input")
        self.primary = primaries[0]
        # per-step row shape of the primary input: the sequence net
        # declares (T, N, ...); a step feeds one (N, ...) slice
        self.row_shape: Tuple[int, ...] = tuple(
            net.blob_shapes[self.primary][2:]
        )
        # token ids when an Embed layer consumes the primary input
        # (ints in, clamp range known); raw features otherwise
        self.vocab: Optional[int] = None
        for lp in net.layers:
            if lp.type == "Embed" and self.primary in lp.bottom:
                self.vocab = int(lp.sub("embed_param").get("input_dim"))
                break
        self.token_dtype = (
            jnp.int32 if self.vocab is not None else compute_dtype
        )

    @staticmethod
    def supports(net) -> bool:
        """Cheap probe: does this net carry decode state at all?"""
        return any(lp.type in RECURRENT_TYPES for lp in net.layers)

    # ------------------------------------------------------------------
    def init_carry(self, n: int = 1):
        """The zero decode state for ``n`` parallel sessions — one
        fixed-shape f32 tuple per recurrent layer (h, c for LSTM; h for
        RNN), matching the sequence path's ``lax.scan`` init."""
        carry: Dict[str, Tuple[jax.Array, ...]] = {}
        for lp in self._recurrents:
            h = int(lp.sub("recurrent_param").get("num_output"))
            # one buffer per slot: the engine donates the whole carry,
            # and a buffer cannot be donated twice in one call
            carry[lp.name] = tuple(
                jnp.zeros((n, h), jnp.float32)
                for _ in range(2 if lp.type == "LSTM" else 1)
            )
        return carry

    def step_fn(self, params, state, carry, token):
        """Pure: one token per session row -> (output row (N, ...),
        new carry).  Jit/AOT-compile this; the engine donates ``carry``
        (the pointer-exchange discipline — the old state is consumed by
        the step that supersedes it)."""
        n = token.shape[0]
        blobs: Dict[str, jax.Array] = {self.primary: token[None]}
        for name in self.cont_inputs:
            blobs[name] = jnp.ones((1, n), jnp.float32)
        new_carry = dict(carry)
        ctx = ApplyCtx(
            train=False, rng=None, compute_dtype=self.compute_dtype
        )
        for lp in self.net.layers:
            if lp.type in DATA_LAYER_TYPES:
                continue
            if lp.type in RECURRENT_TYPES:
                out, new_carry[lp.name] = _CELLS[lp.type](
                    lp, params.get(lp.name, {}),
                    blobs[lp.bottom[0]], carry[lp.name],
                    self.compute_dtype,
                )
                blobs[lp.top[0]] = out
                continue
            impl = LAYER_IMPLS[lp.type]
            outs, _ = impl.apply(
                lp, params.get(lp.name, {}), state.get(lp.name),
                [blobs[b] for b in lp.bottom], ctx,
            )
            for top, o in zip(lp.top, outs):
                blobs[top] = o
        return blobs[self.output][0], new_carry


# ---------------------------------------------------------------------------
# the per-session state cache


def _tree_bytes(tree) -> int:
    return sum(
        int(np.asarray(leaf).nbytes)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


class SessionEntry:
    __slots__ = ("gen", "tokens", "carry", "last_out", "nbytes", "hits",
                 "last_hit")

    def __init__(self, gen: int, tokens: np.ndarray, carry,
                 last_out: np.ndarray):
        self.gen = gen
        self.tokens = tokens
        self.carry = carry
        self.last_out = last_out
        self.nbytes = (
            _tree_bytes(carry) + tokens.nbytes + int(last_out.nbytes)
        )
        self.hits = 0
        self.last_hit = 0


class SessionCache:
    """Bounded per-session carry store (module docstring).  Keys are
    ``(net fingerprint, session id)``; the weights generation rides the
    entry as a validity tag.  ``take`` pops (gen mismatch -> drop +
    ``stale_gen``; prefix mismatch -> drop + ``rebuilt``), ``put``
    re-publishes, evicting least-recently-hit entries past the byte
    budget (``SPARKNET_SESSION_CACHE_MB``, default 64)."""

    enabled = True

    def __init__(self, max_mb: Optional[float] = None):
        if max_mb is None:
            max_mb = float(
                os.environ.get("SPARKNET_SESSION_CACHE_MB", "") or 64.0
            )
        self.max_bytes = int(max_mb * (1 << 20))
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], SessionEntry] = {}
        self._clock = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.stale_gen = 0
        self.rebuilt = 0
        self.puts = 0
        self.coalesced = 0
        REGISTRY.register_source("session_cache", self)

    # ------------------------------------------------------------------
    def take(
        self, fingerprint: str, session: str, gen: int,
        tokens: np.ndarray,
    ) -> Tuple[Optional[SessionEntry], str]:
        """Pop the session's entry when it is usable for a request
        carrying ``tokens`` (full prefix) at weights generation
        ``gen``.  Returns ``(entry, cache_state)`` where cache_state is
        the response's observability tag: ``hit`` / ``cold`` /
        ``stale_gen`` (hot-swap invalidation) / ``rebuilt`` (prefix
        mismatch — same session id, different history)."""
        key = (fingerprint, str(session))
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                self.misses += 1
                return None, "cold"
            if entry.gen != gen:
                # never serve state computed under other weights
                self.stale_gen += 1
                return None, "stale_gen"
            n = entry.tokens.size
            if n > tokens.size or not np.array_equal(
                entry.tokens, tokens[:n]
            ):
                self.rebuilt += 1
                return None, "rebuilt"
            self._clock += 1
            entry.hits += 1
            entry.last_hit = self._clock
            self.hits += 1
            return entry, "hit"

    def put(
        self, fingerprint: str, session: str, gen: int,
        tokens: np.ndarray, carry, last_out: np.ndarray,
    ) -> None:
        entry = SessionEntry(gen, tokens, carry, last_out)
        if entry.nbytes > self.max_bytes:
            return  # larger than the whole budget: not cacheable
        key = (fingerprint, str(session))
        with self._lock:
            self._clock += 1
            entry.last_hit = self._clock
            self._entries[key] = entry
            self.puts += 1
            used = sum(e.nbytes for e in self._entries.values())
            if used > self.max_bytes:
                # LRU-by-hit: oldest last_hit goes first; the entry
                # just published is the newest and survives
                for k in sorted(
                    self._entries, key=lambda k: self._entries[k].last_hit
                ):
                    if used <= self.max_bytes or k == key:
                        continue
                    used -= self._entries.pop(k).nbytes
                    self.evictions += 1

    def drop(self, fingerprint: str, session: str) -> None:
        with self._lock:
            self._entries.pop((fingerprint, str(session)), None)

    def note_coalesced(self) -> None:
        """A batched-decode window held back a second row for a session
        already live in the batch (``take`` POPS — admitting both would
        make the later row rebuild from prefix).  The deferred row waits
        for the live row's ``put`` and then takes a hit; this counter
        makes the coalesce observable on /metrics and /dash."""
        with self._lock:
            self.coalesced += 1

    # ------------------------------------------------------------------
    def resident(self) -> Tuple[int, int]:
        with self._lock:
            return (
                len(self._entries),
                sum(e.nbytes for e in self._entries.values()),
            )

    def snapshot(self) -> Dict[str, Any]:
        entries, nbytes = self.resident()
        with self._lock:
            total = self.hits + self.misses + self.stale_gen + self.rebuilt
            return {
                "enabled": True,
                "entries": entries,
                "resident_bytes": nbytes,
                "max_bytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "stale_gen": self.stale_gen,
                "rebuilt": self.rebuilt,
                "puts": self.puts,
                "coalesced": self.coalesced,
                "hit_rate": round(self.hits / total, 4) if total else None,
            }


class _DisabledSessionCache:
    """Shared no-op: the zero-footprint disabled mode, and the cache of
    every non-recurrent engine.  Never registers a registry source,
    never allocates per call."""

    enabled = False

    def take(self, fingerprint, session, gen, tokens):
        return None, "disabled"

    def put(self, fingerprint, session, gen, tokens, carry, last_out):
        pass

    def drop(self, fingerprint, session):
        pass

    def note_coalesced(self):
        pass

    def resident(self):
        return 0, 0

    def snapshot(self):
        return {"enabled": False, "entries": 0}


DISABLED = _DisabledSessionCache()


def make_session_cache() -> Any:
    """The engine's constructor hook: a real cache, or the shared
    disabled singleton under ``SPARKNET_SESSION_CACHE=0``."""
    if os.environ.get("SPARKNET_SESSION_CACHE", "1") in ("0", "off"):
        return DISABLED
    return SessionCache()
