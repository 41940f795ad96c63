"""InferenceEngine — a prototxt + snapshot held resident behind
bucketed, AOT-compiled ``XLANet.apply`` executables.

The one-shot tools (classify, extract_features) pay a full trace +
XLA compile per invocation and per batch shape. A serving process
cannot: request sizes vary per call and compilation is seconds while a
request budget is milliseconds. The engine fixes a small set of batch
*buckets* (default 1/8/32), AOT-compiles the forward once per bucket at
warmup, and pads every request up to the nearest bucket — so steady
state is pure execution, never compilation. Padding is sound because
every layer in the zoo is per-row independent in TEST phase (convs,
pools, FC, Softmax, BN-with-stored-stats, LRN): the padded rows cannot
leak into the real rows, and the real rows' outputs are bit-identical
to an unpadded run of the same executable bucket (tests/test_serve.py
pins this).

Weights are executable **arguments**, not baked-in constants: the
compiled program depends only on the net's architecture, so a weight
hot-swap (:meth:`InferenceEngine.swap`) is an atomic pointer exchange
— zero recompiles, zero dropped requests — and a *different* arch can
never hit a stale executable because the compile cache is keyed by
``(net fingerprint, bucket, dtype)``
(:func:`~sparknet_tpu.serve.compile_cache.net_fingerprint`).  Every
swap bumps a monotone ``generation`` the HTTP layer tags responses
with.  The same fingerprint keys the on-disk persistent compile cache
(``serve/compile_cache.py``), so replica restarts skip AOT warmup.

The decode step donates its carry (the session state the step
supersedes) on every backend, the CPU included, so tests run the path
the chip runs; the bucketed forward donates nothing.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import trace as _trace
from . import quantize as _quantize
from . import session as _session
from .compile_cache import net_fingerprint

Rows = Union[np.ndarray, Dict[str, np.ndarray]]

# batched-decode widths (ISSUE 17): the compiled step executables the
# continuous token-level batcher dispatches through.  The floor is 4,
# not 1, deliberately: XLA CPU compiles the width-1 step with a
# different fusion whose results differ from the batched widths at the
# ulp level, while widths >= 4 are mutually bitwise row-independent
# (pinned by test) — so a lone session pads to 4 and per-row answers
# stay bitwise stable across any batch occupancy.
DECODE_BUCKETS_DEFAULT = (4, 8, 16)


def decode_buckets_from_env() -> Tuple[int, ...]:
    """``SPARKNET_DECODE_BUCKETS`` (e.g. ``"4,8"``) -> sorted widths;
    the default ladder when unset."""
    raw = os.environ.get("SPARKNET_DECODE_BUCKETS", "").strip()
    if not raw:
        return DECODE_BUCKETS_DEFAULT
    widths = tuple(sorted({int(w) for w in raw.split(",") if w.strip()}))
    if not widths or widths[0] < 4:
        # the floor is load-bearing: widths below 4 compile to
        # fusion whose rows are NOT bitwise stable vs the ladder
        raise ValueError(
            f"SPARKNET_DECODE_BUCKETS={raw!r}: want ints >= 4 "
            "(narrower steps break cross-width bitwise row stability)"
        )
    return widths


class _DecodeRow:
    """One live session row inside a ``decode_batch`` window."""

    __slots__ = ("tag", "slot", "session", "tokens", "steps", "top_k",
                 "deadline", "carry", "out", "pos", "generated",
                 "cache_state", "steps_run")

    def __init__(self, tag, slot, session, tokens, steps, top_k,
                 deadline, carry, out, pos, cache_state):
        self.tag = tag
        self.slot = slot
        self.session = session
        self.tokens = tokens          # canonical full prefix
        self.steps = steps            # tokens to greedy-decode beyond it
        self.top_k = top_k
        self.deadline = deadline      # absolute perf_counter, or None
        self.carry = carry            # per-row (1, h) leaf tree
        self.out = out                # last step output, (1, ...) rows
        self.pos = pos                # prefix tokens already incorporated
        self.generated: List[int] = []
        self.cache_state = cache_state
        self.steps_run = 0            # REAL steps this request paid for

    @property
    def n_prefix(self) -> int:
        return int(self.tokens.shape[0])

    def finished(self) -> bool:
        return (
            self.pos >= self.n_prefix
            and len(self.generated) >= self.steps
        )


def load_weights_any(net, params, state, weights: str):
    """Overlay weights from any trained artifact this repo produces:
    ``.caffemodel`` / ``.npz`` weight files (comma-separated lists
    overlay in order, later files winning — ``tools/_common`` rules) or
    a full ``.solverstate.npz``/``.orbax`` training snapshot, from
    which params + net state (BN statistics) are extracted.  Snapshot
    loads run the PR 3 manifest verification — a torn file raises
    :class:`~sparknet_tpu.solver.snapshot.SnapshotError` instead of
    serving garbage weights (the hot-swap safety gate)."""
    from ..solver import snapshot as snap

    if weights.endswith((snap.NPZ_SUFFIX, snap.ORBAX_SUFFIX)):
        from ..proto import caffemodel as cm

        st = snap.load_state(weights)
        p = cm.merge_into(jax.device_get(params), st["params"])
        s = jax.device_get(state)
        if st.get("state"):
            s = cm.merge_into(s, st["state"])
        to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        return to_dev(p), to_dev(s)
    from ..tools._common import load_weights

    return load_weights(net, params, state, weights)


class InferenceEngine:
    def __init__(
        self,
        net,
        params,
        state,
        *,
        buckets: Sequence[int] = (1, 8, 32),
        output: Optional[str] = None,
        compute_dtype: Any = jnp.float32,
        metrics=None,
        layout=None,
        quant: Any = None,
    ):
        """``net``: an ``XLANet`` (any phase; TEST semantics are forced
        at apply time). ``output``: blob to return — defaults to the
        final layer's first top. ``metrics``: optional ``ServeMetrics``
        the engine reports per-bucket batch counts, padding waste and
        device latency into.  ``layout``: a
        :class:`~sparknet_tpu.parallel.partition.Layout` for a
        multi-device replica — weights land per the SAME rule-table
        sharding trees training uses (one sharded compile path for
        train and serve), request rows shard over the batch axis when
        the bucket divides, and the fingerprint (hence both compile
        caches) is keyed by the layout so layouts never alias.
        ``quant``: ``"f32"`` (default), ``"bf16"`` (weights cast to
        bf16 at install, bf16 compute) or ``"int8"`` (per-channel
        int8 weights + in-graph per-row activation quantization,
        ``serve/quantize.py``) — the mode folds into the fingerprint
        so the compile caches never alias precisions."""
        if not buckets:
            raise ValueError("InferenceEngine: need at least one bucket")
        self.quant = _quantize.normalize_mode(quant)
        if self.quant == "bf16":
            # the weights-as-arguments bf16 mode implies bf16 compute
            compute_dtype = jnp.bfloat16
        if self.quant == "int8" and layout is not None:
            raise ValueError(
                "InferenceEngine: quant='int8' with a multi-device "
                "layout is not supported (quantize the replicated "
                "serving shape; layouts keep f32/bf16)"
            )
        if self.quant == "int8" and _session.DecodeStepper.supports(net):
            raise ValueError(
                "InferenceEngine: quant='int8' on a recurrent net is "
                "not supported (the decode step's per-channel scale "
                "capture does not cover recurrent cells; use f32/bf16)"
            )
        self.net = net
        self.buckets: Tuple[int, ...] = tuple(sorted({int(b) for b in buckets}))
        if self.buckets[0] < 1:
            raise ValueError(f"buckets must be >= 1, got {self.buckets}")
        self.compute_dtype = compute_dtype
        self.metrics = metrics
        self.output = output or net.layers[-1].top[0]
        if self.output not in net.blob_shapes:
            raise ValueError(
                f"output blob {self.output!r} not in net "
                f"(have: {sorted(net.blob_shapes)})"
            )
        producer = next(
            (l for l in reversed(net.layers) if self.output in l.top), None
        )
        # topk() must not re-softmax a net that already ends in one
        self.output_is_prob = producer is not None and producer.type == "Softmax"
        self.input_names = list(net.input_names) or ["data"]
        self._row_shapes = {
            name: tuple(net.blob_shapes[name][1:]) for name in self.input_names
        }
        self.layout = layout
        self._mesh = None
        if layout is not None:
            from ..parallel import partition as _partition

            self._partition = _partition
            self._mesh = layout.mesh()
        self._cache: Dict[Tuple[str, int, str], Any] = {}
        # session-aware decode (serve/session.py): recurrent nets get
        # a compiled single-token step whose carry is an executable
        # argument, plus the per-session carry cache.  Non-recurrent
        # nets share the zero-footprint DISABLED singleton.
        self._stepper = None
        self._step_cache: Dict[Tuple[str, int], Any] = {}
        if _session.DecodeStepper.supports(net):
            if layout is not None:
                raise ValueError(
                    "InferenceEngine: recurrent nets serve single-"
                    "device (sessions are per-row state; layouts are "
                    "for the stateless bucketed path)"
                )
            self._stepper = _session.DecodeStepper(
                net, self.output, compute_dtype=self.compute_dtype
            )
        # batched-decode width ladder (only meaningful with a stepper);
        # compiled lazily on first batched dispatch so replica boot cost
        # stays flat — warmup still compiles only the width-1 step
        self.decode_buckets: Tuple[int, ...] = (
            decode_buckets_from_env() if self._stepper is not None else ()
        )
        self.session_cache = (
            _session.make_session_cache()
            if self._stepper is not None else _session.DISABLED
        )
        self._compile_lock = threading.Lock()
        # weights state: swapped atomically under _swap_lock; infer()
        # snapshots (params, state, generation) once per call so a swap
        # mid-stream never mixes generations within one batch
        self._swap_lock = threading.Lock()
        self.generation = 0
        self.weights_source: Optional[str] = None
        self.warmup_s: Optional[float] = None
        # previous installed generation, kept resident for O(1)
        # recompile-free rollback (deploy/rollback.py): post-install
        # trees + fingerprint, one level deep
        self._resident_prev: Optional[Dict[str, Any]] = None
        self.rolled_back_from: Optional[str] = None
        self._swap_file_count = 0
        self._install(params, state)

    # ------------------------------------------------------------------
    def _install(self, params, state) -> None:
        """Normalize + publish a weight set (init and swap share this):
        device arrays in, fingerprint recomputed — a structural change
        (different arch) changes the executable-cache key, so stale
        executables are unreachable by construction.

        Quantized modes transform here, at install time — which for a
        ``swap_from_file`` means scales are captured from the verified
        snapshot at hot-swap time, never cached across generations.  A
        host-side f32 reference of the incoming tree is kept so the
        next file swap merges onto full-precision weights, not onto a
        quantized tree."""
        if self.quant != "f32":
            self._ref_params = jax.device_get(params)
            self._ref_state = jax.device_get(state)
            if self.quant == "int8":
                params = _quantize.quantize_tree(self.net, params)
            else:
                params = _quantize.bf16_tree(params)
        if self._mesh is not None:
            # per-leaf rule-table placement: the SAME sharding trees a
            # training run with this layout uses (recomputed per swap —
            # an arch change reshapes the trees)
            lay = self.layout
            self._params_sh = self._partition.sharding_tree(
                params, lay.rules, self._mesh, lay.validate
            )
            self._state_sh = self._partition.sharding_tree(
                state, lay.rules, self._mesh, lay.validate
            )
            params = self._partition.place(params, self._params_sh)
            state = self._partition.place(state, self._state_sh)
        else:
            to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
            params, state = to_dev(params), to_dev(state)
        self.fingerprint = net_fingerprint(
            self.net, params, state, self.compute_dtype,
            layout=self.layout, quant=self.quant,
        )
        self.params = params
        self.state = state

    def swap(
        self, params, state, *, source: Optional[str] = None
    ) -> int:
        """Hot-swap the served weights; returns the new generation.
        Atomic: in-flight ``infer`` calls finish on the snapshot they
        took; the next call serves the new weights.  Same-arch swaps
        reuse every compiled executable (weights are arguments); an
        arch change re-keys the cache (and pays compiles — warm them
        via :meth:`warmup` before routing traffic)."""
        with self._swap_lock:
            # retain the outgoing generation resident: rollback is
            # then a pure pointer exchange — no file I/O, no
            # re-quantize, no recompile (weights are arguments)
            self._resident_prev = {
                "params": self.params,
                "state": self.state,
                "fingerprint": self.fingerprint,
                "weights_source": self.weights_source,
                "ref_params": getattr(self, "_ref_params", None),
                "ref_state": getattr(self, "_ref_state", None),
                "params_sh": getattr(self, "_params_sh", None),
                "state_sh": getattr(self, "_state_sh", None),
            }
            self._install(params, state)
            self.generation += 1
            self.weights_source = source
            gen = self.generation
        if self.metrics is not None:
            self.metrics.record_hot_swap(gen)
        return gen

    def rollback(self) -> int:
        """Swap back to the resident previous generation — O(1) and
        recompile-free (the retained trees were installed once
        already; the compile cache keys on their fingerprint).  One
        level deep and consumed on use: a second rollback without an
        intervening swap raises, which is what makes a double
        burn-fire roll back exactly once."""
        with self._swap_lock:
            prev = self._resident_prev
            if prev is None:
                raise ValueError(
                    "rollback: no previous generation resident"
                )
            self._resident_prev = None
            self.rolled_back_from = self.weights_source
            self.params = prev["params"]
            self.state = prev["state"]
            self.fingerprint = prev["fingerprint"]
            self.weights_source = prev["weights_source"]
            if prev["ref_params"] is not None:
                self._ref_params = prev["ref_params"]
                self._ref_state = prev["ref_state"]
            if prev["params_sh"] is not None:
                self._params_sh = prev["params_sh"]
                self._state_sh = prev["state_sh"]
            self.generation += 1
            gen = self.generation
        if self.metrics is not None:
            self.metrics.record_hot_swap(gen)
        return gen

    def swap_from_file(self, weights: str) -> int:
        """Load + verify + swap from any weights artifact.  Snapshot
        files are manifest-verified by the loader (PR 3): a torn file
        raises before the swap, so the old generation keeps serving.
        Quantized engines merge onto the retained f32 reference tree
        (never onto int8/bf16 leaves) and re-capture scales in
        ``_install``.

        With ``SPARKNET_DEPLOY_GATE`` on, solverstate snapshots must
        additionally carry a *pass* gate verdict matching the file's
        current digest and not be in the ineligibility ledger
        (deploy/gate.py) — otherwise :class:`DeployGateError` raises
        here and the HTTP layer answers 409.  Manifest verification
        alone is no longer a license to serve."""
        if ".solverstate." in os.path.basename(weights):
            from ..deploy import gate as _gate

            if _gate.gate_required():
                _gate.require_eligible(weights)
        if self.quant != "f32":
            base_params, base_state = self._ref_params, self._ref_state
        else:
            base_params, base_state = self.params, self.state
        params, state = load_weights_any(
            self.net, base_params, base_state, weights
        )
        # deploy.regressed_weights chaos: scale one leaf AFTER the
        # gate saw clean bytes — the silent post-gate regression the
        # rollback watch exists to catch
        from .. import chaos as _chaos

        plan = _chaos.get_plan()
        rule = plan.match(
            "deploy.regressed_weights", index=self._swap_file_count
        ) if plan else None
        self._swap_file_count += 1
        if rule:
            # scale HALF the units of the first weight matrix: a
            # uniform scale would be argmax-invariant (ReLU is
            # positively homogeneous), but a lopsided one reliably
            # moves top-1 answers — a detectable live regression
            frac = float(rule.params.get("frac", 8.0))
            leaves, treedef = jax.tree_util.tree_flatten(params)
            for i, leaf in enumerate(leaves):
                arr = np.array(leaf)
                if arr.ndim < 2:
                    continue
                arr[..., : max(1, arr.shape[-1] // 2)] *= frac
                leaves[i] = arr
                params = jax.tree_util.tree_unflatten(treedef, leaves)
                break
        return self.swap(params, state, source=weights)

    def _weights_snapshot(self):
        with self._swap_lock:
            return (
                self.params, self.state, self.generation, self.fingerprint
            )

    # ------------------------------------------------------------------
    @classmethod
    def from_files(
        cls, model: str, weights: Optional[str] = None, **kwargs
    ) -> "InferenceEngine":
        """Build from a deploy prototxt path plus optional weights
        (``.caffemodel`` / ``.npz`` / ``.solverstate.npz``)."""
        from ..nets.xlanet import XLANet
        from ..proto import caffe_pb

        net_param = caffe_pb.load_net(model)
        net = XLANet(net_param, "TEST")
        params, state = net.init(jax.random.PRNGKey(0))
        if weights:
            params, state = load_weights_any(net, params, state, weights)
        eng = cls(net, params, state, **kwargs)
        if weights:
            eng.weights_source = weights
        return eng

    # ------------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (the padding target); the largest
        bucket when n exceeds it (the caller then chunks)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _input_dtype(self, name: str):
        return jnp.int32 if name == "label" else self.compute_dtype

    def _fwd(self, params, state, batch):
        if self.quant == "int8":
            blobs, _ = _quantize.apply_int8(self.net, params, state, batch)
        else:
            blobs, _ = self.net.apply(
                params, state, batch, train=False, rng=None
            )
        return blobs[self.output]

    def _executable(self, bucket: int, weights=None):
        """The compiled program for ``bucket``, against a consistent
        (params, state, fingerprint) triple — the caller's snapshot, or
        the engine's current weights."""
        params, state, _, fingerprint = (
            weights if weights is not None else self._weights_snapshot()
        )
        key = (fingerprint, bucket, jnp.dtype(self.compute_dtype).name)
        exe = self._cache.get(key)
        if exe is not None:
            return exe
        with self._compile_lock:
            exe = self._cache.get(key)
            if exe is not None:
                return exe
            structs = {
                name: jax.ShapeDtypeStruct(
                    (bucket,) + self._row_shapes[name], self._input_dtype(name)
                )
                for name in self.input_names
            }
            shape_of = lambda t: jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t
            )
            # nothing is donated: params/state are the resident weights,
            # and the batch cannot alias the (smaller) output — on the
            # chip XLA answered "donated buffers were not usable" for
            # every bucket
            jit_kw: Dict[str, Any] = {}
            if self._mesh is not None:
                jit_kw["in_shardings"] = (
                    self._params_sh, self._state_sh,
                    self._bucket_sharding(bucket),
                )
            exe = (
                jax.jit(self._fwd, **jit_kw)
                .lower(shape_of(params), shape_of(state), structs)
                .compile()
            )
            self._cache[key] = exe
        return exe

    def _bucket_sharding(self, bucket: int):
        """Request rows shard over the layout's batch axis when the
        bucket divides it; small buckets stay replicated (a bucket-1
        request can't split)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        dp = self.layout.batch_axis
        ndp = self._mesh.shape.get(dp, 1)
        spec = P(dp) if ndp > 1 and bucket % ndp == 0 else P()
        return NamedSharding(self._mesh, spec)

    def warmup(self) -> "InferenceEngine":
        """Compile every bucket up front, so the first request of each
        size never pays a compile inside its latency budget.  Timed
        into ``warmup_s`` — with the persistent compile cache enabled
        (``serve/compile_cache.py``) a warm restart deserializes
        instead of compiling, and this number is the proof.  Recurrent
        nets warm the decode step instead: their serving surface is
        ``generate``, and bucketed sequence forwards would compile
        programs sessions never run."""
        t0 = time.perf_counter()
        if self._stepper is not None:
            self._step_executable()
            from .batcher import decode_batching_enabled

            if decode_batching_enabled():
                self._warm_decode_ladder()
        else:
            for b in self.buckets:
                self._executable(b)
        self.warmup_s = round(time.perf_counter() - t0, 3)
        return self

    def _warm_decode_ladder(self) -> None:
        """Compile AND run one throwaway step at every batched-decode
        width.  A window that forms at a width nobody warmed would pay
        the compile — and the first-execution runtime init, ~2 orders
        above steady state — inside live rows' latency budgets.
        Side-effect free: touches no session cache or metrics."""
        if self._stepper is None:
            return
        weights = self._weights_snapshot()
        params, state, _, _ = weights
        stepper = self._stepper
        for w in self.decode_buckets:
            exe = self._step_executable(w, weights)
            tok = jnp.zeros(
                (w,) + stepper.row_shape, jnp.dtype(stepper.token_dtype)
            )
            out, _ = exe(params, state, stepper.init_carry(w), tok)
            jax.block_until_ready(out)

    # ------------------------------------------------------------------
    def _as_batch(self, rows: Rows) -> Dict[str, np.ndarray]:
        if not isinstance(rows, dict):
            rows = {self.input_names[0]: rows}
        batch = {}
        n = None
        for name, arr in rows.items():
            if name not in self._row_shapes:
                continue  # extra blobs the net doesn't take
            arr = np.asarray(arr)
            want = self._row_shapes[name]
            if tuple(arr.shape[1:]) != want:
                raise ValueError(
                    f"input {name!r}: rows shaped {tuple(arr.shape[1:])}, "
                    f"net wants {want}"
                )
            if n is None:
                n = len(arr)
            elif len(arr) != n:
                raise ValueError(
                    f"input {name!r}: {len(arr)} rows, others have {n}"
                )
            batch[name] = arr
        if n is None or n == 0:
            raise ValueError("infer: empty request")
        # inputs the caller omitted (e.g. 'label' on a TEST-phase net
        # whose requested output doesn't depend on it) ride as zeros
        for name in self.input_names:
            if name not in batch:
                batch[name] = np.zeros(
                    (n,) + self._row_shapes[name],
                    jnp.dtype(self._input_dtype(name)).name,
                )
        return batch

    def infer(self, rows: Rows) -> np.ndarray:
        """Run the net on ``rows``; see :meth:`infer_tagged`."""
        return self.infer_tagged(rows)[0]

    def infer_tagged(self, rows: Rows) -> Tuple[np.ndarray, int]:
        """Run the net on ``rows`` (an (N, ...) array for the first
        input, or a dict blob name -> (N, ...) array). Requests are
        padded up to the nearest bucket; N beyond the largest bucket is
        chunked. Returns ``(output rows, weights generation)`` — the
        generation the WHOLE call was computed with (one snapshot per
        call, so a concurrent swap never splits a request)."""
        batch = self._as_batch(rows)
        weights = self._weights_snapshot()
        params, state, gen, _ = weights
        n = len(next(iter(batch.values())))
        max_b = self.buckets[-1]
        outs = []
        start = 0
        while start < n:
            take = min(n - start, max_b)
            bucket = self.bucket_for(take)
            dev = {}
            for name, arr in batch.items():
                chunk = arr[start : start + take]
                if take < bucket:
                    pad = np.zeros(
                        (bucket - take,) + chunk.shape[1:], chunk.dtype
                    )
                    chunk = np.concatenate([chunk, pad])
                dev[name] = jnp.asarray(chunk, self._input_dtype(name))
            if self._mesh is not None:
                # AOT executables take inputs exactly as compiled: the
                # request batch must land pre-sharded on the mesh
                bsh = self._bucket_sharding(bucket)
                dev = {
                    name: jax.device_put(a, bsh) for name, a in dev.items()
                }
            exe = self._executable(bucket, weights)
            t0 = time.perf_counter()
            with _trace.span("serve.infer", cat="serve",
                             bucket=bucket, rows=take,
                             padded=bucket - take, gen=gen):
                # np.asarray is the device fence
                out = np.asarray(exe(params, state, dev))
            if self.metrics is not None:
                self.metrics.record_batch(
                    bucket,
                    rows=take,
                    padded_rows=bucket - take,
                    device_s=time.perf_counter() - t0,
                )
            outs.append(out[:take])
            start += take
        return (outs[0] if len(outs) == 1 else np.concatenate(outs)), gen

    # ------------------------------------------------- sessions / decode
    def _step_executable(self, n: int = 1, weights=None):
        """The compiled single-token decode step for ``n`` parallel
        session rows (``serve/session.py``) — ``step(params, state,
        carry, token)`` with the carry donated, AOT-compiled once per
        (fingerprint, n).  The same key discipline as
        the bucketed cache: a hot-swap of the same arch reuses it (a
        pointer exchange), an arch change re-keys it."""
        params, state, _, fingerprint = (
            weights if weights is not None else self._weights_snapshot()
        )
        key = (fingerprint, int(n))
        exe = self._step_cache.get(key)
        if exe is not None:
            return exe
        with self._compile_lock:
            exe = self._step_cache.get(key)
            if exe is not None:
                return exe
            stepper = self._stepper
            shape_of = lambda t: jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype), t
            )
            token_struct = jax.ShapeDtypeStruct(
                (n,) + stepper.row_shape, jnp.dtype(stepper.token_dtype)
            )
            # donate the carry (arg 2): the step's output carry
            # supersedes it — the session-state pointer exchange
            exe = (
                jax.jit(stepper.step_fn, donate_argnums=(2,))
                .lower(
                    shape_of(params), shape_of(state),
                    shape_of(stepper.init_carry(n)), token_struct,
                )
                .compile()
            )
            self._step_cache[key] = exe
        return exe

    def _decode_prep(self, tokens, steps: int):
        """Canonicalize + validate a decode request's (tokens, steps) —
        the shared gate of :meth:`generate` and :meth:`decode_batch`
        (identical errors on both paths, so the A/B flag never changes
        what a bad request sees)."""
        if self._stepper is None:
            raise ValueError(
                "generate: model has no recurrent layer — serve a "
                "decoder net (e.g. char_rnn_deploy.prototxt)"
            )
        stepper = self._stepper
        if stepper.vocab is not None:
            tokens = np.asarray(tokens, np.int64).ravel()
            if tokens.size and not (
                (0 <= tokens).all() and (tokens < stepper.vocab).all()
            ):
                raise ValueError(
                    f"generate: token ids out of range "
                    f"[0, {stepper.vocab})"
                )
            tokens = tokens.astype(np.int32)
        else:
            tokens = np.asarray(tokens, jnp.dtype(self.compute_dtype).name)
            tokens = tokens.reshape((-1,) + stepper.row_shape)
        steps = int(steps)
        if tokens.size == 0:
            raise ValueError("generate: empty token prefix")
        if steps < 0:
            raise ValueError(f"generate: steps must be >= 0, got {steps}")
        if steps and stepper.vocab is None:
            raise ValueError(
                "generate: steps>0 needs a token-id net (Embed input) "
                "to feed generated ids back"
            )
        return tokens, steps

    def generate(
        self,
        tokens,
        *,
        session: Optional[str] = None,
        steps: int = 0,
        top_k: int = 5,
    ) -> Dict[str, Any]:
        """Multi-step autoregressive decode — the session-aware serving
        entry point (``POST /generate``).

        ``tokens``: the session's FULL token prefix (requests are
        self-contained; the cache is an optimization, never a
        correctness dependency).  ``session``: a session id — with one,
        the per-session carry cache skips the already-processed prefix
        (O(new tokens) instead of O(prefix)); without one (or on any
        miss) the prefix replays through the same compiled step, so hit
        and cold answers are bit-identical by construction.  ``steps``:
        how many tokens to greedy-decode beyond the prefix.

        Returns one JSON-able dict: generated ``tokens``, final-step
        ``indices``/``probs`` (top-k), the weights ``gen``,
        ``cache_state`` (hit/cold/stale_gen/rebuilt/disabled),
        ``session_tokens`` (prefix incorporated so far) and
        ``steps_run`` (tokens actually stepped — the O(1)-vs-O(prefix)
        cost, observable per response)."""
        tokens, steps = self._decode_prep(tokens, steps)
        stepper = self._stepper
        weights = self._weights_snapshot()
        params, state, gen, fingerprint = weights
        cache = self.session_cache
        carry = None
        done = 0
        out = None
        cache_state = "cold" if session is None else None
        if session is not None:
            # pointer-exchange: take POPS the entry (its carry may be
            # donated to the step below); put publishes the successor.
            entry, cache_state = cache.take(
                fingerprint, session, gen, tokens
            )
            if entry is not None:
                carry, done, out = entry.carry, entry.tokens.size, (
                    entry.last_out
                )
        if carry is None:
            carry = stepper.init_carry(1)
        exe = self._step_executable(1, weights)
        t0 = time.perf_counter()
        suffix = tokens[done:]
        n_new = int(
            len(suffix) if stepper.vocab is not None else suffix.shape[0]
        )
        with _trace.span("serve.generate", cat="serve",
                         session=session or "", gen=gen,
                         cache_state=cache_state, steps=steps,
                         prefix=int(tokens.shape[0]), new=n_new):
            for i in range(n_new):
                tok = jnp.asarray(
                    suffix[i : i + 1], jnp.dtype(stepper.token_dtype)
                ).reshape((1,) + stepper.row_shape)
                out, carry = exe(params, state, carry, tok)
            generated: list = []
            for _ in range(steps):
                nxt = int(np.argmax(np.asarray(out)[0]))
                generated.append(nxt)
                out, carry = exe(
                    params, state, carry,
                    jnp.asarray([nxt], jnp.int32),
                )
        device_s = time.perf_counter() - t0
        if stepper.vocab is not None and generated:
            all_tokens = np.concatenate(
                [tokens, np.asarray(generated, np.int32)]
            )
        else:
            all_tokens = tokens
        # np.asarray doubles as the device fence before publication
        out_host = np.asarray(out)
        if session is not None:
            cache.put(
                fingerprint, session, gen, all_tokens, carry, out_host
            )
        if self.metrics is not None:
            self.metrics.record_batch(
                1, rows=1, padded_rows=0, device_s=device_s
            )
        idx, probs = self.postprocess(out_host, top_k)
        return {
            "tokens": [int(t) for t in generated],
            "indices": idx[0].tolist(),
            "probs": probs[0].tolist(),
            "gen": gen,
            "cache_state": cache_state,
            "session_tokens": int(all_tokens.shape[0]),
            "steps_run": n_new + len(generated),
        }

    # ----------------------------------------- continuous batched decode
    def decode_batch(
        self,
        requests: Sequence[Dict[str, Any]] = (),
        *,
        admit=None,
        on_result=None,
    ) -> List[Any]:
        """Continuous token-level batched decode (ISSUE 17): K live
        sessions advance one token per dispatch through ONE batched
        step executable, with admission and retirement at step
        boundaries — PR 9's continuous batcher at token granularity.

        ``requests``: dicts with ``tokens`` (full prefix), optional
        ``session`` / ``steps`` / ``top_k`` / ``deadline`` (absolute
        ``perf_counter`` time) / ``tag`` (opaque, handed back through
        ``on_result``).  ``admit(free_slots)``: polled at every step
        boundary for late arrivals (return an iterable of request
        dicts; ``None``/empty when nothing is waiting).  ``on_result
        (tag, value)``: called the moment a row retires — ``value`` is
        the :meth:`generate`-shaped payload, or an exception
        (``ValueError`` for bad requests, ``DeadlineExceeded`` for
        per-token deadline sheds).  Returns the values in request-
        intake order for direct callers.

        Semantics, per row, are exactly :meth:`generate`: cache take at
        admission, cold prefix replay as batch rows, greedy decode,
        cache put at retirement.  Rows are padded up to the smallest
        width in :attr:`decode_buckets` (floor 4 — width 1 compiles to
        ulp-different fusion on CPU; widths >= 4 are mutually bitwise
        row-independent, so per-row answers never depend on batch
        occupancy).  Fairness is structural: every live row advances
        exactly one token per dispatch, so a hot Zipf session cannot
        starve the rest.  A second row for a session already live in
        the window is **coalesced**: deferred until the live row
        retires (whose ``put`` publishes the carry the deferred row
        then takes as a hit) — ``take`` POPS, so admitting both would
        silently rebuild the later row from its prefix.  Padded slots
        are never rows: they appear in no response's ``steps_run`` /
        ``session_tokens`` and only in the occupancy gauges.  One
        weights snapshot covers the whole window (a hot-swap lands at
        the next window, same discipline as ``infer_tagged``)."""
        from .batcher import DeadlineExceeded

        if self._stepper is None:
            raise ValueError(
                "decode_batch: model has no recurrent layer — serve a "
                "decoder net (e.g. char_rnn_deploy.prototxt)"
            )
        stepper = self._stepper
        weights = self._weights_snapshot()
        params, state, gen, fingerprint = weights
        cache = self.session_cache
        max_w = self.decode_buckets[-1]
        pending = deque(requests)
        ordered: List[Any] = []
        live: List[_DecodeRow] = []
        active: Dict[str, _DecodeRow] = {}
        deferred: Dict[str, deque] = {}

        def finish(slot, tag, value):
            ordered[slot] = value
            if on_result is not None:
                on_result(tag, value)

        def release(session):
            """A session's live row left the window: admit the oldest
            coalesce-deferred request for it, if any."""
            active.pop(session, None)
            q = deferred.get(session)
            if q:
                activate(q.popleft())
                if not q:
                    deferred.pop(session, None)

        def retire(row: _DecodeRow) -> None:
            out_host = np.asarray(row.out)
            if row.generated and stepper.vocab is not None:
                all_tokens = np.concatenate(
                    [row.tokens, np.asarray(row.generated, np.int32)]
                )
            else:
                all_tokens = row.tokens
            if row.session is not None:
                cache.put(
                    fingerprint, row.session, gen, all_tokens,
                    row.carry, out_host,
                )
            idx, probs = self.postprocess(out_host, row.top_k)
            finish(row.slot, row.tag, {
                "tokens": [int(t) for t in row.generated],
                "indices": idx[0].tolist(),
                "probs": probs[0].tolist(),
                "gen": gen,
                "cache_state": row.cache_state,
                "session_tokens": int(all_tokens.shape[0]),
                "steps_run": row.steps_run,
            })
            if self.metrics is not None:
                self.metrics.record_decode_done(retired=1)
            if row.session is not None:
                release(row.session)

        def activate(req: Dict[str, Any]) -> None:
            """Build the row (cache take, carry init) and admit it —
            or retire it on the spot when a hit already covers the
            whole request (full prefix cached, steps=0)."""
            session = req.get("session")
            tokens, steps = req["_tokens"], req["_steps"]
            carry = None
            done = 0
            out = None
            cache_state = "cold" if session is None else None
            if session is not None:
                entry, cache_state = cache.take(
                    fingerprint, session, gen, tokens
                )
                if entry is not None:
                    carry, done, out = (
                        entry.carry, entry.tokens.size, entry.last_out
                    )
            if carry is None:
                carry = stepper.init_carry(1)
            row = _DecodeRow(
                tag=req.get("tag", req["_slot"]), slot=req["_slot"],
                session=None if session is None else str(session),
                tokens=tokens, steps=steps,
                top_k=int(req.get("top_k", 5)),
                deadline=req.get("deadline"),
                carry=carry, out=out, pos=done, cache_state=cache_state,
            )
            if session is not None and cache.enabled:
                active[row.session] = row
            if row.finished():
                retire(row)
            else:
                live.append(row)

        def intake(req) -> None:
            req = dict(req)
            req["_slot"] = len(ordered)
            ordered.append(None)
            req.setdefault("tag", req["_slot"])
            try:
                req["_tokens"], req["_steps"] = self._decode_prep(
                    req.get("tokens"), req.get("steps", 0)
                )
            except (ValueError, TypeError) as e:
                finish(req["_slot"], req["tag"], e)
                return
            session = req.get("session")
            if (
                session is not None and cache.enabled
                and str(session) in active
            ):
                # coalesce: the SAME session is already a live row and
                # take POPS — defer until its put republishes the carry
                cache.note_coalesced()
                deferred.setdefault(str(session), deque()).append(req)
                return
            activate(req)

        def shed(slot, tag, session, waited) -> None:
            finish(slot, tag, DeadlineExceeded(
                f"decode row expired mid-window "
                f"(deadline passed {waited:.3f}s ago)"
            ))
            if self.metrics is not None:
                self.metrics.record_decode_done(shed=1)
            if session is not None:
                release(session)

        dispatches = 0
        # the batched carry stays RESIDENT across dispatches: `order`
        # names the rows whose carries live in ``carry_b`` (slot-
        # aligned); a row's ``carry`` is None while resident.  Restack
        # happens only when membership or width changes — steady-state
        # steps feed the device tree straight back in, instead of
        # paying an unstack + concatenate per token.
        carry_b = None
        order: List[_DecodeRow] = []
        width = 0

        def materialize(row: _DecodeRow) -> None:
            """Pull a resident row's per-row carry out of the batched
            tree (lazily: membership changes and retirements only)."""
            if row.carry is None:
                i = order.index(row)
                row.carry = {
                    k: tuple(a[i : i + 1] for a in tup)
                    for k, tup in carry_b.items()
                }

        while True:
            now = time.perf_counter()
            # (a) per-token deadline shedding at the step boundary
            expired = [
                r for r in live
                if r.deadline is not None and now > r.deadline
            ]
            for r in expired:
                live.remove(r)
                shed(r.slot, r.tag, r.session, now - r.deadline)
            for sid in list(deferred):
                q = deferred.get(sid) or ()
                for req in [
                    r for r in q
                    if r.get("deadline") is not None
                    and now > r["deadline"]
                ]:
                    q.remove(req)
                    shed(req["_slot"], req["tag"], None,
                         now - req["deadline"])
                if sid in deferred and not deferred[sid]:
                    deferred.pop(sid)
            # (b) step-boundary admission: queued requests first, then
            # the caller's admit hook (the batcher's queue drain)
            while pending and len(live) < max_w:
                intake(pending.popleft())
            if admit is not None and len(live) < max_w:
                for req in admit(max_w - len(live)) or ():
                    intake(req)
            if not live:
                if pending:
                    continue
                break
            # (c) one batched step: every live row advances ONE token
            n = len(live)
            w = next(b for b in self.decode_buckets if b >= n)
            if carry_b is None or w != width or live != order:
                # membership or width changed: restack once.  Resident
                # rows are materialized from the old batched tree by
                # their old slot; newcomers already carry their own.
                for r in live:
                    materialize(r)
                parts = [r.carry for r in live]
                if w > n:
                    parts.append(stepper.init_carry(w - n))
                carry_b = {
                    k: tuple(
                        jnp.concatenate([p[k][j] for p in parts])
                        for j in range(len(parts[0][k]))
                    )
                    for k in parts[0]
                }
                width = w
            tok_np = np.zeros(
                (width,) + stepper.row_shape,
                jnp.dtype(stepper.token_dtype).name,
            )
            for i, row in enumerate(live):
                if row.pos < row.n_prefix:
                    tok_np[i] = row.tokens[row.pos]
                    row.pos += 1
                else:
                    nxt = int(np.argmax(np.asarray(row.out)[0]))
                    row.generated.append(nxt)
                    tok_np[i] = nxt
                row.steps_run += 1
            exe = self._step_executable(width, weights)
            t0 = time.perf_counter()
            with _trace.span("serve.decode_batch", cat="serve",
                             width=width, rows=n, padded=width - n,
                             gen=gen, dispatch=dispatches):
                out_b, carry_b = exe(
                    params, state, carry_b, jnp.asarray(tok_np)
                )
                jax.block_until_ready(out_b)  # the device fence
            if self.metrics is not None:
                self.metrics.record_decode_step(
                    width, rows=n, padded_rows=width - n,
                    device_s=time.perf_counter() - t0,
                )
            dispatches += 1
            # (d) one host transfer for the whole window; rows go
            # carry-resident (their state lives in ``carry_b`` until a
            # membership change or their own retirement pulls it out)
            out_host = np.asarray(out_b)
            order = list(live)
            for i, row in enumerate(live):
                row.out = out_host[i : i + 1]
                row.carry = None
            # retire finished rows (their put may release a coalesce-
            # deferred row into the window)
            done_rows = [r for r in live if r.finished()]
            for r in done_rows:
                materialize(r)
                live.remove(r)
            for r in done_rows:
                retire(r)
        return ordered

    # ------------------------------------------------------------------
    def postprocess(self, out: np.ndarray, top_k: int = 5):
        """Output-blob rows -> (indices (N, k), probs (N, k)); softmax
        applied here iff the net did not already end in one."""
        out = np.asarray(out, np.float64).reshape(len(out), -1)
        if not self.output_is_prob:
            out = np.exp(out - out.max(-1, keepdims=True))
            out = out / out.sum(-1, keepdims=True)
        idx = np.argsort(-out, axis=-1)[:, :top_k]
        return idx, np.take_along_axis(out, idx, axis=-1)

    def topk(self, rows: Rows, top_k: int = 5):
        """infer + postprocess — the classification entry point the
        classify tool and the HTTP server share."""
        return self.postprocess(self.infer(rows), top_k)
