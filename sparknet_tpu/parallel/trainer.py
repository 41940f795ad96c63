"""ParallelSolver: the multi-chip training driver.

Plays the role of the reference's Spark driver program (SURVEY.md §1-3:
broadcast -> mapPartitions(train) -> reduce/average; mount empty, no
file:line), with the driver logic compiled away: placement is a mesh
sharding, broadcast is replication, and the average is an in-program
collective.  Two modes:

- ``mode="sync"``  — one global batch per iteration, gradient
  all-reduce inside the step (modern synchronous DP; the better
  default on a TPU pod where ICI makes sync cheap).
- ``mode="local"`` — SparkNet's τ-local SGD: each mesh ``dp`` slice
  runs τ independent steps, then weights are averaged.  The τ knob
  reproduces the paper's communication/staleness tradeoff — and with
  ``tau="auto"`` becomes a telemetry-driven control loop
  (:mod:`.tau_controller`).

Communication in both modes routes through :mod:`.comm` (bucketed
reduction, optional bf16/int8 compression with error-feedback
residuals in opt state); ``SPARKNET_COMM=monolithic`` restores the
pre-bucketing fused all-reduce as the A/B baseline.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from ..proto import caffe_pb
from ..solver.trainer import Solver
from . import comm as comm_mod
from .data_parallel import make_dp_eval_step, make_dp_train_step
from .local_sgd import (
    RESIDUAL_KEY,
    RoundBuffer,
    init_local_opt_state,
    init_local_residual,
    make_local_scan,
    make_local_sgd_round,
    make_round_reduce,
    round_batch_sharding,
    stack_round_batches,
)
from ..telemetry import anomaly as _anomaly
from .mesh import DP_AXIS, batch_sharding, make_mesh, replicate
from .tau_controller import TauController, parse_tau
from . import multihost
from . import partition as partition_mod


class ParallelSolver(Solver):
    def __init__(
        self,
        solver: caffe_pb.SolverParameter,
        input_shapes: Dict[str, Tuple[int, ...]],
        *,
        mesh: Optional[jax.sharding.Mesh] = None,
        mode: str = "sync",
        tau=1,
        dp_axis: str = DP_AXIS,
        comm_config: Optional[comm_mod.CommConfig] = None,
        layout: Optional[Any] = None,
        layout_rules: str = "auto",
        **kw: Any,
    ):
        """``layout``: a :class:`~sparknet_tpu.parallel.partition.Layout`
        (or a ``"dp=2,tp=2"`` axes string resolved against
        ``layout_rules`` — ``"auto"`` picks the ``"bert"`` ruleset for
        model-protocol nets and ``"tp"`` for prototxt nets).  With a
        layout, sync training compiles through the unified
        rule-table/NamedSharding path (parallel/partition.py): any
        dp×tp×ep combination is a table entry, no new step builder.
        ``mode="local"`` (τ-local SGD) and bucketed/compressed sync
        comm remain dp-only and accept only dp-shaped layouts."""
        if kw.get("batch_transform") is not None:
            # the parallel modes build their own train steps below,
            # which would silently drop the transform — reject, per the
            # base Solver's can't-believe-it-took-effect policy
            raise ValueError(
                "batch_transform (device-side augmentation) is not "
                "supported by ParallelSolver — use the base Solver"
            )
        if isinstance(layout, str):
            rules = layout_rules
            if rules == "auto":
                rules = "bert" if kw.get("model") is not None else "tp"
            layout = partition_mod.parse_layout(layout, rules=rules)
        self.layout: Optional[partition_mod.Layout] = layout
        self._plan: Optional[partition_mod.Plan] = None
        super().__init__(solver, input_shapes, **kw)
        if mesh is None:
            mesh = layout.mesh() if layout is not None else make_mesh()
        self.mesh = mesh
        self.mode = mode
        self.comm = (
            comm_config if comm_config is not None
            else comm_mod.resolve_config()
        )
        # recorded into the solverstate (Solver.save env_meta): resuming
        # under a different wire format warns through the existing
        # env-drift machinery, on top of the residual reconciliation
        self.env_meta["grad_compress"] = self.comm.compress
        tau0, tau_auto = parse_tau(tau)
        self.tau = int(tau0)
        self.tau_controller: Optional[TauController] = None
        if tau_auto:
            if mode == "sync":
                raise ValueError(
                    "tau='auto' drives local-SGD round length — it "
                    "needs mode='local' (--parallel local)"
                )
            self.tau_controller = TauController(tau=self.tau)
            self.tau = self.tau_controller.tau
        if mode != "sync" and self.tau > 1:
            # local-SGD materialises only per-round tau-means, so the
            # display window is in ROUNDS: ceil(average_loss / tau)
            # rounds ≈ the last average_loss iterations
            from collections import deque

            n_rounds = -(-max(1, solver.average_loss) // self.tau)
            self._loss_window = deque(maxlen=n_rounds)
        self.dp_axis = dp_axis
        ndp = self.mesh.shape.get(dp_axis, 1)
        for which, xnet in (("train", self.train_net), ("test", self.test_net)):
            for name in xnet.input_names:
                bs = xnet.blob_shapes[name][0]
                if bs % ndp:
                    raise ValueError(
                        f"{which} input {name!r}: batch {bs} not divisible "
                        f"by dp={ndp}"
                    )
        if self.layout is not None:
            non_dp = [
                f"{a}={s}" for a, s in self.mesh.shape.items()
                if a != dp_axis and s > 1
            ]
            if non_dp and mode == "local":
                raise ValueError(
                    "mode='local' (τ-local SGD averaging) is dp-only; "
                    f"layout has non-trivial axes {non_dp} — use "
                    "mode='sync' for model-parallel layouts"
                )
            if non_dp and (
                self.comm.for_sync() == "bucketed" if mode == "sync" else False
            ):
                raise ValueError(
                    "bucketed/compressed sync comm is an explicit dp "
                    f"shard_map program; layout axes {non_dp} need the "
                    "unified path — drop --grad-compress / "
                    "SPARKNET_COMM=bucketed"
                )
            if mode == "sync" and self.comm.for_sync() != "bucketed":
                self._plan = partition_mod.make_plan(
                    self.layout, self.params, self.state, solver,
                    mesh=self.mesh,
                )
            # snapshots carry the layout + per-leaf specs so a resume
            # under a different layout warns and relayouts explicitly;
            # a live reshard re-records both (reshard.py) so snapshots
            # taken after the migration carry the NEW layout
            self._record_layout_env()
        if self._plan is not None:
            self.params = partition_mod.place(
                self.params, self._plan.params_sh
            )
            self.state = partition_mod.place(self.state, self._plan.state_sh)
        else:
            self.params = replicate(self.params, self.mesh)
            self.state = replicate(self.state, self.mesh)
        # multi-host: each process feeds its local rows; _put_batch
        # assembles them into globally-sharded arrays
        self._multihost = jax.process_count() > 1
        if self._plan is not None:
            self._eval_sharding = self._plan.batch_eval_sh
            self._train_sharding = self._plan.batch_train_sh
        else:
            self._eval_sharding = batch_sharding(self.mesh, dp_axis)
            if solver.iter_size > 1:
                self._train_sharding = jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec(None, dp_axis)
                )
            else:
                self._train_sharding = self._eval_sharding
        if mode == "sync":
            if self._plan is not None:
                self.opt_state = partition_mod.place(
                    self.opt_state, self._plan.opt_sh
                )
                self._train_step = partition_mod.make_sharded_train_step(
                    self.train_net, solver, self._plan
                )
                self._eval_step = partition_mod.make_sharded_eval_step(
                    self.test_net, self._plan
                )
            else:
                self.opt_state = replicate(self.opt_state, self.mesh)
                if (
                    self.comm.for_sync() == "bucketed"
                    and self.comm.wants_residual
                ):
                    self.opt_state[RESIDUAL_KEY] = jax.device_put(
                        init_local_residual(self.params, ndp),
                        self._dp_sharding(),
                    )
                self._train_step = make_dp_train_step(
                    self.train_net, solver, self.mesh, dp_axis,
                    config=self.comm,
                )
                self._eval_step = make_dp_eval_step(
                    self.test_net, self.mesh, dp_axis
                )
            comm_mod.count_reduction(self.comm, self.params, "sync_grads")
        elif mode == "local":
            if self.tau < 1:
                raise ValueError(f"tau must be >= 1, got {self.tau}")
            opt_state = init_local_opt_state(solver, self.params, ndp)
            if (
                self.comm.for_local() == "bucketed"
                and self.comm.wants_residual
            ):
                opt_state[RESIDUAL_KEY] = init_local_residual(
                    self.params, ndp
                )
            self.opt_state = jax.device_put(opt_state, self._dp_sharding())
            # round fns keyed by effective tau: the last round of a
            # step(n) with n % tau != 0 runs a shorter compiled round
            # rather than overshooting n.  Bucketed rounds split into a
            # per-tau scan and ONE tau-independent reduce program.
            self._rounds: Dict[int, Any] = {}
            self._reduce_fn = (
                make_round_reduce(self.mesh, self.comm, dp_axis)
                if self.comm.for_local() == "bucketed" else None
            )
            self._round_buffer = RoundBuffer()
            self._batch_sharding = round_batch_sharding(
                self.mesh, dp_axis, solver.iter_size
            )
            self._eval_step = make_dp_eval_step(self.test_net, self.mesh, dp_axis)
            comm_mod.count_reduction(self.comm, self.params, "round_average")
        else:
            raise ValueError(f"mode {mode!r} (want 'sync' or 'local')")
        if self.tau_controller is not None and not self.timeline.enabled:
            # the controller's widen signal IS the timeline's sync share
            # — auto-tau implies attribution even without --trace
            from ..telemetry import timeline as _ttl

            self.timeline = _ttl.Timeline(fence=True)
            self.timeline.start()

    # ------------------------------------------------------------------
    def _record_layout_env(self) -> None:
        """(Re)write the snapshot env's layout + per-leaf specs from the
        solver's CURRENT layout — called at construction and again by
        every live reshard, so a snapshot always resumes into the
        layout the job was actually running."""
        if self.layout is None:
            return
        import json as _json

        self.env_meta["layout"] = partition_mod.layout_to_json(self.layout)
        specs = (
            self._plan.specs if self._plan is not None
            else partition_mod.specs_record(
                self.params, self.layout.rules, self.mesh
            )
        )
        self.env_meta["param_specs"] = _json.dumps(specs, sort_keys=True)

    def reshard(self, new_layout, *, reason: str = "explicit"):
        """Migrate this running solver to ``new_layout`` in place —
        see :func:`sparknet_tpu.parallel.reshard.reshard`."""
        from . import reshard as reshard_mod

        return reshard_mod.reshard(self, new_layout, reason=reason)

    def _dp_sharding(self):
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.dp_axis)
        )

    def layout_report(self) -> Optional[Dict[str, Any]]:
        """Machine-readable layout record for the apps' ``layout:``
        line: mesh shape, rule count, sharded/replicated leaf counts
        and the layout fingerprint (None without a layout)."""
        if self.layout is None:
            return None
        if self._plan is not None:
            out = self._plan.report()
            out["path"] = "unified"
            return out
        out = {
            "name": self.layout.name,
            "mesh": dict(self.mesh.shape),
            "rules": len(self.layout.rules),
            "fingerprint": partition_mod.layout_fingerprint(self.layout),
            "path": f"legacy-{self.mode}",
        }
        return out

    def _env_drift_message(self, key, saved, cur) -> str:
        if key == "param_specs":
            return ""  # the layout key carries the aggregated notice
        if key == "layout":
            import json as _json

            saved_name = "unknown"
            try:
                d = _json.loads(saved)
                saved_name = f"{d.get('name')}:{dict(d.get('axes') or [])}"
            except (TypeError, ValueError):
                pass
            cur_specs = (
                self._plan.specs if self._plan is not None
                else partition_mod.specs_record(
                    self.params, self.layout.rules, self.mesh
                )
            )
            saved_specs = str(
                (getattr(self, "_restored_env", None) or {}).get(
                    "param_specs", ""
                )
            )
            return partition_mod.relayout_warning(
                saved_specs,
                cur_specs,
                saved_layout=saved_name,
                current_layout=(
                    f"{self.layout.name}:{dict(self.mesh.shape)}"
                ),
            )
        return super()._env_drift_message(key, saved, cur)

    # ------------------------------------------------------------------
    def _put_batch(self, batch, train: bool = True):
        """sync mode: jit's in_shardings place single-host batches; with
        multiple processes each host contributes only its local rows, so
        the global array must be assembled explicitly."""
        if not self._multihost:
            return batch
        sharding = self._train_sharding if train else self._eval_sharding
        return multihost.put_global(batch, sharding)

    def _wants_residual(self) -> bool:
        active = (
            self.comm.for_local() if self.mode == "local"
            else self.comm.for_sync()
        )
        return active == "bucketed" and self.comm.wants_residual

    def _reconcile_residual(self, opt_state):
        """Snapshot <-> config drift: a pre-comm (or --grad-compress
        none) snapshot restored into a lossy run gets fresh zero
        residuals; a lossy snapshot restored into a lossless run drops
        them.  Either way the restore proceeds with a warning instead
        of a KeyError deep inside the compiled step."""
        wants, has = self._wants_residual(), RESIDUAL_KEY in opt_state
        if wants and not has:
            if jax.process_index() == 0:
                print(
                    "WARNING: snapshot carries no error-feedback "
                    "residuals (taken without --grad-compress?) — "
                    "starting compression from zero residuals",
                    file=sys.stderr, flush=True,
                )
            ndp = self.mesh.shape[self.dp_axis]
            opt_state = dict(opt_state)
            opt_state[RESIDUAL_KEY] = init_local_residual(self.params, ndp)
        elif has and not wants:
            if jax.process_index() == 0:
                print(
                    "WARNING: dropping the snapshot's error-feedback "
                    "residuals (--grad-compress is off in this run)",
                    file=sys.stderr, flush=True,
                )
            opt_state = {
                k: v for k, v in opt_state.items() if k != RESIDUAL_KEY
            }
        return opt_state

    def _place_restored(self, params, state, opt_state):
        if self._plan is not None:
            # relayout-on-resume: leaves land wherever the RUN's rule
            # table puts them, whatever the snapshot's layout was (the
            # env-drift hook prints the aggregated warning)
            if opt_state:
                opt_state = self._reconcile_residual(opt_state)
            return (
                partition_mod.place(params, self._plan.params_sh),
                partition_mod.place(state, self._plan.state_sh),
                partition_mod.place(opt_state, self._plan.opt_sh)
                if opt_state else opt_state,
            )
        params = replicate(params, self.mesh)
        state = replicate(state, self.mesh)
        if opt_state:
            opt_state = self._reconcile_residual(opt_state)
        if self.mode == "sync":
            resid = None
            if RESIDUAL_KEY in opt_state:
                opt_state = dict(opt_state)
                resid = opt_state.pop(RESIDUAL_KEY)
            opt_state = replicate(opt_state, self.mesh)
            if resid is not None:
                opt_state[RESIDUAL_KEY] = jax.device_put(
                    resid, self._dp_sharding()
                )
        else:  # local: per-dp-slice optimizer slots, sharded on dp
            opt_state = jax.device_put(opt_state, self._dp_sharding())
        return params, state, opt_state

    def _reinit_opt_state(self):
        """Elastic weights-only resume: a snapshot taken at a different
        dp width carries incompatible slot layouts (local mode's
        per-dp-slice leading axis) — rebuild fresh slots in THIS
        solver's layout instead."""
        from ..solver.caffe_solver import init_opt_state

        ndp = self.mesh.shape.get(self.dp_axis, 1)
        if self._plan is not None:
            return partition_mod.place(
                init_opt_state(self.sp, self.params), self._plan.opt_sh
            )
        if self.mode == "sync":
            opt = replicate(init_opt_state(self.sp, self.params), self.mesh)
            if self._wants_residual():
                opt[RESIDUAL_KEY] = jax.device_put(
                    init_local_residual(self.params, ndp),
                    self._dp_sharding(),
                )
            return opt
        opt = init_local_opt_state(self.sp, self.params, ndp)
        if self._wants_residual():
            opt[RESIDUAL_KEY] = init_local_residual(self.params, ndp)
        return jax.device_put(opt, self._dp_sharding())

    def _round_fn(self, tau: int):
        """Per-tau compiled round program: the monolithic one-dispatch
        round, or (bucketed) the scan half of the two-program round."""
        if tau not in self._rounds:
            if self._reduce_fn is not None:
                self._rounds[tau] = make_local_scan(
                    self.train_net, self.sp, self.mesh, tau, self.dp_axis
                )
            else:
                self._rounds[tau] = make_local_sgd_round(
                    self.train_net, self.sp, self.mesh, tau, self.dp_axis
                )
        return self._rounds[tau]

    def _next_iteration_batch(self, batches):
        """One iteration's worth of host batches (iter_size micro-batches
        stacked on a leading axis when accumulating, Caffe-style)."""
        if self.sp.iter_size > 1:
            # NO round buffer here: the tau outer stacks copy these
            # inner stacks only at round end, so inner reuse within a
            # round (tau > buffer depth) would alias live data
            return stack_round_batches(
                [next(batches) for _ in range(self.sp.iter_size)]
            )
        return next(batches)

    def _split_residual(self, opt_state):
        if RESIDUAL_KEY not in opt_state:
            return opt_state, {}
        return (
            {k: v for k, v in opt_state.items() if k != RESIDUAL_KEY},
            opt_state[RESIDUAL_KEY],
        )

    def comm_report(self) -> Dict[str, Any]:
        """Machine-readable communication record for the apps' ``comm:``
        line and run reports: the active config, the bucket plan over THIS
        model's params, and the tau controller's decision log when one
        is driving."""
        leaves = jax.tree_util.tree_leaves(self.params)
        plan = comm_mod.plan_buckets(leaves, self.comm.bucket_bytes)
        mode = (
            self.comm.for_local() if self.mode == "local"
            else self.comm.for_sync()
        )
        out = {
            "mode": mode,
            "compress": self.comm.compress,
            "bucket_mb": self.comm.bucket_mb,
            "buckets": comm_mod.bucket_histogram(plan, leaves),
            "wire_bytes_per_reduction": comm_mod.wire_bytes(
                plan if mode == "bucketed"
                else ((tuple(range(len(leaves))),) if leaves else ()),
                leaves, self.comm.compress,
            ),
        }
        if self.tau_controller is not None:
            out["tau_controller"] = self.tau_controller.snapshot()
        return out

    def _dispatch(self, batch):
        """Sync mode's iteration: the mesh program built at
        construction (``_train_step``, which a live reshard swaps)
        takes the step's key and the counter from the host, so it costs
        a split and a scalar placement more than the base's one
        dispatch.  Local mode never comes here: it has its own
        :meth:`step`."""
        self.rng, step_rng = jax.random.split(self.rng)
        self.params, self.state, self.opt_state, metrics = self._train_step(
            self.params, self.state, self.opt_state, batch,
            jnp.asarray(self.iter, jnp.int32), step_rng,
        )
        return metrics

    def lower_step(self, batch):
        """As :meth:`Solver.lower_step`, of sync mode's mesh program
        (kept for :meth:`Solver.step_scopes` as the base keeps its own:
        the partitioned module's instructions are one chip's).  Local
        mode has no program of one step, it dispatches rounds of tau:
        nothing is lowered, None comes back and ``step_scopes`` stays
        None."""
        if self.mode != "sync":
            self._lowered = None
            return None
        self._lowered = self._train_step.lower(
            self.params, self.state, self.opt_state,
            self._put_batch(batch), jnp.asarray(self.iter, jnp.int32),
            self.rng,
        )
        return self._lowered

    def step(self, batches: Iterator[Dict[str, Any]], n: int = 1, log_fn=None):
        if self.mode == "sync":
            return super().step(batches, n, log_fn)
        metrics: Dict[str, Any] = {}
        end = self.iter + n
        tl = self.timeline  # same phase brackets as Solver.step: one
        # local-SGD round = tau iterations in one compiled dispatch;
        # bucketed comm adds the round-end reduce as its own dispatch,
        # bracketed grad_allreduce so the EXPOSED reduction time reads
        # off the table separately from multihost_sync's barrier time
        controller = self.tau_controller
        while self.iter < end:
            if self.stop_requested:
                break
            tau = min(self.tau, end - self.iter)
            with tl.phase("input_wait"):
                stacked = stack_round_batches(
                    [self._next_iteration_batch(batches) for _ in range(tau)],
                    buffer=self._round_buffer,
                )
            with tl.phase("device_put"):
                if self._multihost:
                    stacked = multihost.put_global(
                        stacked, self._batch_sharding
                    )
                else:
                    stacked = jax.device_put(stacked, self._batch_sharding)
            phases0 = tl.phase_seconds() if controller is not None else None
            wall0 = tl.wall_s if controller is not None else 0.0
            self.rng, step_rng = jax.random.split(self.rng)
            prev = self.iter
            it_arr = jnp.asarray(self.iter, jnp.int32)
            if self._reduce_fn is not None:
                opt_solver, resid = self._split_residual(self.opt_state)
                with tl.phase("compiled_step"):
                    p_start, p_stack, st_stack, opt_out, metrics = (
                        self._round_fn(tau)(
                            self.params, self.state, opt_solver,
                            stacked, it_arr, step_rng,
                        )
                    )
                    if tl.fence:
                        jax.block_until_ready(metrics)
                with tl.phase("grad_allreduce"):
                    self.params, self.state, resid = self._reduce_fn(
                        p_start, p_stack, st_stack, resid
                    )
                    if tl.fence:
                        jax.block_until_ready(self.params)
                self.opt_state = (
                    {**opt_out, RESIDUAL_KEY: resid}
                    if self._wants_residual() else opt_out
                )
            else:
                with tl.phase("compiled_step"):
                    self.params, self.state, self.opt_state, metrics = (
                        self._round_fn(tau)(
                            self.params,
                            self.state,
                            self.opt_state,
                            stacked,
                            it_arr,
                            step_rng,
                        )
                    )
                    if tl.fence:
                        jax.block_until_ready(metrics)
            self.iter += tau
            if controller is not None:
                # host sync per round — the controller's price, only
                # paid under --tau auto (the loss is about to be fetched
                # for display smoothing anyway on display rounds)
                phases1 = tl.phase_seconds()
                sync_s = sum(
                    phases1.get(k, 0.0) - (phases0 or {}).get(k, 0.0)
                    for k in ("grad_allreduce", "multihost_sync")
                )
                # anomaly advisory hook: only consumed single-process —
                # straggler advisories live on rank 0's board, and a
                # multi-host run needs every rank to pick the same τ
                # (consuming rank-0-only signal would diverge them)
                advisories = (
                    _anomaly.active("straggler")
                    if multihost.process_count() == 1 else None
                )
                self.tau = controller.observe_round(
                    round_s=max(tl.wall_s - wall0, 1e-9),
                    sync_s=sync_s,
                    loss=float(metrics.get("loss", 0.0)),
                    advisories=advisories,
                )
            d = self.sp.display
            if log_fn and d:
                # round metrics are already tau-means; the window holds
                # ceil(average_loss/tau) rounds (sized in __init__), so
                # the display covers ≈ the last average_loss iterations
                self._push_loss(metrics)
                if (self.iter // d) > (prev // d):
                    log_fn(self.iter, self._smoothed(metrics))
        return metrics
