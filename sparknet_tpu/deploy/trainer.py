"""Incremental trainer: the training half of the closed loop.

``python -m sparknet_tpu.deploy.trainer`` is what the deploy
controller's ChildPool supervises (supervise/pool.py — crash =
respawn = resume): it consumes the tee's growing packed log and emits
manifest-verified solverstate candidates into the gate's watch
directory.

Resume is *exact*: the solver's iteration is the log position
(``iter * batch_size`` records consumed), so a restart restores the
newest verified solverstate and ``align_feed`` fast-forwards the
reopened log stream with shard-level O(1) ``skip(n)`` — no reread, no
drift.  Because the tee only ever APPENDS manifested shards and the
stream runs unshuffled, the first N batches of the grown log are
bit-identical to the same N batches of the shorter log, which makes
restart-vs-continuous training bitwise equal (pinned by test).
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, Optional

from .tee import CONSUMED_NAME, recover_log

DEFAULT_SOLVER_TXT = (
    "base_lr: {lr} momentum: 0.9 lr_policy: 'fixed' display: 0 "
    "max_iter: 1000000000"
)


class IncrementalTrainer:
    """Train-to-log-head loop over a tee log directory."""

    def __init__(
        self,
        log_dir: str,
        net: str,
        out_dir: str,
        *,
        prefix: str = "inc",
        batch_size: int = 16,
        base_lr: float = 0.05,
        solver_text: Optional[str] = None,
        init_weights: Optional[str] = None,
        seed: int = 0,
    ):
        self.log_dir = log_dir
        self.net = net
        self.out_dir = out_dir
        self.prefix = prefix
        self.batch_size = int(batch_size)
        self.base_lr = float(base_lr)
        self.solver_text = solver_text
        self.init_weights = init_weights
        self.seed = int(seed)
        self._solver = None
        os.makedirs(out_dir, exist_ok=True)

    # ------------------------------------------------- solver build

    @property
    def snapshot_prefix(self) -> str:
        return os.path.join(self.out_dir, self.prefix)

    def _build_solver(self, fields: Dict[str, Any]):
        from ..proto import caffe_pb
        from ..solver.trainer import Solver

        text = self.solver_text or DEFAULT_SOLVER_TXT.format(
            lr=self.base_lr
        )
        sp = caffe_pb.load_solver(text, is_path=False)
        shapes = {
            k: tuple([self.batch_size] + list(f.get("shape") or []))
            for k, f in fields.items()
        }
        solver = Solver(
            sp, shapes,
            net_param=caffe_pb.load_net(self.net),
            seed=self.seed,
        )
        solver.env_meta["deploy_log"] = os.path.abspath(self.log_dir)
        return solver

    def _restore_or_init(self, solver) -> None:
        from ..solver.snapshot import newest_verified_solverstate

        got = newest_verified_solverstate(self.snapshot_prefix)
        if got is not None:
            solver.restore(got[1])
            return
        if self.init_weights:
            # first generation trains FROM the serving weights, not
            # from random init — the candidate must beat/agree with
            # the baseline at the gate, so start there
            solver.load_weights(self.init_weights)

    # ------------------------------------------------- the loop body

    def run_once(self) -> Optional[str]:
        """Train from the current solver iteration to the current log
        head; save + return a candidate snapshot path when any new
        full batch was consumed, else None."""
        from ..data import records as rec
        from ..solver.snapshot import NPZ_SUFFIX

        recover_log(self.log_dir)
        if not os.path.exists(
            os.path.join(self.log_dir, rec.MANIFEST_NAME)
        ):
            return None
        ds = rec.PackedDataset(self.log_dir)
        head = ds.num_records // self.batch_size
        if self._solver is None:
            with open(
                os.path.join(self.log_dir, rec.MANIFEST_NAME)
            ) as fh:
                import json

                fields = json.load(fh).get("fields") or {}
            if not fields:
                return None
            self._solver = self._build_solver(fields)
            self._restore_or_init(self._solver)
        solver = self._solver
        if solver.iter >= head:
            return None
        # unshuffled stream + append-only log: batch k is the same
        # bytes no matter how much the log has grown since
        it = ds.batches(
            self.batch_size, shuffle=False, drop_remainder=True
        )
        solver.align_feed(it)
        solver.step(it, head - solver.iter)
        getattr(it, "close", lambda: None)()
        path = self.snapshot_prefix + f"_iter_{solver.iter}{NPZ_SUFFIX}"
        # disk-full degrades to skip-with-counter: training continues
        # and the NEXT head advance emits a candidate carrying this
        # learning; no candidate is better than a torn one
        if not solver.save_or_skip(path, prefix=self.snapshot_prefix):
            return None
        self._publish_consumed()
        return path

    def _publish_consumed(self) -> None:
        """Advertise the durable resume floor (records consumed as of
        the newest saved solverstate) into the log dir, best-effort —
        the tee's bounded-log retention (SPARKNET_DEPLOY_LOG_MB) only
        evicts shards wholly below this floor, so a restart can always
        skip() back to its resume point without touching them."""
        from ..utils import safeio

        if self._solver is None:
            return
        safeio.best_effort_write_json(
            os.path.join(self.log_dir, CONSUMED_NAME),
            {
                "records": int(self._solver.iter) * self.batch_size,
                "pid": os.getpid(),
                "t": time.time(),
            },
            site="records",
        )

    def follow(
        self,
        *,
        interval_s: float = 1.0,
        max_rounds: Optional[int] = None,
        on_candidate=None,
    ) -> int:
        """Poll the log and train forever (the supervised-child mode);
        returns the number of candidates emitted (bounded runs)."""
        emitted = 0
        rounds = 0
        while max_rounds is None or rounds < max_rounds:
            rounds += 1
            path = self.run_once()
            if path is not None:
                emitted += 1
                print(f"trainer: candidate {path}", flush=True)
                if on_candidate is not None:
                    on_candidate(path)
            else:
                time.sleep(interval_s)
        return emitted


def main(argv=None) -> int:
    from ..utils import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(
        prog="sparknet-deploy-trainer",
        description="incremental trainer over a deploy tee log",
    )
    ap.add_argument("--log-dir", required=True,
                    help="tee log directory (packed shard split)")
    ap.add_argument("--net", required=True,
                    help="TRAIN .prototxt (Input data/label + loss)")
    ap.add_argument("--out-dir", required=True,
                    help="candidate snapshot directory (the gate watches"
                         " this)")
    ap.add_argument("--prefix", default="inc")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--base-lr", type=float, default=0.05)
    ap.add_argument("--solver", default=None,
                    help="solver .prototxt path (default: inline fixed-"
                         "lr momentum solver)")
    ap.add_argument("--init-weights", default=None,
                    help="weights to start the first generation from "
                         "(the serving baseline)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--once", action="store_true",
                    help="one train-to-head round, then exit")
    ap.add_argument("--interval-s", type=float, default=1.0)
    ap.add_argument("--max-rounds", type=int, default=None)
    args = ap.parse_args(argv)

    solver_text = None
    if args.solver:
        with open(args.solver) as fh:
            solver_text = fh.read()
    tr = IncrementalTrainer(
        args.log_dir, args.net, args.out_dir,
        prefix=args.prefix, batch_size=args.batch_size,
        base_lr=args.base_lr, solver_text=solver_text,
        init_weights=args.init_weights, seed=args.seed,
    )
    if args.once:
        path = tr.run_once()
        print(f"trainer: {'candidate ' + path if path else 'no new data'}",
              flush=True)
        return 0
    tr.follow(interval_s=args.interval_s, max_rounds=args.max_rounds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
