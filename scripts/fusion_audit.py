#!/usr/bin/env python
"""Trace-driven step-fusion audit — find the dispatch gaps we already
record (ISSUE 12 tentpole c).

The telemetry subsystem has produced per-phase timelines and
Perfetto-loadable Chrome traces since PR 5; this tool finally *reads
them back* to answer one question: where does an iteration's wall time
go that no phase accounts for?  Host time between compiled regions —
extra per-iteration dispatches (an ``jax.random.split`` program, a
scalar ``device_put`` for the iteration counter), unfenced syncs,
python bookkeeping — shows up as *gaps* between the timeline's phase
spans.  The audit:

1. parses a ``--trace`` Chrome JSON (``telemetry/trace.py`` schema);
2. rebuilds each thread's span sequence and measures the unattributed
   gap between adjacent spans, aggregated by phase *transition* (e.g.
   ``device_put -> compiled_step`` is where pre-step host dispatches
   hide);
3. reports per-phase shares plus ranked findings with the concrete
   fix each one grounds: fold host dispatches into the compiled step
   (the base Solver's one step program does; ParallelSolver's sync
   dispatch does not yet), donate/prefetch buffers for ``device_put``
   stalls, ``jax.remat`` / more data workers where input or memory
   dominates.

All timing comes from the trace file — this script reads clocks
*nobody* ran for it and contains no ad-hoc timers (the check.sh smoke
asserts it never grows one).

    python scripts/fusion_audit.py run_trace.json
    python scripts/fusion_audit.py run_trace.json --json
    python scripts/fusion_audit.py run_trace.json --informational  # CI

Exit code 1 when a finding crosses its threshold (``--gap-pct``,
``--put-pct``, ``--input-pct``) unless ``--informational``.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List

# the train-loop phases the timeline brackets (telemetry/timeline.py);
# everything else (serve spans, comm phases) still counts as attributed
STEP_PHASES = (
    "input_wait", "device_put", "multihost_sync", "compiled_step",
    "grad_allreduce", "eval", "snapshot",
)


def load_events(path: str) -> List[Dict[str, Any]]:
    with open(path) as fh:
        doc = json.load(fh)
    evs = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    out = []
    for e in evs:
        if e.get("ph") == "X" and "ts" in e and "dur" in e:
            out.append(e)
    return out


def audit(events: List[Dict[str, Any]],
          phases=STEP_PHASES) -> Dict[str, Any]:
    """The machine-readable audit record: per-phase totals, the
    unattributed gap between adjacent phase spans per thread,
    aggregated by transition, and per-iteration statistics anchored on
    ``compiled_step`` occurrences."""
    by_thread: Dict[tuple, list] = defaultdict(list)
    phase_totals: Dict[str, list] = defaultdict(lambda: [0.0, 0])
    for e in events:
        if e["name"] in phases:
            by_thread[(e.get("pid"), e.get("tid"))].append(e)
            t = phase_totals[e["name"]]
            t[0] += e["dur"]
            t[1] += 1

    transitions: Dict[str, list] = defaultdict(lambda: [0.0, 0])
    gap_total = 0.0
    span_total = 0.0
    wall = 0.0
    iters = 0
    for evs in by_thread.values():
        evs.sort(key=lambda e: e["ts"])
        span_total += sum(e["dur"] for e in evs)
        wall += (
            evs[-1]["ts"] + evs[-1]["dur"] - evs[0]["ts"]
        ) if len(evs) > 1 else 0.0
        iters += sum(1 for e in evs if e["name"] == "compiled_step")
        for a, b in zip(evs, evs[1:]):
            gap = b["ts"] - (a["ts"] + a["dur"])
            if gap <= 0:
                continue  # nested/overlapping spans attribute elsewhere
            gap_total += gap
            t = transitions[f"{a['name']} -> {b['name']}"]
            t[0] += gap
            t[1] += 1

    gap_share = gap_total / wall if wall > 0 else 0.0
    rec = {
        "wall_us": round(wall, 1),
        "attributed_us": round(span_total, 1),
        "gap_us": round(gap_total, 1),
        "gap_share": round(gap_share, 4),
        "iterations": iters,
        "gap_us_per_iter": (
            round(gap_total / iters, 1) if iters else None
        ),
        "phases": {
            name: {
                "total_us": round(t[0], 1),
                "count": t[1],
                "mean_us": round(t[0] / t[1], 1) if t[1] else None,
                "share": round(t[0] / wall, 4) if wall > 0 else None,
            }
            for name, t in sorted(phase_totals.items())
        },
        "transitions": {
            name: {
                "gap_us": round(t[0], 1),
                "count": t[1],
                "mean_us": round(t[0] / t[1], 1) if t[1] else None,
            }
            for name, t in sorted(
                transitions.items(), key=lambda kv: -kv[1][0]
            )
        },
    }
    return rec


def findings(rec: Dict[str, Any], args) -> List[Dict[str, Any]]:
    """Ranked, thresholded findings — each names the fix it grounds."""
    out: List[Dict[str, Any]] = []
    wall = rec["wall_us"] or 1.0
    if rec["gap_share"] * 100.0 > args.gap_pct and rec["iterations"]:
        top = next(iter(rec["transitions"]), None)
        out.append({
            "kind": "dispatch_gap",
            "share_pct": round(100 * rec["gap_share"], 1),
            "gap_us_per_iter": rec["gap_us_per_iter"],
            "hottest_transition": top,
            "fix": (
                "host work between compiled regions (per-iteration "
                "rng-split dispatch, scalar device_put of the step "
                "counter, python bookkeeping): fold it into the step "
                "— the base Solver compiles split+increment into its "
                "one step program; ParallelSolver's sync dispatch "
                "still pays them"
            ),
        })
    put = rec["phases"].get("device_put")
    if put and put["share"] is not None and (
        100.0 * put["share"] > args.put_pct
    ):
        out.append({
            "kind": "device_put_stall",
            "share_pct": round(100 * put["share"], 1),
            "mean_us": put["mean_us"],
            "fix": (
                "H2D placement dominates: donate request-scoped "
                "buffers, stage the next batch with data/prefetch."
                "DoubleBuffer, or move augmentation on-device "
                "(Solver batch_transform)"
            ),
        })
    inp = rec["phases"].get("input_wait")
    if inp and inp["share"] is not None and (
        100.0 * inp["share"] > args.input_pct
    ):
        out.append({
            "kind": "input_bound",
            "share_pct": round(100 * inp["share"], 1),
            "fix": (
                "host blocked on the feed: raise --data-workers, "
                "switch to packed shard readers (--data-format "
                "packed), or attach the decoded-batch cache"
            ),
        })
    step = rec["phases"].get("compiled_step")
    if step and step["share"] is not None and step["share"] > 0.9:
        out.append({
            "kind": "compute_bound",
            "share_pct": round(100 * step["share"], 1),
            "fix": (
                "the compiled step dominates — dispatch fusion won't "
                "move it; next levers are jax.remat (HBM-bound nets), "
                "layout hints (step_compile_kw scoped-VMEM sweep) and "
                "precision (docs/QUANTIZATION.md)"
            ),
            "informational": True,
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="audit a --trace Chrome JSON for dispatch gaps"
    )
    ap.add_argument("trace", help="Chrome trace JSON (--trace output)")
    ap.add_argument("--gap-pct", type=float, default=10.0,
                    help="max tolerated unattributed-gap share, "
                         "percent of thread wall (default 10)")
    ap.add_argument("--put-pct", type=float, default=15.0,
                    help="max tolerated device_put share (default 15)")
    ap.add_argument("--input-pct", type=float, default=30.0,
                    help="max tolerated input_wait share (default 30)")
    ap.add_argument("--json", action="store_true",
                    help="print the full audit record as JSON only")
    ap.add_argument("--informational", action="store_true",
                    help="report but always exit 0 (the check.sh mode)")
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    rec = audit(events)
    found = findings(rec, args)
    rec["findings"] = found
    if args.json:
        print(json.dumps(rec))
    else:
        print(f"fusion audit: {args.trace}")
        print(
            f"  wall {rec['wall_us'] / 1e3:.1f} ms over "
            f"{rec['iterations']} iterations; unattributed gap "
            f"{rec['gap_us'] / 1e3:.2f} ms "
            f"({100 * rec['gap_share']:.1f}% of wall"
            + (
                f", {rec['gap_us_per_iter']:.0f} us/iter)"
                if rec["gap_us_per_iter"] is not None else ")"
            )
        )
        w = max((len(n) for n in rec["phases"]), default=5)
        for name, p in rec["phases"].items():
            print(
                f"  {name:<{w}} {p['total_us'] / 1e3:>9.2f} ms "
                f"{100 * (p['share'] or 0):>6.1f}% x{p['count']}"
            )
        for name, t in list(rec["transitions"].items())[:5]:
            print(
                f"  gap {name}: {t['gap_us'] / 1e3:.2f} ms total, "
                f"{t['mean_us']} us mean x{t['count']}"
            )
        for f in found:
            print(f"  FINDING [{f['kind']}] {f.get('share_pct')}% — "
                  f"{f['fix']}")
        if not found:
            print("  no findings above thresholds")
        # one machine-readable line, like the apps' `layout:`/`comm:`
        print("fusion_audit: " + json.dumps({
            "gap_share": rec["gap_share"],
            "gap_us_per_iter": rec["gap_us_per_iter"],
            "iterations": rec["iterations"],
            "findings": [f["kind"] for f in found],
        }))
    gating = [f for f in found if not f.get("informational")]
    return 1 if gating and not args.informational else 0


if __name__ == "__main__":
    sys.exit(main())
