#!/usr/bin/env python
"""Compare two bench records — the first reader of the BENCH_* trail.

PR 5 started embedding a telemetry block (registry + step-phase
breakdown) in every bench record and PR 6 added wire-byte estimates;
until now nothing read them back.  This tool diffs two records and
prints a regression table:

    python scripts/bench_diff.py old.json new.json
    python scripts/bench_diff.py old.json new.json --informational

Rows: headline throughput, step time, each step-phase's share of
attributed time, the wire-bytes-per-reduction estimate when a comm
sub-record exists, and the data-plane cold/cached epoch throughput
(+ decode-skip ratio) when the record came from
``BENCH_MODEL=data_plane``.  Thresholds (tunable by flag) mark a row REGRESSED;
the exit code is 1 when anything regressed unless ``--informational``
(the scripts/check.sh invocation) — so the same tool serves both a CI
trip-wire and a human diff.

Accepts either shape on disk: a raw ``bench.py`` output record, or the
driver wrapper ``{"parsed": {...}}`` the repo's BENCH_r*.json use.
Stdlib-only.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional


def load_record(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict):
        raise SystemExit(f"{path}: not a JSON object")
    # driver wrapper: the bench line lives under "parsed"
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        return doc["parsed"]
    return doc


def phase_shares(rec: Dict[str, Any]) -> Dict[str, float]:
    """Phase -> share of attributed time, from the embedded telemetry
    timeline ({} when the record predates PR 5)."""
    tl = (rec.get("telemetry") or {}).get("timeline") or {}
    phases = tl.get("phases") or {}
    total = sum(p.get("total_s", 0.0) for p in phases.values())
    if total <= 0:
        return {}
    return {
        name: p.get("total_s", 0.0) / total for name, p in phases.items()
    }


def find_key(obj: Any, key: str) -> Optional[float]:
    """First numeric value under ``key`` anywhere in the record (the
    comm sub-record's location varies by BENCH_MODEL)."""
    if isinstance(obj, dict):
        if key in obj and isinstance(obj[key], (int, float)):
            return float(obj[key])
        for v in obj.values():
            got = find_key(v, key)
            if got is not None:
                return got
    elif isinstance(obj, list):
        for v in obj:
            got = find_key(v, key)
            if got is not None:
                return got
    return None


def _fmt(v: Optional[float], unit: str = "") -> str:
    if v is None:
        return "—"
    if unit == "%":
        return f"{100 * v:.1f}%"
    if unit == "B":
        return f"{v:,.0f}"
    return f"{v:.2f}"


def diff(old: Dict[str, Any], new: Dict[str, Any], args) -> int:
    rows = []  # (name, old, new, unit, regressed, note)

    def add(name, a, b, unit, regressed, note=""):
        rows.append((name, a, b, unit, regressed, note))

    # headline throughput: higher is better
    a, b = old.get("value"), new.get("value")
    if a and b:
        drop = (a - b) / a
        add(
            old.get("metric", "throughput"), a, b, "",
            drop > args.throughput_pct / 100.0,
            f"{-drop:+.1%}",
        )
    # step time: lower is better
    a, b = old.get("step_ms"), new.get("step_ms")
    if a and b:
        rise = (b - a) / a
        add("step_ms", a, b, "", rise > args.throughput_pct / 100.0,
            f"{rise:+.1%}")
    # phase shares: a share that grew by more than N percentage points
    ps_old, ps_new = phase_shares(old), phase_shares(new)
    for name in sorted(set(ps_old) | set(ps_new)):
        a, b = ps_old.get(name), ps_new.get(name)
        grew = (
            a is not None and b is not None
            and (b - a) * 100.0 > args.phase_pp
        )
        note = f"{(b or 0) - (a or 0):+.1%}" if a is not None and b is not None else "new" if a is None else "gone"
        add(f"phase:{name}", a, b, "%", grew, note)
    # wire bytes per reduction (comm records): more bytes = regression
    a = find_key(old, "wire_bytes_per_reduction")
    b = find_key(new, "wire_bytes_per_reduction")
    if a and b:
        rise = (b - a) / a
        add("wire_bytes_per_reduction", a, b, "B",
            rise > args.wire_pct / 100.0, f"{rise:+.1%}")
    # data-plane records (BENCH_MODEL=data_plane): cold/cached epoch
    # throughput and the decode-skip ratio — higher is better for all
    for key in ("cold_rows_per_sec", "cached_rows_per_sec",
                "cached_speedup"):
        a, b = find_key(old, key), find_key(new, key)
        if a and b:
            drop = (a - b) / a
            add(key, a, b, "",
                drop > args.throughput_pct / 100.0, f"{-drop:+.1%}")
    # serving records (loadgen / BENCH_MODEL=serving_tier): end-to-end
    # request latency, lower is better (top-level keys only — nested
    # per-arm copies would double-report)
    for key in ("p50_ms", "p99_ms"):
        a, b = old.get(key), new.get(key)
        if a and b:
            rise = (b - a) / a
            add(key, a, b, "", rise > args.throughput_pct / 100.0,
                f"{rise:+.1%}")
    # sharding records (BENCH_MODEL=sharding): unified-vs-legacy step
    # time, compile wall time, and the donated-buffer peak-memory
    # estimate — all lower-is-better.  The fusion A/B's two step
    # times (BENCH_MODEL=fusion) ride the same direction.
    for key in ("unified_step_ms", "legacy_step_ms", "compile_s_unified",
                "compile_s_legacy", "donated_peak_mb",
                "step_ms_fused", "step_ms_legacy"):
        a, b = find_key(old, key), find_key(new, key)
        if a and b:
            rise = (b - a) / a
            add(key, a, b, "", rise > args.throughput_pct / 100.0,
                f"{rise:+.1%}")
    # ratio fields, higher is better: continuous-vs-fill p99 win, the
    # compile cache's warm-restart warmup speedup, and the unified
    # sharding path's step-time win over the legacy shard_map program
    for key in ("p99_improvement", "warm_restart_speedup",
                "unified_speedup"):
        a, b = find_key(old, key), find_key(new, key)
        if a and b:
            drop = (a - b) / a
            add(key, a, b, "",
                drop > args.throughput_pct / 100.0, f"{-drop:+.1%}")
    # the chaos bar is absolute: any failed request regresses
    a, b = find_key(old, "failed_requests"), find_key(new, "failed_requests")
    if b is not None:
        add("failed_requests", a, b, "", bool(b),
            "ZERO is the bar" if b else "ok")
    # request-trace overhead (serving_tier records): % p50 cost of
    # tracing-on vs tracing-off at equal load — an ABSOLUTE bar like
    # failed_requests, not a ratio against the old record
    b = find_key(new, "reqtrace_overhead_pct")
    if b is not None:
        a = find_key(old, "reqtrace_overhead_pct")
        over = b > args.reqtrace_pct
        add("reqtrace_overhead_pct", a, b, "", over,
            f"≤{args.reqtrace_pct:g}% is the bar" if over else "ok")
    # quantized-inference records (BENCH_MODEL=quant_serving): the
    # accuracy and cache-key bars are ABSOLUTE and platform-blind;
    # the speed floors (int8 >= 1.5x, bf16 >= 1.2x) gate accelerator
    # records only — XLA CPU has no int8 GEMM path, and such records
    # carry speedup_gate="informational-on-cpu" to say so.  The
    # weight-bytes compression is real on every platform and gets its
    # own absolute floor.
    for key in ("int8_disagree_pct", "bf16_disagree_pct"):
        b = new.get(key)
        if b is not None:
            over = b > args.quant_disagree_pct
            add(key, old.get(key), b, "", over,
                f"≤{args.quant_disagree_pct:g}% is the bar"
                if over else "ok")
    fd = new.get("fingerprints_distinct")
    if fd is not None:
        add("fingerprints_distinct", None, float(bool(fd)), "",
            not fd,
            "ok" if fd else "precision compile-cache keys ALIAS")
    b = new.get("int8_weight_compression")
    if b is not None:
        low = b < args.int8_bytes_x
        add("int8_weight_compression", old.get("int8_weight_compression"),
            b, "", low,
            f"≥{args.int8_bytes_x:g}x is the bar" if low else "ok")
    speed_gated = new.get("speedup_gate") != "informational-on-cpu"
    for key, floor in (("int8_speedup", args.int8_speedup_min),
                       ("bf16_speedup", args.bf16_speedup_min)):
        b = new.get(key)
        if b is not None:
            bad = speed_gated and b < floor
            add(key, old.get(key), b, "", bad,
                f"≥{floor:g}x floor" if bad
                else ("cpu-informational" if not speed_gated else "ok"))
    # live-resharding records (BENCH_MODEL=reshard, ISSUE 14): the
    # in-place migration must stay cheaper than the warm restart it
    # replaces (>=1x ABSOLUTE, like fusion's bar — the restart arm
    # already understates the real cost by excluding process spawn and
    # backend init), the migration must preserve weights BITWISE
    # (zero tolerance), and the relayout/restart costs diff
    # lower-is-better against the previous record
    for key in ("relayout_ms", "reshard_total_ms", "restart_ms"):
        a, b = find_key(old, key), find_key(new, key)
        if a and b:
            rise = (b - a) / a
            add(key, a, b, "", rise > args.throughput_pct / 100.0,
                f"{rise:+.1%}")
    b = new.get("reshard_vs_restart_speedup")
    if b is not None:
        bad = b < args.reshard_speedup_min
        add("reshard_vs_restart_speedup",
            old.get("reshard_vs_restart_speedup"), b, "", bad,
            f"≥{args.reshard_speedup_min:g}x is the bar" if bad else "ok")
    bp = new.get("bitwise_preserved")
    if bp is not None:
        add("bitwise_preserved", None, float(bool(bp)), "", not bp,
            "ok" if bp else "migration PERTURBED weights")
    ch = new.get("cache_hit_warm")
    if ch is not None:
        add("reshard_cache_hit_warm", None, float(bool(ch)), "", not ch,
            "ok" if ch else "seen layout RECOMPILED")
    # fusion records (BENCH_MODEL=fusion): the audit-driven fix must
    # actually cut step time — an absolute >1.0x bar, like
    # failed_requests' zero
    b = new.get("fusion_speedup")
    if b is not None:
        bad = b <= 1.0
        add("fusion_speedup", old.get("fusion_speedup"), b, "", bad,
            "audit fix must cut step_ms" if bad else "ok")
    # session-serving records (BENCH_MODEL=session_serving, ISSUE 13):
    # the cached path must beat the cold full-prefix replay by the
    # ABSOLUTE floor (>=5x by default — an O(1) step vs an O(prefix)
    # rebuild should not be close), at equal correctness (hit-vs-cold
    # answers bitwise equal), with zero failed session requests during
    # the chaos arm (a killed holder costs a migration, never an
    # answer)
    if str(new.get("metric", "")).startswith("session_serving"):
        b = new.get("cached_speedup")
        if b is not None:
            low = b < args.session_speedup_min
            add("session_cached_speedup", old.get("cached_speedup"), b,
                "", low,
                f"≥{args.session_speedup_min:g}x is the bar"
                if low else "ok")
        bi = new.get("bit_identical")
        if bi is not None:
            add("session_bit_identical", None, float(bool(bi)), "",
                not bi,
                "ok" if bi else "hit-vs-cold answers DIFFER")
        # batched-decode arm (ISSUE 17): K sessions sharing one step
        # dispatch must beat one-at-a-time decode on aggregate
        # tokens/sec by the floor.  A throughput ratio, so CPU records
        # gate informationally (speedup_gate — the PR 12 honest-
        # labeling discipline); the batched-vs-serial continuation
        # match is ABSOLUTE on every platform.
        b = new.get("batched_tokens_per_sec_speedup")
        if b is not None:
            gated = new.get("speedup_gate") != "informational-on-cpu"
            low = gated and b < args.decode_speedup_min
            add("batched_tokens_per_sec_speedup",
                old.get("batched_tokens_per_sec_speedup"), b, "", low,
                f"≥{args.decode_speedup_min:g}x floor" if low
                else ("cpu-informational" if not gated else "ok"))
        # the device-side ratio (tokens stepped per engine-second) is
        # overhead-immune, so it gates on EVERY backend — this is the
        # CPU-honest form of the ≥3x batching claim
        d = new.get("batched_device_speedup")
        if d is not None:
            low = d < args.decode_speedup_min
            add("batched_device_speedup",
                old.get("batched_device_speedup"), d, "", low,
                f"≥{args.decode_speedup_min:g}x floor" if low else "ok")
        tm = new.get("batched_tokens_match")
        if tm is not None:
            add("batched_tokens_match", None, float(bool(tm)), "",
                not tm,
                "ok" if tm
                else "batched-vs-serial continuations DIFFER")
    b = find_key(new, "session_failed_requests")
    if b is not None:
        a = find_key(old, "session_failed_requests")
        add("session_failed_requests", a, b, "", bool(b),
            "ZERO is the bar" if b else "ok")
    # autoscale arm (serving_tier records, ISSUE 16): across the same
    # seeded 10x open-loop spike, the elastic + admission tier must
    # hold the interactive p99-within-SLO fraction above an ABSOLUTE
    # floor and finish with zero outright failures and zero session
    # errors; the static arm's fraction and the gap are informational
    # evidence the spike actually bites (the static tier is EXPECTED
    # to collapse — its counts never regress this diff)
    b = new.get("autoscale_slo_ok_frac")
    if b is not None:
        low = b < args.autoscale_slo_min
        add("autoscale_slo_ok_frac", old.get("autoscale_slo_ok_frac"),
            b, "", low,
            f"≥{args.autoscale_slo_min:g} is the bar" if low else "ok")
        add("static_slo_ok_frac", old.get("static_slo_ok_frac"),
            new.get("static_slo_ok_frac"), "", False, "informational")
        g = new.get("autoscale_slo_gap")
        if g is not None:
            add("autoscale_slo_gap", old.get("autoscale_slo_gap"), g,
                "", False, "elastic minus static")
    for key, what in (
        ("autoscale_failed_requests", "failed request"),
        ("autoscale_session_failed", "session error"),
    ):
        b = new.get(key)
        if b is not None:
            add(key, old.get(key), b, "", bool(b),
                f"ZERO {what}s is the bar" if b else "ok")
    sp = new.get("autoscale_sessions_preserved")
    if sp is not None:
        add("autoscale_sessions_preserved", None, float(bool(sp)), "",
            not sp, "ok" if sp else "session LOST across scale-down")
    b = find_key(new, "session_migrations")
    if b is not None:
        a = find_key(old, "session_migrations")
        add("session_migrations", a, b, "", False, "informational")
    # closed-loop deploy records (BENCH_MODEL=closed_loop, ISSUE 18):
    # zero failed requests across both rolls AND the rollback, zero
    # post-rollback answers from the bad generation — both ABSOLUTE —
    # and the tier-wide rollback latency diffs lower-is-better (the
    # resident-previous pointer exchange must stay cheap)
    for key, what in (
        ("deploy_failed_requests", "failed request"),
        ("bad_gen_served_after_rollback", "bad-generation answer"),
    ):
        b = new.get(key)
        if b is not None:
            add(key, old.get(key), b, "", bool(b),
                f"ZERO {what}s is the bar" if b else "ok")
    a, b = old.get("rollback_ms"), new.get("rollback_ms")
    if a and b:
        rise = (b - a) / a
        add("rollback_ms", a, b, "", rise > args.rollback_pct / 100.0,
            f"{rise:+.1%}")
    # served-generation coverage (hot-swap observability): count of
    # distinct generations answered during the run — informational
    gens_old = (old.get("tier") or {}).get("served_generations")
    gens_new = (new.get("tier") or {}).get("served_generations")
    if gens_new is not None:
        add("served_generations",
            float(len(gens_old)) if gens_old is not None else None,
            float(len(gens_new)), "", False, str(gens_new))

    if not rows:
        print("bench_diff: no comparable fields between the two records")
        return 0
    w = max(len(r[0]) for r in rows)
    print(f"{'field':<{w}} {'old':>14} {'new':>14} {'delta':>8}  verdict")
    regressed = 0
    for name, a, b, unit, bad, note in rows:
        verdict = "REGRESSED" if bad else "ok"
        regressed += bad
        print(
            f"{name:<{w}} {_fmt(a, unit):>14} {_fmt(b, unit):>14} "
            f"{note:>8}  {verdict}"
        )
    print(
        f"bench_diff: {regressed} regressed row(s) "
        f"(thresholds: throughput {args.throughput_pct}%, "
        f"phase +{args.phase_pp}pp, wire {args.wire_pct}%)"
    )
    return 1 if regressed and not args.informational else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two BENCH_*.json records with thresholds"
    )
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--throughput-pct", type=float, default=10.0,
                    help="max tolerated throughput drop / step-time "
                         "rise, percent (default 10)")
    ap.add_argument("--phase-pp", type=float, default=10.0,
                    help="max tolerated phase-share growth, percentage "
                         "points (default 10)")
    ap.add_argument("--wire-pct", type=float, default=25.0,
                    help="max tolerated wire-bytes growth, percent "
                         "(default 25)")
    ap.add_argument("--reqtrace-pct", type=float, default=2.0,
                    help="max tolerated request-tracing p50 overhead, "
                         "percent of the tracing-off p50 (default 2)")
    ap.add_argument("--quant-disagree-pct", type=float, default=0.5,
                    help="max tolerated quantized top-1 disagreement "
                         "vs the f32 reference, percent (default 0.5)")
    ap.add_argument("--int8-speedup-min", type=float, default=1.5,
                    help="int8 serve-throughput floor vs f32, x "
                         "(accelerator records only; default 1.5)")
    ap.add_argument("--bf16-speedup-min", type=float, default=1.2,
                    help="bf16 serve-throughput floor vs f32, x "
                         "(accelerator records only; default 1.2)")
    ap.add_argument("--int8-bytes-x", type=float, default=1.5,
                    help="int8 resident-weight-bytes compression "
                         "floor vs f32, x (default 1.5)")
    ap.add_argument("--reshard-speedup-min", type=float, default=1.0,
                    help="live-reshard cost floor vs a warm restart, x "
                         "(reshard records; absolute gate, default 1)")
    ap.add_argument("--autoscale-slo-min", type=float, default=0.15,
                    help="absolute floor on the autoscale arm's "
                         "interactive p99-within-SLO fraction across "
                         "the 10x spike (serving_tier records; "
                         "default 0.15 — this 1-cpu container's "
                         "client-side latency is dominated by thread "
                         "scheduling the tier cannot control; the "
                         "hard evidence is the zero-failure bars and "
                         "the positive gap vs the static arm)")
    ap.add_argument("--decode-speedup-min", type=float, default=3.0,
                    help="batched-decode aggregate tokens/sec floor vs "
                         "one-at-a-time decode, x (session_serving "
                         "records; accelerator records only — CPU "
                         "records carry speedup_gate="
                         "informational-on-cpu; default 3)")
    ap.add_argument("--session-speedup-min", type=float, default=5.0,
                    help="session-cache cached-vs-cold per-request "
                         "latency floor, x (session_serving records; "
                         "default 5)")
    ap.add_argument("--rollback-pct", type=float, default=100.0,
                    help="max tolerated tier-rollback latency rise, "
                         "percent (closed_loop records; default 100 — "
                         "tens of ms on this box, so scheduling noise "
                         "needs generous headroom; the real guarantees "
                         "are the zero bars)")
    ap.add_argument("--informational", action="store_true",
                    help="print the table but always exit 0 (the "
                         "check.sh mode)")
    args = ap.parse_args(argv)
    return diff(load_record(args.old), load_record(args.new), args)


if __name__ == "__main__":
    sys.exit(main())
