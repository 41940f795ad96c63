#!/usr/bin/env bash
# Tier-1 regression gate (ISSUE 4 satellite): run the suite EXACTLY as
# ROADMAP.md specifies, then compare the FAILED/ERROR set against the
# committed baseline (tests/known_failures.txt — empty since PR 21:
# every test passes under jax 0.9.0).  Exit nonzero only on NEW
# failures, so "tier-1 no worse than seed" is machine-checkable:
#
#   ./scripts/check.sh            # full tier-1 + diff vs baseline
#   CHECK_LOG=/tmp/my.log ./scripts/check.sh
#
# Also surfaces the conftest leak-fixture summary (stray input-pipeline
# workers / /dev/shm segments after the session) — a leak shows up as a
# session error and therefore as a NEW failure.
set -uo pipefail
cd "$(dirname "$0")/.."

LOG=${CHECK_LOG:-/tmp/_t1.log}
KNOWN=tests/known_failures.txt
rm -f "$LOG"

# ROADMAP.md "Tier-1 verify", verbatim run parameters
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"

# ---- leak-fixture summary (session-scoped assert in tests/conftest.py)
if grep -aqE "workers leaked past tests|segments leaked past tests" "$LOG"; then
  echo "check.sh: LEAK — the conftest leak fixture tripped:"
  grep -aE "workers leaked past tests|segments leaked past tests" "$LOG"
else
  echo "check.sh: leak fixture clean (no stray pipeline workers or shm segments)"
fi

# ---- diff the failure set against the committed baseline
failures=$(grep -aE '^(FAILED|ERROR) ' "$LOG" \
  | sed -E 's/^(FAILED|ERROR) //; s/ - .*//' | sort -u)
known=$(grep -vE '^[[:space:]]*(#|$)' "$KNOWN" | sort -u)

new=$(comm -23 <(printf '%s\n' "$failures" | sed '/^$/d') \
               <(printf '%s\n' "$known" | sed '/^$/d'))
fixed=$(comm -13 <(printf '%s\n' "$failures" | sed '/^$/d') \
                 <(printf '%s\n' "$known" | sed '/^$/d'))

if [[ -n "$fixed" ]]; then
  echo "check.sh: known failures now PASSING (prune them from $KNOWN):"
  printf '  %s\n' $fixed
fi

if [[ -n "$new" ]]; then
  echo "check.sh: NEW failures vs $KNOWN:"
  printf '  %s\n' $new
  exit 1
fi

if [[ $rc -ne 0 && -z "$failures" ]]; then
  # pytest died without reporting failures (timeout, crash, collection
  # wedge) — that is not a clean pass
  echo "check.sh: pytest exited $rc with no parseable failure list — treating as failure"
  exit "$rc"
fi

# ---- telemetry lint: ad-hoc time.perf_counter metric plumbing belongs
# in sparknet_tpu/telemetry/ now.  Per-file counts are frozen in
# scripts/perf_counter_allowlist.txt ("count path"); a NEW file using
# perf_counter, or more uses in an existing file, fails — decreases and
# telemetry/ itself are fine.
ALLOW=scripts/perf_counter_allowlist.txt
pc_now=$(grep -rc "perf_counter" sparknet_tpu --include='*.py' \
  | grep -v ":0$" | grep -v "^sparknet_tpu/telemetry/" \
  | awk -F: '{print $2, $1}' | sort -k2)
pc_bad=$(awk 'NR==FNR { if ($1 ~ /^#/) next; allowed[$2]=$1; next }
              { if (!($2 in allowed) || $1 > allowed[$2])
                  printf "  %s: %d uses (allowed %d)\n", $2, $1, allowed[$2] }' \
  "$ALLOW" <(printf '%s\n' "$pc_now"))
if [[ -n "$pc_bad" ]]; then
  echo "check.sh: perf_counter LINT — new ad-hoc timing outside sparknet_tpu/telemetry/:"
  printf '%s\n' "$pc_bad"
  echo "  (route new metrics through the telemetry registry/tracer, or consciously bump $ALLOW)"
  exit 1
fi
echo "check.sh: perf_counter lint clean (counts within $ALLOW)"

# ---- telemetry smoke: 5 CPU train iters with --trace must emit a valid
# Chrome trace (Perfetto schema basics) and a nonempty step-time table
SMOKE_DIR=$(mktemp -d /tmp/_telemetry_smoke.XXXXXX)
SMOKE_LOG="$SMOKE_DIR/smoke.log"
cat > "$SMOKE_DIR/net.prototxt" <<'EOF'
name: "smoke"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.05 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
EOF
cat > "$SMOKE_DIR/solver.prototxt" <<EOF
net: "net.prototxt"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 5
display: 0
snapshot_prefix: "$SMOKE_DIR/snap"
EOF
if timeout -k 10 300 env JAX_PLATFORMS=cpu python -m sparknet_tpu.tools.caffe train \
    "--solver=$SMOKE_DIR/solver.prototxt" --synthetic --synthetic-n=64 \
    --batch-size=8 --data-workers=0 --native-loader=off \
    "--trace=$SMOKE_DIR/trace.json" > "$SMOKE_LOG" 2>&1 \
  && grep -q "step-time breakdown" "$SMOKE_LOG" \
  && grep -qE "compiled_step +[0-9]" "$SMOKE_LOG" \
  && python - "$SMOKE_DIR/trace.json" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
evs = d["traceEvents"]
assert evs, "empty traceEvents"
for e in evs:
    assert e["ph"] in ("X", "M") and "pid" in e and "tid" in e and "name" in e, e
EOF
then
  echo "check.sh: telemetry smoke OK (valid trace + step-time table)"
  # ---- fusion audit (ISSUE 12): the dispatch-gap audit must parse the
  # check run's own trace — informational (findings don't gate), but a
  # parse failure does.  All its timing comes from the trace file; the
  # quant smoke below asserts it never grows an ad-hoc clock.
  if python scripts/fusion_audit.py "$SMOKE_DIR/trace.json" --informational; then
    echo "check.sh: fusion audit OK (parsed the telemetry smoke trace)"
  else
    echo "check.sh: fusion AUDIT FAILED on $SMOKE_DIR/trace.json"
    exit 1
  fi
  rm -rf "$SMOKE_DIR"
else
  echo "check.sh: telemetry SMOKE FAILED — log tail:"
  tail -20 "$SMOKE_LOG"
  exit 1
fi

# ---- comm smoke (ISSUE 6): 5 CPU local-SGD iters on a 2-device virtual
# mesh with the adaptive-tau controller and bf16-compressed reduction
# must emit the controller decision log (tau: line + report JSON with
# decisions), the comm: record line, and a grad_allreduce row in the
# step-time table — the bucketed reduce running as its own attributed
# program.
COMM_DIR=$(mktemp -d /tmp/_comm_smoke.XXXXXX)
COMM_LOG="$COMM_DIR/smoke.log"
cat > "$COMM_DIR/net.prototxt" <<'EOF'
name: "comm_smoke"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.05 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
EOF
cat > "$COMM_DIR/solver.prototxt" <<EOF
net: "net.prototxt"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 5
display: 0
snapshot_prefix: "$COMM_DIR/snap"
EOF
if timeout -k 10 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m sparknet_tpu.tools.caffe train \
    "--solver=$COMM_DIR/solver.prototxt" --synthetic --synthetic-n=64 \
    --batch-size=8 --data-workers=0 --native-loader=off \
    --parallel=local --tau=auto --grad-compress=bf16 \
    "--trace=$COMM_DIR/trace.json" > "$COMM_LOG" 2>&1 \
  && grep -q '^tau: {' "$COMM_LOG" \
  && grep -q '^comm: {' "$COMM_LOG" \
  && grep -qE "grad_allreduce +[0-9]" "$COMM_LOG" \
  && python - "$COMM_DIR/snap_tau_controller.json" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
assert d["decisions"], "empty tau controller decision log"
for dec in d["decisions"]:
    assert dec["action"] in ("hold", "widen", "narrow"), dec
    assert d["tau_min"] <= dec["next_tau"] <= d["tau_max"], dec
EOF
then
  echo "check.sh: comm smoke OK (tau controller log + grad_allreduce attribution)"
  rm -rf "$COMM_DIR"
else
  echo "check.sh: comm SMOKE FAILED — log tail:"
  tail -20 "$COMM_LOG"
  exit 1
fi

# ---- sharding smoke (ISSUE 10): 5 CPU train iters through the unified
# rule-table path (--layout dp=2,tp=2) on a 2×2 virtual-CPU mesh must
# print the layout: line (mesh + rule + sharded-leaf record), match a
# single-device run to reduction-order accuracy (GSPMD partitioning is
# semantics-preserving; cross-partitioning equality is ulp-level — the
# BITWISE bar for identical shardings is pinned in tests/test_partition
# .py), and a REPEAT unified run must be bitwise-identical (the
# compiled path is deterministic).
SH_DIR=$(mktemp -d /tmp/_sharding_smoke.XXXXXX)
SH_LOG="$SH_DIR/smoke.log"
cat > "$SH_DIR/net.prototxt" <<'EOF'
name: "sharding_smoke"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.05 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
EOF
sh_solver() {
  cat > "$SH_DIR/solver_$1.prototxt" <<EOF
net: "net.prototxt"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 5
display: 0
snapshot: 5
snapshot_prefix: "$SH_DIR/w_$1"
EOF
}
sh_solver single; sh_solver uni; sh_solver uni2
if timeout -k 10 300 env JAX_PLATFORMS=cpu python -m sparknet_tpu.tools.caffe train \
      "--solver=$SH_DIR/solver_single.prototxt" --synthetic --synthetic-n=64 \
      --batch-size=8 --data-workers=0 --native-loader=off >> "$SH_LOG" 2>&1 \
  && timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
      python -m sparknet_tpu.tools.caffe train \
      "--solver=$SH_DIR/solver_uni.prototxt" --synthetic --synthetic-n=64 \
      --batch-size=8 --data-workers=0 --native-loader=off \
      --layout=dp=2,tp=2 > "$SH_DIR/uni.log" 2>&1 \
  && grep -q '^layout: {' "$SH_DIR/uni.log" \
  && timeout -k 10 300 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
      python -m sparknet_tpu.tools.caffe train \
      "--solver=$SH_DIR/solver_uni2.prototxt" --synthetic --synthetic-n=64 \
      --batch-size=8 --data-workers=0 --native-loader=off \
      --layout=dp=2,tp=2 >> "$SH_LOG" 2>&1 \
  && python - "$SH_DIR" <<'EOF'
import json, sys
import numpy as np
d = sys.argv[1]
line = [l for l in open(f"{d}/uni.log") if l.startswith("layout: ")][-1]
rep = json.loads(line[len("layout: "):])
assert rep["mesh"] == {"dp": 2, "tp": 2}, rep
assert rep["path"] == "unified" and rep["sharded"] >= 1, rep
a = np.load(f"{d}/w_single_iter_5.npz")
b = np.load(f"{d}/w_uni_iter_5.npz")
c = np.load(f"{d}/w_uni2_iter_5.npz")
for k in a.files:
    assert (b[k] == c[k]).all(), f"unified run not deterministic at {k}"
    if a[k].dtype.kind == "f":
        assert np.allclose(a[k], b[k], rtol=1e-5, atol=1e-6), (
            f"unified vs single-device weights differ at {k}: "
            f"max {np.abs(a[k] - b[k]).max()}"
        )
    else:
        assert (a[k] == b[k]).all(), k
print(f"sharding smoke: layout {rep['mesh']} sharded={rep['sharded']}/"
      f"{rep['param_leaves']}, weights match single-device")
EOF
then
  echo "check.sh: sharding smoke OK (unified dp=2,tp=2 == single device, layout line present)"
  rm -rf "$SH_DIR"
else
  echo "check.sh: sharding SMOKE FAILED — log tails:"
  tail -15 "$SH_LOG"
  tail -15 "$SH_DIR/uni.log" 2>/dev/null
  exit 1
fi

# ---- reshard smoke (ISSUE 14): a 5-iter caffe train on a 2×2 virtual
# mesh migrates dp=4 -> dp=2,tp=2 IN PLACE at iteration 2 (request-file
# control surface) — the reshard: line must appear, the final weights
# must be BITWISE equal to a fresh layout-B run replayed from the
# reshard-point snapshot, post-reshard snapshots must carry the new
# layout env, and resharding back to seen layouts must hit the
# per-layout compile cache (no new executable).  Migration timing rides
# the telemetry timeline — the perf_counter allowlist is unchanged.
if timeout -k 10 580 env JAX_PLATFORMS=cpu python scripts/reshard_smoke.py; then
  echo "check.sh: reshard smoke OK (mid-run dp=4 -> dp=2,tp=2, bitwise vs replay, cache-warm reshard-back)"
else
  echo "check.sh: reshard SMOKE FAILED"
  exit 1
fi

# ---- data-plane smoke (ISSUE 8): pack a tiny synthetic dataset, train
# 5 CPU iters three ways — legacy in-memory feed, packed shard readers
# cold (filling the decoded-batch cache), and packed again served from
# the cache (a second "job" in the same namespace).  The cached run's
# `data cache:` line must show hits > 0, and all three final weight
# files must be BITWISE equal — switching --data-format / --data-cache
# can never change training results.
DP_DIR=$(mktemp -d /tmp/_data_plane_smoke.XXXXXX)
DP_LOG="$DP_DIR/smoke.log"
DP_NS="dpsmoke_$$"
cat > "$DP_DIR/net.prototxt" <<'EOF'
name: "dp_smoke"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.05 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" top: "loss" }
EOF
dp_solver() {
  cat > "$DP_DIR/solver_$1.prototxt" <<EOF
net: "net.prototxt"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 5
display: 0
snapshot: 5
snapshot_prefix: "$DP_DIR/w_$1"
EOF
}
dp_solver legacy; dp_solver packed; dp_solver cached
if timeout -k 10 120 env JAX_PLATFORMS=cpu python -m sparknet_tpu.tools.pack_records \
      --source synthetic-cifar --n 256 --out "$DP_DIR/packed" >> "$DP_LOG" 2>&1 \
  && timeout -k 10 300 env JAX_PLATFORMS=cpu python -m sparknet_tpu.tools.caffe train \
      "--solver=$DP_DIR/solver_legacy.prototxt" --synthetic --synthetic-n=256 \
      --batch-size=16 --data-workers=0 --native-loader=off >> "$DP_LOG" 2>&1 \
  && timeout -k 10 300 env JAX_PLATFORMS=cpu python -m sparknet_tpu.tools.caffe train \
      "--solver=$DP_DIR/solver_packed.prototxt" "--data-dir=$DP_DIR/packed" \
      --data-format=packed "--data-cache=$DP_NS" \
      --batch-size=16 --data-workers=0 --native-loader=off >> "$DP_LOG" 2>&1 \
  && timeout -k 10 300 env JAX_PLATFORMS=cpu python -m sparknet_tpu.tools.caffe train \
      "--solver=$DP_DIR/solver_cached.prototxt" "--data-dir=$DP_DIR/packed" \
      --data-format=packed "--data-cache=$DP_NS" \
      --batch-size=16 --data-workers=0 --native-loader=off > "$DP_DIR/cached.log" 2>&1 \
  && grep -q '^data cache: {' "$DP_DIR/cached.log" \
  && python - "$DP_DIR" <<'EOF'
import json, re, sys
import numpy as np
d = sys.argv[1]
line = [l for l in open(f"{d}/cached.log") if l.startswith("data cache: ")][-1]
stats = json.loads(line[len("data cache: "):])
assert stats["hits"] > 0, f"cached run had no cache hits: {stats}"
a = np.load(f"{d}/w_legacy_iter_5.npz")
b = np.load(f"{d}/w_packed_iter_5.npz")
c = np.load(f"{d}/w_cached_iter_5.npz")
for k in a.files:
    assert (a[k] == b[k]).all(), f"legacy vs packed weights differ at {k}"
    assert (a[k] == c[k]).all(), f"legacy vs cached weights differ at {k}"
print(f"data-plane smoke: cache hits={stats['hits']}, weights bitwise equal")
EOF
then
  echo "check.sh: data-plane smoke OK (packed + cached == legacy weights, hits > 0)"
  python -m sparknet_tpu.data.cache clear "$DP_NS" > /dev/null 2>&1
  rm -rf "$DP_DIR"
else
  echo "check.sh: data-plane SMOKE FAILED — log tails:"
  tail -15 "$DP_LOG"
  tail -15 "$DP_DIR/cached.log" 2>/dev/null
  python -m sparknet_tpu.data.cache clear "$DP_NS" > /dev/null 2>&1
  exit 1
fi

# ---- serving-tier smoke (ISSUE 9 + 11): 2 subprocess engine replicas
# behind the router take a closed-loop HTTP burst while one replica is
# SIGKILLed and a rolling hot-swap to a new verified solverstate lands —
# zero failed requests, both generations served, the respawned replica
# must boot off the persistent compile cache (no new entries written
# during its warmup), and the router's /traces export must hold a
# stitched request waterfall with >=5 spans attributing >=90% of wall
# latency (telemetry/reqtrace.py).
if timeout -k 10 580 env JAX_PLATFORMS=cpu python scripts/serving_smoke.py; then
  echo "check.sh: serving smoke OK (replica kill + hot-swap, 0 failed, cache-hit respawn, stitched waterfall)"
else
  echo "check.sh: serving SMOKE FAILED"
  exit 1
fi

# ---- session smoke (ISSUE 13): a 1-router/2-replica tier on the
# char-rnn decoder runs a 3-step /generate session with a SIGKILL of
# the state-holding replica mid-session — step 2 must hit the session
# cache, the post-kill step must answer migrated+cold with the
# migration counted, and the final answers must equal a fresh
# cold-path request bitwise (rebuilt, never wrong).
if timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/session_smoke.py; then
  echo "check.sh: session smoke OK (affinity hit + holder kill -> counted migration, answers == cold path)"
else
  echo "check.sh: session SMOKE FAILED"
  exit 1
fi

# ---- decode batch smoke (ISSUE 17): 4 concurrent sessions drive
# /generate through the continuous token-level batcher (K rows per
# compiled step dispatch) while the state-holding replica is SIGKILLed
# mid-burst — zero failed requests, every batched row must equal its
# one-at-a-time serial replay exactly (tokens/probs/indices), and the
# tier's healthz decode block must show the batched path ran.
if timeout -k 10 580 env JAX_PLATFORMS=cpu python scripts/decode_batch_smoke.py; then
  echo "check.sh: decode batch smoke OK (4-session burst + holder kill, 0 failed, rows == serial replay)"
else
  echo "check.sh: decode batch SMOKE FAILED"
  exit 1
fi

# ---- autoscale smoke (ISSUE 16): a 1-replica char-rnn tier with
# --autoscale-max 2 takes a seeded 12x open-loop spike — the controller
# must scale 1->2 on the windowed-p99 breach, admission must shed batch
# (429) while interactive keeps answering, a holder SIGKILL mid-burst
# must resolve to a counted migration (post-kill step migrated+cold),
# the tier must scale back to 1 after the cool window draining the
# session-holder through the migration path, and the drained session's
# next step must equal a fresh cold-path request bitwise — zero failed
# requests, zero session errors end to end.
if timeout -k 10 580 env JAX_PLATFORMS=cpu python scripts/autoscale_smoke.py; then
  echo "check.sh: autoscale smoke OK (12x spike -> scale 1->2->1, batch shed, holder kill -> migration, 0 failed)"
else
  echo "check.sh: autoscale SMOKE FAILED"
  exit 1
fi

# ---- closed-loop deploy smoke (ISSUE 18): a 2-replica tier with
# --deploy-dir closes the lifecycle — served traffic tees into a packed
# log, the supervised incremental trainer emits candidates, the eval
# gate verifies + agreement-checks each before the roll, the first roll
# survives its watch window and becomes baseline, the second roll is
# chaos-regressed post-gate (deploy.regressed_weights) and the watch's
# front-door probe replay fires an auto-rollback to the resident
# previous generation — zero failed requests end to end, the bad
# digest machine-checkably ineligible (ledger + re-roll -> 409), zero
# bad-generation answers after rollback.
if timeout -k 10 580 env JAX_PLATFORMS=cpu python scripts/closed_loop_smoke.py; then
  echo "check.sh: closed-loop smoke OK (tee -> train -> gate -> roll -> regression -> rollback, 0 failed)"
else
  echo "check.sh: closed-loop SMOKE FAILED"
  exit 1
fi

# ---- storage-fault smoke (ISSUE 19): the same closed-loop tier rides
# out a seeded volume-wide ENOSPC storm hitting the tee in every
# replica plus a one-shot ENOSPC on the trainer's candidate snapshot —
# zero failed requests, zero trainer give-ups/respawns, the tee pauses
# (counted drops) and RESUMES sealing once the storm clears, the
# skipped snapshot never stalls the roll loop (2 gated rolls), the
# post-storm tier answers bit-exact vs the pinned baseline, and the
# tee log decodes end to end with no bare staging files left behind.
if timeout -k 10 580 env JAX_PLATFORMS=cpu python scripts/storage_smoke.py; then
  echo "check.sh: storage smoke OK (ENOSPC storm -> tee pause/resume + snapshot skip, 0 failed, bit-exact)"
else
  echo "check.sh: storage SMOKE FAILED"
  exit 1
fi

# ---- quant smoke (ISSUE 12): an int8 1-replica tier hot-swaps a
# manifest-verified snapshot (scales re-captured at swap time), the
# quant tag rides /healthz and /classify next to gen, f32-vs-int8
# top-1 agreement holds the <0.5% disagreement bar, the persistent
# compile cache keys f32 and int8 into DISTINCT fingerprint dirs, and
# the fusion-audit/quantize code contains no ad-hoc perf_counter
# clocks (allowlist frozen).
if timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/quant_smoke.py; then
  echo "check.sh: quant smoke OK (int8 hot-swap + agreement + precision-distinct cache)"
else
  echo "check.sh: quant SMOKE FAILED"
  exit 1
fi

# ---- cluster observability smoke (ISSUE 7): a real 2-process heartbeat
# run must merge rank 1's piggybacked snapshots on rank 0 — the script
# asserts the cluster phase table renders with both rank columns and at
# least one aggregated per-rank registry series.
if timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/cluster_smoke.py; then
  echo "check.sh: cluster smoke OK (2-process heartbeat merge)"
else
  echo "check.sh: cluster SMOKE FAILED"
  exit 1
fi

echo "check.sh: OK — no new failures ($(printf '%s\n' "$failures" | sed '/^$/d' | wc -l) known)"
exit 0
