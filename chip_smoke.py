"""chip_smoke.py — does the system still start on the chip?

    python chip_smoke.py        # no arguments; refuses anything but a TPU

Drives the main paths once, in THIS process (a chip belongs to one
process), through the entry points a user calls:

1. ``train_alexnet``  ``apps/imagenet_app`` — bvlc_alexnet at its published
   widths, bf16, batch 512, the app's default live feed, a few steps
2. ``train_bert``     ``apps/bert_app`` — BERT-base, S=512, batch 64, and the
   Pallas flash kernels asserted present in the lowered step
3. ``serve``          the serving stack (``serve/replica.build_stack``) over
   cifar10_quick (``/healthz``, ``/classify``) and char_rnn
   (``/generate``: session hit == cold replay), over a real socket
4. ``multichip``      phase 1 as dp=4 sync, τ-local and dp=2,tp=2 — only
   where four devices exist; otherwise an explicit skip

Weights are random, made from a seed; depth is what a few steps need.
Each phase prints PASS/FAIL, wall seconds with compile apart from the
rest, and device memory.  These are observations for CHANGES.md, not
metrics.  The last line of stdout is one JSON object; the exit code is
0 only when every phase that ran passed.

The phases are functions of their sizes so that a CPU test can rehearse
them tiny (tests/test_chip_smoke.py); ``__main__`` takes no size.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import os
import re
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from sparknet_tpu.utils import compile_cache

_HERE = os.path.dirname(os.path.abspath(__file__))
_ZOO = os.path.join(_HERE, "sparknet_tpu", "models", "prototxt")
_WORK = os.path.join(_HERE, "runs", "chip_smoke")  # runs/ is gitignored


class SmokeFailure(Exception):
    """A phase ran but what came out is wrong."""


def _check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


# ---------------------------------------------------------------- observation

class CompileClock:
    """Sums jax's backend-compile durations (XLA and Mosaic; on a
    persistent-cache hit this is the retrieval) and counts cache hits and
    misses, so a phase can report compile seconds apart from the rest.
    Tracing and lowering are left in "the rest": jax reports them nested
    (an inner jit's trace inside its caller's), so their sum overcounts.
    jax's listener registry has no public unregister, so one clock lives
    for the process and phases diff it."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self._COMPILE:
            with self._lock:
                self._totals["compile_s"] += seconds

    def _event(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key:
            with self._lock:
                self._totals[key] += 1

    def read(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)


class DeviceBytes:
    """Largest ``bytes_in_use`` seen on every device while the block
    runs, sampled from a thread (the apps free their arrays on return,
    so a reading after the run says nothing).  None per device where
    the backend reports no memory statistics (CPU)."""

    def __init__(self, period_s: float = 0.1):
        self._period_s = period_s
        self._devices = jax.devices()
        self.max_bytes: List[Optional[int]] = [None] * len(self._devices)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="chip-smoke-bytes", daemon=True
        )

    def _sample(self) -> None:
        for i, d in enumerate(self._devices):
            stats = d.memory_stats()
            if stats and "bytes_in_use" in stats:
                self.max_bytes[i] = max(
                    self.max_bytes[i] or 0, int(stats["bytes_in_use"])
                )

    def _run(self) -> None:
        while not self._stop.wait(self._period_s):
            self._sample()

    def __enter__(self) -> "DeviceBytes":
        self._sample()
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(10)
        self._sample()


class _Tee(io.TextIOBase):
    """stdout that also keeps what passed through it."""

    def __init__(self, stream):
        self._stream = stream
        self._kept = io.StringIO()

    def write(self, text: str) -> int:
        self._kept.write(text)
        return self._stream.write(text)

    def flush(self) -> None:
        self._stream.flush()

    def getvalue(self) -> str:
        return self._kept.getvalue()


def _run_main(main: Callable, argv: Sequence[str]):
    """``main(argv)`` with its stdout shown AND kept: the apps report
    iterations and losses as printed lines, which is what a user sees
    and what the phase checks."""
    print(f"$ {main.__module__} {' '.join(argv)}", flush=True)
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        result = main(list(argv))
    return tee.getvalue(), result


_ITER_LINE = re.compile(r"^Iteration (\d+), loss = (\S+?),?(?:\s|$)", re.M)
_DONE_LINE = re.compile(r"^Optimization Done\. (\d+) iters", re.M)


def _check_training_output(text: str, iters: int) -> Dict[str, Any]:
    """The printed record of a training run: every displayed loss is a
    finite float read back from the device, the last displayed iteration
    and the ``Optimization Done`` count both equal ``iters``."""
    shown = [(int(i), float(v)) for i, v in _ITER_LINE.findall(text)]
    _check(bool(shown), "no 'Iteration N, loss = X' line was printed")
    bad = [(i, v) for i, v in shown if not math.isfinite(v)]
    _check(not bad, f"non-finite loss at {bad}")
    _check(
        shown[-1][0] == iters,
        f"last displayed iteration {shown[-1][0]} != {iters}",
    )
    done = _DONE_LINE.search(text)
    _check(
        done is not None and int(done.group(1)) == iters,
        f"iteration counter did not reach {iters}: "
        f"{done.group(0) if done else 'no Optimization Done line'}",
    )
    return {"first_loss": shown[0][1], "last_loss": shown[-1][1]}


# --------------------------------------------------------------------- phases

def _alexnet_solver(iters: int) -> str:
    """The zoo's AlexNet solver with its schedule cut to ``iters`` steps
    (one eval batch at the end, no snapshots), passed through the app's
    own ``--solver`` flag.  Hyper-parameters stay the zoo file's."""
    with open(os.path.join(_ZOO, "bvlc_alexnet_solver.prototxt")) as fh:
        text = fh.read()
    cut = {
        "net": f'"{os.path.join(_ZOO, "bvlc_alexnet_train_val.prototxt")}"',
        "test_iter": "1",
        "test_interval": "0",
        "display": "1",
        "max_iter": str(iters),
        "snapshot": "0",
        "snapshot_prefix": f'"{os.path.join(_WORK, "alexnet")}"',
    }
    for field, value in cut.items():
        text, n = re.subn(
            rf"^{field}:.*$", f"{field}: {value}", text, flags=re.M
        )
        _check(n == 1, f"zoo AlexNet solver has no '{field}:' line")
    os.makedirs(_WORK, exist_ok=True)
    path = os.path.join(_WORK, f"alexnet_solver_{iters}.prototxt")
    with open(path, "w") as fh:
        fh.write(text + "test_initialization: false\n")
    return path


def train_alexnet(
    batch_size: int = 512,
    iters: int = 6,
    bf16: bool = True,
    extra: Sequence[str] = (),
    synthetic_n: Optional[int] = None,
) -> Dict[str, Any]:
    """ImageNetApp on bvlc_alexnet (full published widths) with the
    app's default live feed: ``--native-loader auto``, ``--data-workers
    -1``, ``--prefetch 2``."""
    from sparknet_tpu.apps import imagenet_app

    argv = [
        "--arch", "alexnet", "--solver", _alexnet_solver(iters),
        "--synthetic",
        # the TEST split is an eighth of this and must hold one batch
        "--synthetic-n", str(synthetic_n or max(2048, 8 * batch_size)),
        "--batch-size", str(batch_size),
        *(["--bf16"] if bf16 else []),
        *extra,
    ]
    text, test_metrics = _run_main(imagenet_app.main, argv)
    out = _check_training_output(text, iters)
    _check(
        bool(test_metrics)
        and all(math.isfinite(v) for v in test_metrics.values()),
        f"TEST pass returned {test_metrics!r}",
    )
    feed = re.search(r"^train feed: (.*)$", text, re.M)
    _check(feed is not None, "the app did not say which feed it resolved to")
    out["feed"] = feed.group(1)
    out["test"] = {k: round(v, 4) for k, v in test_metrics.items()}
    return out


def train_bert(
    config: str = "base",
    seq_len: int = 512,
    batch_size: int = 64,
    iters: int = 4,
    bf16: bool = True,
    require_pallas: bool = True,
) -> Dict[str, Any]:
    """BertApp; on a TPU the step must hold the Pallas flash kernels
    (forward, dq, dkv, with in-kernel dropout) — ``ops.attention`` picks
    the reference path on other backends, and a finite loss cannot tell
    the two apart."""
    from sparknet_tpu.apps import bert_app

    argv = [
        "--config", config, "--seq-len", str(seq_len),
        "--batch-size", str(batch_size), "--max-iter", str(iters),
        "--display", "1", *(["--bf16"] if bf16 else []),
    ]
    # the same build the app is about to do, lowered (not compiled): the
    # text of the step says which attention engine is in it
    solver, feed, _cfg = bert_app.build(bert_app.parser().parse_args(argv))
    kernels = solver.lower_step(next(feed)).as_text().count("tpu_custom_call")
    del solver, feed
    gc.collect()
    print(f"train_bert: {kernels} tpu_custom_call op(s) in the lowered step")
    if require_pallas:
        _check(
            kernels >= 3,
            f"{kernels} Pallas kernel call(s) in the lowered BERT step; "
            f"flash forward + dq + dkv were expected (reference attention?)",
        )
    text, metrics = _run_main(bert_app.main, argv)
    out = _check_training_output(text, iters)
    _check(
        math.isfinite(metrics.get("loss", float("nan"))),
        f"BertApp returned {metrics!r}",
    )
    out["pallas_kernel_calls"] = kernels
    return out


def _serving_stack(model: str, buckets: str):
    """The stack ``tools/serve`` and ``serve/replica`` both assemble,
    from the same flags, on an ephemeral port."""
    from sparknet_tpu.serve.replica import add_engine_args, build_stack

    ap = argparse.ArgumentParser()
    add_engine_args(ap)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args(
        ["--model", os.path.join(_ZOO, model), "--buckets", buckets]
    )
    engine, _batcher, _metrics, server = build_stack(args)
    return engine, server.start()


def _ok(reply, what: str) -> dict:
    status, body = reply
    _check(status == 200, f"{what}: HTTP {status} {body}")
    return body


def serve(classify_requests: int = 6, generate_steps: int = 8) -> Dict[str, Any]:
    """One server after the other in this process, over a real socket.
    The zoo's deploy nets are small: this proves the engine's AOT
    compile, donation and decode ladder on the device, not a width."""
    from sparknet_tpu.serve.server import Client

    out: Dict[str, Any] = {}
    rng = np.random.default_rng(0)

    engine, server = _serving_stack("cifar10_quick_deploy.prototxt", "1,8,32")
    try:
        client = Client(server.host, server.port)
        hz = _ok(client.healthz(), "/healthz")
        rows = rng.normal(size=(5, 32, 32, 3)).astype(np.float32)
        batched = _ok(client.classify(rows, top_k=10), "/classify x5")
        for _ in range(classify_requests - 2):
            _ok(client.classify(rows[:3], top_k=10), "/classify x3")
        single = _ok(client.classify(rows[:1], top_k=10), "/classify x1")
        # same row through the 8-row bucket (padded) and the 1-row
        # bucket: padding is per-row independent, so the answers agree.
        # Compared per class, not by rank: random weights leave the ten
        # probabilities close enough for rounding to reorder them.
        def distribution(reply):
            dist = np.zeros(10)
            dist[reply["indices"][0]] = reply["probs"][0]
            return dist

        in_batch, alone = distribution(batched), distribution(single)
        _check(
            np.isfinite(in_batch).all() and abs(in_batch.sum() - 1.0) < 1e-3,
            f"/classify probabilities are not a distribution: {in_batch}",
        )
        _check(
            np.allclose(in_batch, alone, atol=1e-3),
            f"the same row classified differently in a batch {in_batch} "
            f"and alone {alone}",
        )
        m = _ok(client.metrics(), "/metrics.json")
        _check(m["errors"] == 0, f"classify server counted errors: {m}")
        out["classify"] = {
            "requests": m["requests"], "errors": m["errors"],
            "warmup_s": engine.warmup_s, "health": hz.get("status"),
        }
    finally:
        server.stop()

    engine, server = _serving_stack("char_rnn_deploy.prototxt", "1")
    try:
        client = Client(server.host, server.port)
        _ok(client.healthz(), "/healthz")
        prefix = [int(t) for t in rng.integers(0, 96, size=12)]
        first = _ok(
            client.generate(prefix, session="smoke", steps=generate_steps),
            "/generate (new session)",
        )
        grown = prefix + first["tokens"]
        hit = _ok(
            client.generate(grown, session="smoke", steps=generate_steps),
            "/generate (session hit)",
        )
        cold = _ok(
            client.generate(grown, steps=generate_steps),
            "/generate (cold replay)",
        )
        _check(
            first["cache_state"] == "cold" and hit["cache_state"] == "hit"
            and cold["cache_state"] == "cold",
            f"cache states {first['cache_state']}/{hit['cache_state']}/"
            f"{cold['cache_state']}, wanted cold/hit/cold",
        )
        _check(
            hit["steps_run"] < cold["steps_run"],
            f"the session hit stepped {hit['steps_run']} tokens, the cold "
            f"replay {cold['steps_run']}: the cached carry was not used",
        )
        # the carry is donated to every step on an accelerator; a stale
        # or reused buffer shows up as a hit that disagrees with replay
        _check(
            hit["tokens"] == cold["tokens"]
            and np.allclose(hit["probs"], cold["probs"], atol=1e-6),
            f"session hit {hit['tokens']} != cold replay {cold['tokens']}",
        )
        m = _ok(client.metrics(), "/metrics.json")
        _check(m["errors"] == 0, f"generate server counted errors: {m}")
        out["generate"] = {
            "requests": m["requests"], "errors": m["errors"],
            "warmup_s": engine.warmup_s, "tokens": hit["tokens"],
            "hit_steps": hit["steps_run"], "cold_steps": cold["steps_run"],
        }
    finally:
        server.stop()
    out["errors"] = out["classify"]["errors"] + out["generate"]["errors"]
    return out


MULTICHIP_RUNS = (
    ("sync dp=4", ("--parallel", "sync"), 6),
    ("local tau=5 dp=4", ("--parallel", "local", "--tau", "5"), 10),
    ("layout dp=2,tp=2", ("--layout", "dp=2,tp=2"), 6),
)


def multichip(
    batch_size: int = 512,
    bf16: bool = True,
    runs=MULTICHIP_RUNS,
    synthetic_n: Optional[int] = None,
) -> Dict[str, Any]:
    """Phase 1 again over four devices, three ways.  After each, every
    device must have held a comparable share of the bytes — "everything
    on the first device" is the failure this looks for."""
    out: Dict[str, Any] = {}
    for label, flags, iters in runs:
        gc.collect()
        with DeviceBytes() as seen:
            out[label] = train_alexnet(
                batch_size, iters, bf16, extra=flags, synthetic_n=synthetic_n
            )
        out[label]["max_bytes_in_use"] = seen.max_bytes
        print(f"multichip[{label}]: max bytes_in_use per device "
              f"{seen.max_bytes}", flush=True)
        held = [b for b in seen.max_bytes if b is not None]
        if held:  # the backend reports memory (not the CPU rehearsal)
            _check(
                len(held) >= 4 and min(held) > 0
                and min(held) >= 0.25 * max(held),
                f"{label}: device memory is lopsided: {seen.max_bytes}",
            )
    return out


# --------------------------------------------------------------------- driver

def run_phase(name: str, fn: Callable[[], Dict[str, Any]], clock: CompileClock):
    """Run one phase at the boundary that must keep going: any failure
    is recorded with its traceback and the next phase still runs, so one
    call to the chip shows every cause, not the first."""
    print(f"\n=== {name} ===", flush=True)
    before, t0 = clock.read(), time.perf_counter()
    try:
        detail, error = fn(), None
    except Exception as e:  # noqa: BLE001 — reported below, exit code != 0
        traceback.print_exc()
        detail, error = {}, f"{type(e).__name__}: {e}"
    wall_s = time.perf_counter() - t0
    after = clock.read()
    spent = {k: after[k] - before[k] for k in after}
    stats = jax.devices()[0].memory_stats() or {}
    record = {
        "phase": name,
        "ok": error is None,
        "error": error,
        "wall_s": round(wall_s, 2),
        "compile_s": round(spent["compile_s"], 2),
        "rest_s": round(wall_s - spent["compile_s"], 2),
        "cache_hits": spent["cache_hits"],
        "cache_misses": spent["cache_misses"],
        # device 0's high-water mark since the process started (the
        # runtime cannot reset it), and what is still held right now
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
        "detail": detail,
    }
    print(f"{name}: {'PASS' if error is None else 'FAIL'} "
          f"{json.dumps(record, default=str)}", flush=True)
    gc.collect()  # the next phase gets the device memory back
    return record


def versions() -> Dict[str, str]:
    import importlib.metadata as md

    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found platform={dev.platform} "
            f"({dev.device_kind} x{len(devices)}); rehearse the phases on "
            f"a CPU with tests/test_chip_smoke.py",
            file=sys.stderr,
        )
        return 2
    from sparknet_tpu.serve.compile_cache import cache_entries

    cache_dir = compile_cache.enable()
    entries_before = cache_entries(cache_dir)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(devices)} versions={json.dumps(versions())}")
    placed = compile_cache.ENV if os.environ.get(compile_cache.ENV) else None
    print(f"chip_smoke: compile cache {cache_dir} "
          f"({'placed by ' + placed if placed else 'checkout default'}), "
          f"{entries_before} entries before", flush=True)

    clock = CompileClock()
    records = [
        run_phase("train_alexnet", train_alexnet, clock),
        run_phase("train_bert", train_bert, clock),
        run_phase("serve", serve, clock),
    ]
    if len(devices) >= 4:
        records.append(run_phase("multichip", multichip, clock))
    else:
        print(f"\nmultichip: skipped ({len(devices)} device)", flush=True)

    entries_after = cache_entries(cache_dir)
    print(f"\nchip_smoke: compile cache {cache_dir}: {entries_before} entries "
          f"before, {entries_after} after "
          f"(+{entries_after - entries_before})")
    for r in records:
        print(f"chip_smoke: {r['phase']:<14} {'PASS' if r['ok'] else 'FAIL'}  "
              f"wall {r['wall_s']:>7.1f}s  compile {r['compile_s']:>7.1f}s  "
              f"rest {r['rest_s']:>6.1f}s  peak_bytes_in_use "
              f"{r['peak_bytes_in_use']}"
              + (f"  {r['error']}" if r["error"] else ""))
    failed = [r["phase"] for r in records if not r["ok"]]
    result: Dict[str, Any] = {"ok": not failed, "device": device}
    if failed:
        result["failed"] = failed
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    if len(sys.argv) > 1:
        sys.exit("chip_smoke.py takes no arguments")
    sys.exit(main())
