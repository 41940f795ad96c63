"""Inference serving subsystem — the training stack's other half.

The reference (and this repo until now) stops at training: a trained
snapshot could only be exercised by one-shot, compile-per-invocation
tools. ``serve`` turns any zoo prototxt + snapshot into a persistent
engine behind a batched request queue:

- :class:`~sparknet_tpu.serve.engine.InferenceEngine` — weights loaded
  once, ``XLANet.apply`` AOT-compiled per batch-size bucket, requests
  padded up to the nearest bucket.
- :class:`~sparknet_tpu.serve.batcher.MicroBatcher` — thread-safe
  dynamic micro-batching (max-batch / max-latency knobs, bounded-queue
  backpressure, graceful drain).
- :class:`~sparknet_tpu.serve.metrics.ServeMetrics` — per-bucket
  counters, latency histograms, queue-depth / padding-waste gauges,
  dumpable as one JSON line.
- :class:`~sparknet_tpu.serve.server.InferenceServer` /
  :class:`~sparknet_tpu.serve.server.Client` — stdlib HTTP front end
  (``/classify``, ``/healthz``, ``/metrics``) plus the in-process
  client tests and load generators drive.
- :func:`~sparknet_tpu.serve.loadgen.run_loadgen` /
  :func:`~sparknet_tpu.serve.loadgen.run_http_loadgen` — offline and
  over-the-wire closed-loop load generators (``serve --bench``): one
  requests/s and p99 record a run.
- :class:`~sparknet_tpu.serve.router.Router` — the production tier: a
  stateless front load-balancing ``/classify`` over N replica
  processes (spawned via ``supervise/pool.py``), peer-retrying a
  killed replica's in-flight requests, and rolling weight hot-swaps
  one replica at a time.
- :mod:`~sparknet_tpu.serve.hotswap` — snapshot watch: newer
  manifest-verified solverstates roll into serving automatically.
- :mod:`~sparknet_tpu.serve.compile_cache` — per-net persistent XLA
  compile cache; replica restarts skip AOT warmup.
- :mod:`~sparknet_tpu.serve.quantize` — bf16/int8 engine variants:
  per-channel scales captured from verified snapshots at hot-swap
  time, int8 matmul/conv with f32 rescale, precision-keyed compile
  caches, and the router's live ``--quant-ab`` A/B
  (docs/QUANTIZATION.md).
- :mod:`~sparknet_tpu.serve.session` — session-aware serving (ISSUE
  13): a recurrent net's decode step compiled once with the carried
  state as a donated executable argument
  (:class:`~sparknet_tpu.serve.session.DecodeStepper`), the
  LRU-by-hit, generation-tagged per-session state cache
  (:class:`~sparknet_tpu.serve.session.SessionCache`), the engine's
  ``generate`` entry point (``POST /generate``) and the router's
  session-affinity dispatch with counted migrations
  (docs/SERVING.md "Sessions").

See docs/SERVING.md for the architecture and knob reference.
"""

from .batcher import Backpressure, DeadlineExceeded, MicroBatcher
from .engine import InferenceEngine
from .loadgen import run_http_loadgen, run_loadgen
from .metrics import Counter, LatencyHistogram, ServeMetrics
from .router import Router
from .server import Client, InferenceServer
from .session import DecodeStepper, SessionCache

__all__ = [
    "Backpressure",
    "Client",
    "Counter",
    "DeadlineExceeded",
    "DecodeStepper",
    "InferenceEngine",
    "InferenceServer",
    "LatencyHistogram",
    "MicroBatcher",
    "Router",
    "ServeMetrics",
    "SessionCache",
    "run_http_loadgen",
    "run_loadgen",
]
