"""XLANet: a Caffe ``NetParameter`` compiled to pure JAX functions.

This is the TPU-native replacement for the reference's ``CaffeNet``
(Scala wrapper over a native Caffe solver via JavaCPP — SURVEY.md §1-2;
reference mount empty, no file:line). Where ``CaffeNet`` owns a mutable
native net and copies weights across the JNI boundary, ``XLANet`` is a
*compiler*: it walks the layer DAG once at construction (static shape
inference, numpy-only), and exposes

- ``init(rng) -> (WeightCollection, state)`` — filler-initialised params
- ``apply(params, state, batch, train, rng) -> (blobs, new_state)``
- ``loss_and_metrics(blobs)`` — weighted loss-layer sum + metric tops

all pure, all jit/pjit/grad-compatible. The whole forward+backward is
one XLA program; there is no per-layer dispatch at run time and no
host<->device weight copying (the JNI cost center in the reference).

Layout is NHWC (see layers.py). Batches are dicts of blob name ->
array, e.g. ``{"data": (N,H,W,C) float, "label": (N,) int}``.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..proto.caffe_pb import NetParameter
from ..utils.profiling import scope
from .layers import (
    ApplyCtx,
    DATA_LAYER_TYPES,
    LAYER_IMPLS,
    LOSS_LAYER_TYPES,
    Shape,
)
from .weights import WeightCollection


def layer_scope(lp) -> str:
    """A prototxt layer's scope, from its own type and name:
    ``convolution.conv1``, ``lrn.norm1``, ``pooling.inception_3a_pool``
    (a ``/`` or a parenthesis of the name reads ``_``: the scope is one
    element of an operation's path)."""
    return f"{lp.type.lower()}.{re.sub(r'[^\w.-]', '_', lp.name)}"


class XLANet:
    def __init__(
        self,
        net: NetParameter,
        phase: str = "TRAIN",
        input_shapes: Optional[Dict[str, Shape]] = None,
        compute_dtype: Any = jnp.float32,
        remat: bool = False,
    ):
        """``remat``: wrap each layer's apply in ``jax.checkpoint`` so
        only layer-boundary blobs survive the forward pass — intra-layer
        intermediates (BN normalization, LRN chains, dropout masks)
        recompute during backward. The HBM-for-FLOPs trade for deep
        BN-heavy nets (ResNet-50) at large batch; dropout recompute is
        exact (masks are PRNG-keyed, not saved)."""
        self.net = net
        self.phase = phase
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.layers = [
            l for l in net.layers_for_phase(phase) if l.type not in ("Silence",)
        ]
        input_shapes = dict(input_shapes or {})
        # deploy-style declared inputs (NetParameter.input / input_shape,
        # given in Caffe NCHW order -> converted to NHWC here)
        for name, shape in zip(net.inputs, net.input_shapes):
            if name not in input_shapes:
                if len(shape) == 4:
                    n, c, h, w = shape
                    input_shapes[name] = (n, h, w, c)
                else:
                    input_shapes[name] = tuple(shape)

        self.input_names: List[str] = list(net.inputs)
        self.blob_shapes: Dict[str, Shape] = dict(input_shapes)
        self._infer_shapes(input_shapes)

    # ------------------------------------------------------------------
    def _infer_shapes(self, input_shapes: Dict[str, Shape]) -> None:
        for lp in self.layers:
            if lp.type in DATA_LAYER_TYPES:
                for top in lp.top:
                    if top not in self.blob_shapes:
                        if top not in input_shapes:
                            raise ValueError(
                                f"data layer {lp.name!r} top {top!r}: shape not "
                                f"provided via input_shapes"
                            )
                        self.blob_shapes[top] = tuple(input_shapes[top])
                    if top not in self.input_names:
                        self.input_names.append(top)
                continue
            impl = LAYER_IMPLS.get(lp.type)
            if impl is None:
                raise NotImplementedError(
                    f"layer {lp.name!r}: type {lp.type!r} not implemented"
                )
            in_shapes = [self.blob_shapes[b] for b in lp.bottom]
            out_shapes = impl.infer(lp, in_shapes)
            for top, s in zip(lp.top, out_shapes):
                self.blob_shapes[top] = tuple(s)

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Tuple[WeightCollection, Dict[str, Any]]:
        params: WeightCollection = {}
        state: Dict[str, Any] = {}
        for i, lp in enumerate(self.layers):
            if lp.type in DATA_LAYER_TYPES:
                continue
            impl = LAYER_IMPLS[lp.type]
            in_shapes = [self.blob_shapes[b] for b in lp.bottom]
            p = impl.init(lp, jax.random.fold_in(rng, i), in_shapes)
            if p:
                params[lp.name] = p
            if hasattr(impl, "init_state"):
                st = impl.init_state(lp, in_shapes)
                if st:
                    state[lp.name] = st
        return params, state

    # ------------------------------------------------------------------
    def apply(
        self,
        params: WeightCollection,
        state: Dict[str, Any],
        batch: Dict[str, jax.Array],
        *,
        train: Optional[bool] = None,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[Dict[str, jax.Array], Dict[str, Any]]:
        train = (self.phase == "TRAIN") if train is None else train
        blobs: Dict[str, jax.Array] = dict(batch)
        new_state: Dict[str, Any] = dict(state)
        for i, lp in enumerate(self.layers):
            if lp.type in DATA_LAYER_TYPES:
                continue
            impl = LAYER_IMPLS[lp.type]
            with scope("rng"):
                layer_rng = (
                    jax.random.fold_in(rng, i) if rng is not None else None
                )
            inputs = [blobs[b] for b in lp.bottom]

            def run_layer(p, st_in, inputs_, rng_, lp=lp, impl=impl):
                ctx = ApplyCtx(
                    train=train, rng=rng_,
                    compute_dtype=self.compute_dtype,
                )
                return impl.apply(lp, p, st_in, inputs_, ctx)

            if self.remat and train:
                run_layer = jax.checkpoint(run_layer)
            # every layer is a scope of the device's time by its own
            # type and name (utils/profiling.scope): nobody writes one
            with scope(layer_scope(lp)):
                outputs, st = run_layer(
                    params.get(lp.name, {}), state.get(lp.name), inputs,
                    layer_rng,
                )
            for top, out in zip(lp.top, outputs):
                blobs[top] = out
            if st is not None:
                new_state[lp.name] = st
        return blobs, new_state

    # ------------------------------------------------------------------
    def loss_and_metrics(
        self, blobs: Dict[str, jax.Array]
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Weighted sum of loss tops (Caffe: loss layers default weight 1,
        everything else 0) plus scalar metric tops (loss / accuracy)."""
        total = jnp.asarray(0.0, jnp.float32)
        metrics: Dict[str, jax.Array] = {}
        for lp in self.layers:
            is_loss = lp.type in LOSS_LAYER_TYPES
            for ti, top in enumerate(lp.top):
                w = lp.loss_weight[ti] if ti < len(lp.loss_weight) else (1.0 if is_loss else 0.0)
                if w:
                    total = total + w * jnp.sum(blobs[top].astype(jnp.float32))
                if is_loss or lp.type == "Accuracy":
                    metrics[top] = blobs[top]
        return total, metrics

    # ------------------------------------------------------------------
    def dummy_batch(self, dtype=jnp.float32) -> Dict[str, jax.Array]:
        """Zeros batch matching the net's declared inputs (for tracing)."""
        out = {}
        for name in self.input_names:
            s = self.blob_shapes[name]
            if name == "label":
                out[name] = jnp.zeros(s, jnp.int32)
            else:
                out[name] = jnp.zeros(s, dtype)
        return out

    def param_specs(self) -> Dict[str, Dict[str, Tuple[float, float]]]:
        """Per-param (lr_mult, decay_mult) from the prototxt ``param {}``
        entries — consumed by the solver. Spec index i maps to the
        layer's i-th blob in ITS declared order (Caffe's blob order):
        weight-then-bias for most layers, but e.g. PReLU's single blob
        is the slope — layer impls override via ``PARAM_ORDER``."""
        specs: Dict[str, Dict[str, Tuple[float, float]]] = {}
        for lp in self.layers:
            if lp.type in DATA_LAYER_TYPES:
                continue
            impl = LAYER_IMPLS.get(lp.type)
            order = getattr(impl, "PARAM_ORDER", ("weight", "bias"))
            sp: Dict[str, Tuple[float, float]] = {}
            for idx, pname in enumerate(order):
                spec = lp.params[idx] if idx < len(lp.params) else None
                sp[pname] = (
                    spec.lr_mult if spec else 1.0,
                    spec.decay_mult if spec else 1.0,
                )
            specs[lp.name] = sp
        return specs
