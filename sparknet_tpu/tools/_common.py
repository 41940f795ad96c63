"""Shared plumbing for the evaluation-side tools (``caffe test``,
``extract_features``): phase-net construction over a prototxt's own
on-disk data source, and trained-weight overlay.

Kept out of the per-tool modules so data-layer resolution, transformer
policy, and weight merging cannot drift between tools."""

from __future__ import annotations


DATA_SOURCE_TYPES = ("Data", "ImageData", "HDF5Data")


def find_data_layer(net_param, phase: str):
    """The first on-disk-source data layer of the phase, or None."""
    return next(
        (
            l
            for l in net_param.layers_for_phase(phase)
            if l.type in DATA_SOURCE_TYPES
        ),
        None,
    )


def build_phase_net(net_param, model_dir: str, phase: str):
    """(net, dataset, transformer, batch_size) for a phase, reading the
    net's own data layer: batch size and transform_param are honoured
    exactly like training, and a missing ``mean_file`` is regenerated
    from the TRAIN split (what training subtracted), collapsing to the
    per-channel mean if the TRAIN source's resolution differs."""
    from ..apps.cifar_app import (
        _batch_size,
        _dataset_mean,
        make_transformer,
        source_data_shape,
    )
    from ..data.caffe_layers import dataset_from_layer
    from ..nets.xlanet import XLANet

    data_layer = find_data_layer(net_param, phase)
    ds = dataset_from_layer(data_layer, model_dir)
    if ds is None:
        return None, None, None, 0
    bs = _batch_size(data_layer, 32)

    def regenerated_mean():
        mean_ds = dataset_from_layer(
            find_data_layer(net_param, "TRAIN"), model_dir
        )
        src = mean_ds if mean_ds is not None else ds
        m = _dataset_mean(src)
        if (
            src is not ds
            and m.ndim == 3
            and tuple(m.shape[:2]) != tuple(ds.sample_shape()[:2])
        ):
            m = m.mean((0, 1))
        return m

    tf = make_transformer(data_layer, phase == "TRAIN", model_dir,
                          regenerated_mean)
    h, w, c = source_data_shape(ds, tf.crop_size, True, None)
    net = XLANet(net_param, phase, {"data": (bs, h, w, c), "label": (bs,)})
    return net, ds, tf, bs


def load_weights(net, params, state, weights: str):
    """Overlay trained weights (.caffemodel binary NetParameter, or
    this framework's .npz WeightCollection) onto init params/state.
    Comma-separated lists overlay in order with later files winning,
    like the caffe binary's CopyTrainedLayersFrom."""
    import jax
    import jax.numpy as jnp

    from ..proto import caffemodel as cm

    p = jax.device_get(params)
    s = jax.device_get(state)
    for one in weights.split(","):
        one = one.strip()
        if not one:
            continue
        if one.endswith(".npz"):
            from ..nets.weights import load_npz

            p = cm.merge_into(p, load_npz(one))
            continue
        imported, st = cm.import_caffemodel(one, net)
        p = cm.merge_into(p, imported)
        if st:
            s = cm.merge_into(s, st)
    to_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
    return to_dev(p), to_dev(s)


def batch_transform_fn(tf):
    """The host-side per-batch transform every eval tool feeds with."""
    import numpy as np

    def transform(batch, rng):
        return {
            "data": np.asarray(tf(batch["data"], rng), np.float32),
            "label": np.asarray(batch["label"], np.int32),
        }

    return transform
