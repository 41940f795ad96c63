"""Caffe weight interchange: wire format against google.protobuf, and
layout transposition against NCHW math (torch oracle)."""

import numpy as np
import pytest
import jax.numpy as jnp

from google.protobuf import descriptor_pb2

from sparknet_tpu.proto import caffe_pb, caffemodel, wire
from sparknet_tpu.nets.xlanet import XLANet

T = descriptor_pb2.FieldDescriptorProto


def _get_classes():
    """Dynamic caffe.proto subset via the real protobuf runtime — the
    encoding oracle for our hand-rolled wire reader/writer."""
    from google.protobuf import descriptor_pool, message_factory

    pool = descriptor_pool.DescriptorPool()
    pool.Add(_build_fdp())
    if hasattr(message_factory, "GetMessageClassesForFiles"):
        classes = message_factory.GetMessageClassesForFiles(
            ["caffe_oracle.proto"], pool
        )
        return {k.split(".")[-1]: v for k, v in classes.items()}
    factory = message_factory.MessageFactory(pool)
    names = ["BlobShape", "BlobProto", "LayerParameter", "NetParameter"]
    return {
        n: factory.GetPrototype(pool.FindMessageTypeByName(f"caffeoracle.{n}"))
        for n in names
    }


def _build_fdp():
    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.name = "caffe_oracle.proto"
    fdp.package = "caffeoracle"
    bs = fdp.message_type.add()
    bs.name = "BlobShape"
    f = bs.field.add()
    f.name, f.number, f.type, f.label = "dim", 1, T.TYPE_INT64, T.LABEL_REPEATED
    f.options.packed = True
    bp = fdp.message_type.add()
    bp.name = "BlobProto"
    for name, num in (("num", 1), ("channels", 2), ("height", 3), ("width", 4)):
        f = bp.field.add()
        f.name, f.number, f.type, f.label = name, num, T.TYPE_INT32, T.LABEL_OPTIONAL
    f = bp.field.add()
    f.name, f.number, f.type, f.label = "data", 5, T.TYPE_FLOAT, T.LABEL_REPEATED
    f.options.packed = True
    f = bp.field.add()
    f.name, f.number, f.type, f.label = "shape", 7, T.TYPE_MESSAGE, T.LABEL_OPTIONAL
    f.type_name = ".caffeoracle.BlobShape"
    lp = fdp.message_type.add()
    lp.name = "LayerParameter"
    f = lp.field.add()
    f.name, f.number, f.type, f.label = "name", 1, T.TYPE_STRING, T.LABEL_OPTIONAL
    f = lp.field.add()
    f.name, f.number, f.type, f.label = "type", 2, T.TYPE_STRING, T.LABEL_OPTIONAL
    f = lp.field.add()
    f.name, f.number, f.type, f.label = "blobs", 7, T.TYPE_MESSAGE, T.LABEL_REPEATED
    f.type_name = ".caffeoracle.BlobProto"
    np_ = fdp.message_type.add()
    np_.name = "NetParameter"
    f = np_.field.add()
    f.name, f.number, f.type, f.label = "name", 1, T.TYPE_STRING, T.LABEL_OPTIONAL
    f = np_.field.add()
    f.name, f.number, f.type, f.label = "layer", 100, T.TYPE_MESSAGE, T.LABEL_REPEATED
    f.type_name = ".caffeoracle.LayerParameter"
    return fdp


def _oracle_model(conv_w, conv_b, ip_w, ip_b):
    """Serialize a NetParameter with the real protobuf runtime."""
    C = _get_classes()
    net = C["NetParameter"]()
    net.name = "oracle"
    l1 = net.layer.add()
    l1.name, l1.type = "conv1", "Convolution"
    b = l1.blobs.add()
    b.shape.dim.extend(conv_w.shape)
    b.data.extend(conv_w.reshape(-1).tolist())
    b = l1.blobs.add()
    b.shape.dim.extend(conv_b.shape)
    b.data.extend(conv_b.tolist())
    l2 = net.layer.add()
    l2.name, l2.type = "ip1", "InnerProduct"
    b = l2.blobs.add()
    b.shape.dim.extend(ip_w.shape)
    b.data.extend(ip_w.reshape(-1).tolist())
    b = l2.blobs.add()
    b.shape.dim.extend(ip_b.shape)
    b.data.extend(ip_b.tolist())
    return net.SerializeToString()


NET_TXT = """
name: "tiny"
layer { name: "d" type: "Input" top: "data" top: "label" }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "c1"
        convolution_param { num_output: 4 kernel_size: 3 pad: 1 stride: 1 } }
layer { name: "relu1" type: "ReLU" bottom: "c1" top: "c1" }
layer { name: "ip1" type: "InnerProduct" bottom: "c1" top: "ip1"
        inner_product_param { num_output: 5 } }
"""


def _make_net():
    npm = caffe_pb.load_net(NET_TXT, is_path=False)
    shapes = {"data": (2, 6, 6, 3), "label": (2,)}
    return XLANet(npm, "TRAIN", shapes)


def _rand_weights(seed=0):
    rng = np.random.default_rng(seed)
    conv_w = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)  # OIHW
    conv_b = rng.normal(size=(4,)).astype(np.float32)
    ip_w = rng.normal(size=(5, 4 * 6 * 6)).astype(np.float32)  # (out, CHW)
    ip_b = rng.normal(size=(5,)).astype(np.float32)
    return conv_w, conv_b, ip_w, ip_b


def test_wire_decodes_protobuf_encoding():
    conv_w, conv_b, ip_w, ip_b = _rand_weights()
    payload = _oracle_model(conv_w, conv_b, ip_w, ip_b)
    name, blobs = caffemodel.load_caffemodel(payload)
    assert name == "oracle"
    np.testing.assert_array_equal(blobs["conv1"][0], conv_w)
    np.testing.assert_array_equal(blobs["conv1"][1], conv_b)
    np.testing.assert_array_equal(blobs["ip1"][0], ip_w)
    np.testing.assert_array_equal(blobs["ip1"][1], ip_b)


def test_import_matches_nchw_math():
    """Imported weights must reproduce Caffe's NCHW forward bit-for-bit
    (torch conv/linear as the NCHW oracle)."""
    import torch
    import torch.nn.functional as F

    conv_w, conv_b, ip_w, ip_b = _rand_weights()
    payload = _oracle_model(conv_w, conv_b, ip_w, ip_b)
    net = _make_net()
    imported, _ = caffemodel.import_caffemodel(payload, net)
    params = {
        k: {n: jnp.asarray(a) for n, a in v.items()}
        for k, v in imported.items()
    }

    rng = np.random.default_rng(1)
    x_nchw = rng.normal(size=(2, 3, 6, 6)).astype(np.float32)
    # caffe forward in torch: conv(pad1) -> relu -> flatten CHW -> linear
    tx = torch.from_numpy(x_nchw)
    ty = F.relu(F.conv2d(tx, torch.from_numpy(conv_w),
                         torch.from_numpy(conv_b), padding=1))
    t_out = (ty.flatten(1) @ torch.from_numpy(ip_w).T
             + torch.from_numpy(ip_b)).numpy()

    batch = {
        "data": jnp.asarray(np.transpose(x_nchw, (0, 2, 3, 1))),
        "label": jnp.zeros((2,), jnp.int32),
    }
    blobs, _ = net.apply(params, {}, batch, train=False, rng=None)
    np.testing.assert_allclose(
        np.asarray(blobs["ip1"]), t_out, rtol=1e-4, atol=1e-5
    )


def test_export_round_trips(tmp_path):
    conv_w, conv_b, ip_w, ip_b = _rand_weights(3)
    payload = _oracle_model(conv_w, conv_b, ip_w, ip_b)
    net = _make_net()
    imported, _ = caffemodel.import_caffemodel(payload, net)
    out = str(tmp_path / "rt.caffemodel")
    caffemodel.export_caffemodel(out, net, imported)
    # the real protobuf runtime must parse our writer's output
    C = _get_classes()
    msg = C["NetParameter"]()
    msg.ParseFromString(open(out, "rb").read())
    got = {l.name: l for l in msg.layer}
    w = np.asarray(got["conv1"].blobs[0].data, np.float32).reshape(
        tuple(got["conv1"].blobs[0].shape.dim)
    )
    np.testing.assert_allclose(w, conv_w, rtol=1e-6)
    w = np.asarray(got["ip1"].blobs[0].data, np.float32).reshape(
        tuple(got["ip1"].blobs[0].shape.dim)
    )
    np.testing.assert_allclose(w, ip_w, rtol=1e-6)


def test_binaryproto_mean(tmp_path):
    C = _get_classes()
    mean_chw = np.arange(3 * 4 * 5, dtype=np.float32).reshape(3, 4, 5)
    b = C["BlobProto"]()
    b.channels, b.height, b.width = 3, 4, 5
    b.num = 1
    b.data.extend(mean_chw.reshape(-1).tolist())
    out = caffemodel.load_binaryproto_mean(b.SerializeToString())
    np.testing.assert_array_equal(out, np.transpose(mean_chw, (1, 2, 0)))


def test_solver_export_import_round_trip(tmp_path):
    """Solver.export_weights -> Solver.load_weights reproduces params
    exactly (the .caffemodel interchange at the app level)."""
    from sparknet_tpu.solver.trainer import Solver

    sp = caffe_pb.load_solver(
        "base_lr: 0.01 lr_policy: 'fixed' max_iter: 10", is_path=False
    )
    shapes = {"data": (2, 6, 6, 3), "label": (2,)}
    npm = caffe_pb.load_net(NET_TXT, is_path=False)
    s1 = Solver(sp, shapes, net_param=npm, seed=1)
    path = str(tmp_path / "w.caffemodel")
    s1.export_weights(path)

    s2 = Solver(sp, shapes, net_param=npm, seed=2)  # different init
    s2.load_weights(path)
    for layer, ps in s1.params.items():
        for name, arr in ps.items():
            np.testing.assert_allclose(
                np.asarray(s2.params[layer][name]), np.asarray(arr),
                rtol=1e-6, err_msg=f"{layer}.{name}",
            )


def test_legacy_v1_layers_field():
    """V1 nets store weights in NetParameter.layers (field 2)."""
    conv_w = np.ones((2, 3, 1, 1), np.float32)
    blob = (
        caffemodel.wire.encode_packed_floats(5, conv_w.reshape(-1))
        + wire.encode_bytes_field(
            7, b"".join(wire.encode_varint_field(1, d) for d in conv_w.shape)
        )
    )
    v1_layer = (
        wire.encode_string_field(4, "convA")
        + wire.encode_bytes_field(6, blob)
    )
    net = wire.encode_string_field(1, "v1net") + wire.encode_bytes_field(
        2, v1_layer
    )
    name, blobs = caffemodel.load_caffemodel(net)
    assert name == "v1net"
    np.testing.assert_array_equal(blobs["convA"][0], conv_w)


def test_prelu_bias_embed_interchange(tmp_path):
    """Single-blob layers with non-'weight' param names (PReLU slope,
    Bias bias) and Embed round-trip through .caffemodel import/export
    into the params XLANet actually reads."""
    net_txt = """
name: "pb"
layer { name: "data" type: "Input" top: "data" }
layer { name: "ids" type: "Input" top: "ids" }
layer { name: "act" type: "PReLU" bottom: "data" top: "act" }
layer { name: "sh" type: "Bias" bottom: "act" top: "sh" }
layer { name: "emb" type: "Embed" bottom: "ids" top: "emb"
        embed_param { num_output: 3 input_dim: 5
          weight_filler { type: "gaussian" std: 1.0 } } }
"""
    npm = caffe_pb.load_net(net_txt, is_path=False)
    net = XLANet(npm, "TRAIN", {"data": (2, 4), "ids": (2,)})
    import jax

    params, state = net.init(jax.random.PRNGKey(0))
    # give recognisable values, export, then re-import
    params = {k: {n: jnp.asarray(np.arange(v.size, dtype=np.float32).reshape(v.shape) + i)
                  for i, (n, v) in enumerate(sorted(p.items()))}
              for k, p in params.items()}
    out = str(tmp_path / "pb.caffemodel")
    caffemodel.export_caffemodel(out, net, params)
    imported, _ = caffemodel.import_caffemodel(open(out, "rb").read(), net)
    assert set(imported["act"]) == {"slope"}
    assert set(imported["sh"]) == {"bias"}
    np.testing.assert_allclose(
        imported["act"]["slope"], np.asarray(params["act"]["slope"]).reshape(-1)
    )
    np.testing.assert_allclose(
        imported["sh"]["bias"], np.asarray(params["sh"]["bias"]).reshape(-1)
    )
    # Embed keeps its (input_dim, num_output) table through the generic path
    got = caffemodel.merge_into(jax.device_get(net.init(jax.random.PRNGKey(1))[0]), imported)
    assert got["emb"]["weight"].shape == (5, 3)
    np.testing.assert_allclose(
        got["emb"]["weight"], np.asarray(params["emb"]["weight"]), rtol=1e-6
    )


def test_lstm_caffemodel_layout_round_trip(tmp_path):
    """Recurrent blobs are (out, in) in Caffe; import must transpose to
    our (in, out) and export must invert it — verified by writing a
    Caffe-layout model by hand, importing, and re-exporting."""
    import jax

    net_txt = """
name: "seq"
layer { name: "x" type: "Input" top: "x" }
layer { name: "lstm" type: "LSTM" bottom: "x" top: "lstm"
        recurrent_param { num_output: 3
          weight_filler { type: "xavier" } } }
"""
    npm = caffe_pb.load_net(net_txt, is_path=False)
    net = XLANet(npm, "TRAIN", {"x": (4, 2, 5)})
    rng = np.random.default_rng(9)
    w_xc = rng.normal(size=(12, 5)).astype(np.float32)   # Caffe (4H, in)
    b = rng.normal(size=(12,)).astype(np.float32)
    w_hc = rng.normal(size=(12, 3)).astype(np.float32)   # Caffe (4H, H)
    layer_msg = wire.encode_string_field(1, "lstm") + wire.encode_string_field(
        2, "LSTM"
    )
    for arr in (w_xc, b, w_hc):
        blob = caffemodel.wire.encode_packed_floats(5, arr.reshape(-1)) + \
            wire.encode_bytes_field(
                7,
                b"".join(wire.encode_varint_field(1, d) for d in arr.shape),
            )
        layer_msg += wire.encode_bytes_field(7, blob)
    payload = wire.encode_bytes_field(100, layer_msg)
    imported, _ = caffemodel.import_caffemodel(payload, net)
    np.testing.assert_allclose(imported["lstm"]["weight"], w_xc.T)
    np.testing.assert_allclose(imported["lstm"]["hidden_weight"], w_hc.T)
    np.testing.assert_allclose(imported["lstm"]["bias"], b)
    # shapes now match the net's own params
    init_params, _ = net.init(jax.random.PRNGKey(0))
    for k, v in imported["lstm"].items():
        assert v.shape == tuple(init_params["lstm"][k].shape), k

    out = str(tmp_path / "seq.caffemodel")
    caffemodel.export_caffemodel(
        out, net, {"lstm": {k: jnp.asarray(v) for k, v in imported["lstm"].items()}}
    )
    _, blobs = caffemodel.load_caffemodel(open(out, "rb").read())
    np.testing.assert_allclose(blobs["lstm"][0], w_xc, rtol=1e-6)
    np.testing.assert_allclose(blobs["lstm"][2], w_hc, rtol=1e-6)


def test_load_weights_comma_list(tmp_path):
    """caffe binary semantics: --weights a.caffemodel,b.caffemodel
    overlays in order, later files winning on overlapping layers."""
    import jax

    from sparknet_tpu.proto.caffe_pb import SolverParameter
    from sparknet_tpu.solver.trainer import Solver

    net_txt = """
name: "two"
layer { name: "data" type: "Input" top: "data" }
layer { name: "label" type: "Input" top: "label" }
layer { name: "ipA" type: "InnerProduct" bottom: "data" top: "ipA"
        inner_product_param { num_output: 3
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "ipB" type: "InnerProduct" bottom: "ipA" top: "ipB"
        inner_product_param { num_output: 2
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ipB" bottom: "label" top: "loss" }
"""
    sp = SolverParameter(base_lr=0.1, lr_policy="fixed", max_iter=1)
    sp.net_param = caffe_pb.load_net(net_txt, is_path=False)
    solver = Solver(sp, {"data": (2, 4), "label": (2,)})
    from sparknet_tpu.proto import caffemodel as cm

    # file 1: sets both layers; file 2: overrides only ipB
    p1 = {"ipA": {"weight": np.full((4, 3), 1.0, np.float32)},
          "ipB": {"weight": np.full((3, 2), 2.0, np.float32)}}
    p2 = {"ipB": {"weight": np.full((3, 2), 9.0, np.float32)}}
    f1, f2 = str(tmp_path / "a.caffemodel"), str(tmp_path / "b.caffemodel")
    cm.export_caffemodel(f1, solver.train_net, p1)
    cm.export_caffemodel(f2, solver.train_net, p2)
    solver.load_weights(f"{f1},{f2}")
    got = jax.device_get(solver.params)
    np.testing.assert_allclose(got["ipA"]["weight"], 1.0)
    np.testing.assert_allclose(got["ipB"]["weight"], 9.0)  # later wins
