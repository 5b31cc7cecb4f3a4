"""Serialization sweep — every exported layer round-trips through
save → fresh rebuild → load → bit-identical output, the ``SerializerSpec``
discipline (``zoo/src/test/.../serializer/SerializerSpec.scala``, SURVEY §4):
the reference auto-enumerates every layer class and fails the build if one
isn't serialization-tested.

Here "serialize" means what every persistence path in this framework does
(ZooModel .npz, CheckpointManager): flatten params+state to leaves in
deterministic tree order, write, rebuild the SAME topology fresh (different
rng), install leaves by order, and require identical outputs. Catches
leaf-order nondeterminism, build/init asymmetries, and state handling bugs.
"""

import tempfile

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.pipeline.api.keras import layers as L

B = 2  # batch


def _input_for(kind, shape, rng):
    if kind == "int":
        return rng.integers(0, 7, (B,) + shape).astype(np.int32)
    if kind == "float_pos":  # strictly positive (Log/Sqrt domains)
        return rng.uniform(0.1, 2.0, (B,) + shape).astype(np.float32)
    return rng.normal(size=(B,) + shape).astype(np.float32)


_ROPE = {"rope_type": "default", "rope_theta": 10000.0}

# (factory, input_shape(s) sans batch, input kind) — one per exported layer
CASES = {
    "Dense": (lambda: L.Dense(5), (4,), "float"),
    "Dense_act": (lambda: L.Dense(5, activation="relu", bias=False), (4,), "float"),
    "SparseDense": (lambda: L.SparseDense(5), (4,), "float"),
    "Activation": (lambda: L.Activation("tanh"), (4,), "float"),
    "Dropout": (lambda: L.Dropout(0.3), (4,), "float"),
    "Flatten": (lambda: L.Flatten(), (3, 4), "float"),
    "Reshape": (lambda: L.Reshape((4, 3)), (3, 4), "float"),
    "Permute": (lambda: L.Permute((2, 1)), (3, 4), "float"),
    "RepeatVector": (lambda: L.RepeatVector(3), (4,), "float"),
    "Select": (lambda: L.Select(1, 2), (5, 4), "float"),
    "Squeeze": (lambda: L.Squeeze(2), (3, 1), "float"),
    "ExpandDim": (lambda: L.ExpandDim(1), (3,), "float"),
    "Narrow": (lambda: L.Narrow(1, 1, 2), (5, 4), "float"),
    "Masking": (lambda: L.Masking(0.0), (3, 4), "float"),
    "GaussianNoise": (lambda: L.GaussianNoise(0.1), (4,), "float"),
    "GaussianDropout": (lambda: L.GaussianDropout(0.1), (4,), "float"),
    "TimeDistributed": (lambda: L.TimeDistributed(L.Dense(5)), (3, 4), "float"),
    "Highway": (lambda: L.Highway(), (4,), "float"),
    "Embedding": (lambda: L.Embedding(7, 6), (3,), "int"),
    # row-sharded engine: on the default model=1 mesh this is the
    # unsharded dedup'd lookup, numerically the plain gather
    "ShardedEmbedding": (lambda: L.ShardedEmbedding(7, 6), (3,), "int"),
    # multi-hot bag over the vocab (not id list): input width = vocab size
    "SparseEmbedding": (lambda: L.SparseEmbedding(7, 6), (7,), "float"),
    "WordEmbedding": (lambda: L.WordEmbedding(
        np.arange(42, dtype=np.float32).reshape(7, 6)), (3,), "int"),
    "WordEmbedding_trainable": (lambda: L.WordEmbedding(
        np.arange(42, dtype=np.float32).reshape(7, 6), trainable=True),
        (3,), "int"),
    "BatchNormalization": (lambda: L.BatchNormalization(), (4,), "float"),
    "LayerNorm": (lambda: L.LayerNorm(), (4,), "float"),
    "L2Normalize": (lambda: L.L2Normalize(), (4,), "float"),
    "Convolution1D": (lambda: L.Convolution1D(5, 3), (8, 4), "float"),
    "Convolution2D": (lambda: L.Convolution2D(5, 3, 3), (8, 8, 3), "float"),
    "AtrousConvolution1D": (lambda: L.AtrousConvolution1D(5, 3, atrous_rate=2),
                            (10, 4), "float"),
    "AtrousConvolution2D": (lambda: L.AtrousConvolution2D(
        5, 3, 3, atrous_rate=(2, 2)), (10, 10, 3), "float"),
    "SeparableConvolution2D": (lambda: L.SeparableConvolution2D(6, 3, 3),
                               (8, 8, 3), "float"),
    "DepthwiseConvolution2D": (lambda: L.DepthwiseConvolution2D(
        3, 3, depth_multiplier=2), (8, 8, 3), "float"),
    "Deconvolution2D": (lambda: L.Deconvolution2D(5, 3, 3), (6, 6, 3), "float"),
    "LocallyConnected1D": (lambda: L.LocallyConnected1D(5, 3), (8, 4), "float"),
    "Cropping1D": (lambda: L.Cropping1D((1, 1)), (8, 4), "float"),
    "Cropping2D": (lambda: L.Cropping2D(((1, 1), (1, 1))), (8, 8, 3), "float"),
    "UpSampling1D": (lambda: L.UpSampling1D(2), (4, 3), "float"),
    "UpSampling2D": (lambda: L.UpSampling2D((2, 2)), (4, 4, 3), "float"),
    "ZeroPadding1D": (lambda: L.ZeroPadding1D(1), (4, 3), "float"),
    "ZeroPadding2D": (lambda: L.ZeroPadding2D((1, 1)), (4, 4, 3), "float"),
    "MaxPooling1D": (lambda: L.MaxPooling1D(2), (8, 3), "float"),
    "MaxPooling2D": (lambda: L.MaxPooling2D((2, 2)), (8, 8, 3), "float"),
    "AveragePooling1D": (lambda: L.AveragePooling1D(2), (8, 3), "float"),
    "AveragePooling2D": (lambda: L.AveragePooling2D((2, 2)), (8, 8, 3), "float"),
    "GlobalMaxPooling1D": (lambda: L.GlobalMaxPooling1D(), (8, 3), "float"),
    "GlobalMaxPooling2D": (lambda: L.GlobalMaxPooling2D(), (4, 4, 3), "float"),
    "GlobalAveragePooling1D": (lambda: L.GlobalAveragePooling1D(), (8, 3), "float"),
    "GlobalAveragePooling2D": (lambda: L.GlobalAveragePooling2D(),
                               (4, 4, 3), "float"),
    # --- advanced activations ---
    "LeakyReLU": (lambda: L.LeakyReLU(0.1), (4,), "float"),
    "ELU": (lambda: L.ELU(), (4,), "float"),
    "PReLU": (lambda: L.PReLU(), (4,), "float"),
    "SReLU": (lambda: L.SReLU(), (4,), "float"),
    "ThresholdedReLU": (lambda: L.ThresholdedReLU(0.5), (4,), "float"),
    "RReLU": (lambda: L.RReLU(), (4,), "float"),
    "Softmax": (lambda: L.Softmax(), (4,), "float"),
    "HardTanh": (lambda: L.HardTanh(), (4,), "float"),
    "HardShrink": (lambda: L.HardShrink(), (4,), "float"),
    "SoftShrink": (lambda: L.SoftShrink(), (4,), "float"),
    "Threshold": (lambda: L.Threshold(0.1, -1.0), (4,), "float"),
    "BinaryThreshold": (lambda: L.BinaryThreshold(), (4,), "float"),
    # --- elementwise ---
    "AddConstant": (lambda: L.AddConstant(2.0), (4,), "float"),
    "MulConstant": (lambda: L.MulConstant(0.5), (4,), "float"),
    "Negative": (lambda: L.Negative(), (4,), "float"),
    "Power": (lambda: L.Power(2.0, 1.5, 0.1), (4,), "float"),
    "Exp": (lambda: L.Exp(), (4,), "float"),
    "Log": (lambda: L.Log(), (7,), "float_pos"),
    "Sqrt": (lambda: L.Sqrt(), (7,), "float_pos"),
    "Square": (lambda: L.Square(), (4,), "float"),
    "Mul": (lambda: L.Mul(), (4,), "float"),
    "CAdd": (lambda: L.CAdd((4,)), (4,), "float"),
    "CMul": (lambda: L.CMul((4,)), (4,), "float"),
    "Scale": (lambda: L.Scale((4,)), (4,), "float"),
    "Max": (lambda: L.Max(1), (5, 4), "float"),
    "Expand": (lambda: L.Expand((3, 4)), (1, 4), "float"),
    "ResizeBilinear": (lambda: L.ResizeBilinear(6, 8), (4, 4, 3), "float"),
    # --- 3D family + structured extras ---
    "Convolution3D": (lambda: L.Convolution3D(4, 2, 2, 2), (5, 6, 6, 3),
                      "float"),
    "MaxPooling3D": (lambda: L.MaxPooling3D(), (4, 4, 4, 3), "float"),
    "AveragePooling3D": (lambda: L.AveragePooling3D(), (4, 4, 4, 3), "float"),
    "GlobalMaxPooling3D": (lambda: L.GlobalMaxPooling3D(), (4, 4, 4, 3),
                           "float"),
    "GlobalAveragePooling3D": (lambda: L.GlobalAveragePooling3D(),
                               (4, 4, 4, 3), "float"),
    "ZeroPadding3D": (lambda: L.ZeroPadding3D(), (3, 3, 3, 2), "float"),
    "Cropping3D": (lambda: L.Cropping3D(), (5, 5, 5, 2), "float"),
    "UpSampling3D": (lambda: L.UpSampling3D(), (2, 2, 2, 3), "float"),
    "SpatialDropout1D": (lambda: L.SpatialDropout1D(0.3), (6, 3), "float"),
    "SpatialDropout2D": (lambda: L.SpatialDropout2D(0.3), (4, 4, 3), "float"),
    "SpatialDropout3D": (lambda: L.SpatialDropout3D(0.3), (3, 3, 3, 2),
                         "float"),
    "ConvLSTM2D": (lambda: L.ConvLSTM2D(4, 3), (3, 5, 5, 2), "float"),
    "ConvLSTM3D": (lambda: L.ConvLSTM3D(4, 3), (3, 4, 4, 4, 2), "float"),
    "ConvLSTM3D_seq": (lambda: L.ConvLSTM3D(4, 3, return_sequences=True),
                       (3, 4, 4, 4, 2), "float"),
    "ConvLSTM2D_seq": (lambda: L.ConvLSTM2D(4, 3, return_sequences=True),
                       (3, 5, 5, 2), "float"),
    "LocallyConnected2D": (lambda: L.LocallyConnected2D(4, 3, 3),
                           (6, 6, 2), "float"),
    "ShareConvolution2D": (lambda: L.ShareConvolution2D(4, 3, 3, pad_h=1,
                                                        pad_w=1),
                           (6, 6, 2), "float"),
    "MaxoutDense": (lambda: L.MaxoutDense(5, nb_feature=3), (4,), "float"),
    "LRN2D": (lambda: L.LRN2D(), (4, 4, 7), "float"),
    "WithinChannelLRN": (lambda: L.WithinChannelLRN(3), (6, 6, 3), "float"),
    "KMaxPooling": (lambda: L.KMaxPooling(3), (8, 4), "float"),
    "SeparableConvolution1D": (lambda: L.SeparableConvolution1D(6, 3),
                               (8, 4), "float"),
    "SimpleRNN": (lambda: L.SimpleRNN(5), (6, 4), "float"),
    "LSTM": (lambda: L.LSTM(5, return_sequences=True), (6, 4), "float"),
    "GRU": (lambda: L.GRU(5), (6, 4), "float"),
    "Bidirectional": (lambda: L.Bidirectional(L.LSTM(5, return_sequences=True)),
                      (6, 4), "float"),
    "MultiHeadSelfAttention": (lambda: L.MultiHeadSelfAttention(8, 2),
                               (6, 8), "float"),
    "SparseMoE": (lambda: L.SparseMoE(4, 8, top_k=2), (6,), "float"),
    "GPipe": (lambda: L.GPipe(lambda: L.Dense(6, activation="tanh"),
                              num_stages=2), (6,), "float"),
    "Pipeline": (lambda: L.Pipeline([[L.Dense(5, activation="tanh")],
                                     [L.Dense(3)]]), (6,), "float"),
    "TransformerBlock": (lambda: L.TransformerBlock(8, 2), (6, 8), "float"),
    "TransformerLayer": (lambda: L.TransformerLayer(
        vocab=7, seq_len=6, n_block=2, hidden_size=8, n_head=2), (6,), "int"),
    # the pre-norm decoder's layers; the routed layer keeps counters in
    # its state, which persist with the weights
    "RMSNorm": (lambda: L.RMSNorm(), (4,), "float"),
    "RoutedExperts": (lambda: L.RoutedExperts(4, 8, top_k=2, held=(1, 3)),
                      (6,), "float"),
    "DecoderAttention": (lambda: L.DecoderAttention(
        8, 4, 2, 4, rotary=_ROPE, window=3), (6, 8), "float"),
    "DecoderBlock": (lambda: L.DecoderBlock(
        8, L.DecoderAttention(8, 4, 2, 4, rotary=_ROPE),
        L.RoutedExperts(4, 8, top_k=2)), (6, 8), "float"),
    "DecoderStack": (lambda: L.DecoderStack(
        vocab=7, layer_types=["sliding_attention", "full_attention"],
        hidden_size=8, n_head=4, n_kv_head=2, head_dim=4,
        ffn=lambda i: L.RoutedExperts(4, 8, top_k=2),
        rope_parameters=_ROPE, sliding_window=3), (6,), "int"),
    "GatedFeedForward": (lambda: L.GatedFeedForward(12), (6, 8), "float"),
    "LatentAttention": (lambda: L.LatentAttention(
        8, 2, q_lora_rank=6, kv_lora_rank=4, qk_nope_dim=4, qk_rope_dim=2,
        v_dim=6, rotary=_ROPE), (6, 8), "float"),
    "RoutedExperts_sigmoid_shared": (lambda: L.RoutedExperts(
        4, 8, top_k=2, held=(1, 3), scoring="sigmoid",
        selection_bias=[0.1, -0.2, 0.3, 0.0], routed_scale=1.8,
        shared_dim=8), (6, 8), "float"),
    "DecoderStack_latent": (lambda: L.DecoderStack(
        vocab=7, layer_types=["full_attention"] * 2, hidden_size=8,
        attn=lambda i: L.LatentAttention(
            8, 2, q_lora_rank=6, kv_lora_rank=4, qk_nope_dim=4,
            qk_rope_dim=2, v_dim=6, rotary=_ROPE),
        ffn=lambda i: (L.GatedFeedForward(12) if i == 0
                       else L.RoutedExperts(4, 8, top_k=2, shared_dim=8))),
        (6,), "int"),
    # the LFM2 family's mixer, q/k normalisation, and a stack of both kinds
    # of mixer that ends in the head tied to its own table
    "ShortConvMixer": (lambda: L.ShortConvMixer(8), (6, 8), "float"),
    "DecoderAttention_qk_norm": (lambda: L.DecoderAttention(
        8, 4, 2, 4, rotary=_ROPE, qk_norm=True), (6, 8), "float"),
    "DecoderStack_conv_tied": (lambda: L.DecoderStack(
        vocab=7, layer_types=["conv", "full_attention", "conv"],
        hidden_size=8, n_head=4, n_kv_head=2, head_dim=4,
        rope_parameters=_ROPE, qk_norm=True, tied_head=True,
        ffn=lambda i: L.GatedFeedForward(12)), (6,), "int"),
}


def _roundtrip(factory, shape, kind):
    data_rng = np.random.default_rng(0)
    x = _input_for(kind, shape, data_rng)
    xs = jax.numpy.asarray(x)
    in_shape = (None,) + shape

    l1 = factory()
    p1 = l1.build(jax.random.key(0), in_shape)
    s1 = l1.initial_state(in_shape)
    y1, _ = l1.apply(p1, s1, xs, training=False, rng=None)

    # persist exactly as ZooModel/CheckpointManager do: leaves in tree order
    leaves = [np.asarray(v) for v in jax.tree_util.tree_leaves((p1, s1))]
    with tempfile.NamedTemporaryFile(suffix=".npz") as f:
        np.savez(f.name, **{f"l_{i}": a for i, a in enumerate(leaves)})
        with np.load(f.name) as data:
            loaded = [data[f"l_{i}"] for i in range(len(leaves))]

    l2 = factory()  # fresh instance, DIFFERENT init rng
    p2 = l2.build(jax.random.key(999), in_shape)
    s2 = l2.initial_state(in_shape)
    _, treedef = jax.tree_util.tree_flatten((p2, s2))
    fresh = jax.tree_util.tree_leaves((p2, s2))
    assert len(fresh) == len(loaded), \
        f"leaf count changed across rebuild: {len(fresh)} vs {len(loaded)}"
    for i, (a, b) in enumerate(zip(loaded, fresh)):
        assert np.shape(a) == np.shape(b), \
            f"leaf {i} shape {np.shape(a)} vs rebuilt {np.shape(b)}"
    p2, s2 = jax.tree_util.tree_unflatten(treedef, loaded)
    y2, _ = l2.apply(p2, s2, xs, training=False, rng=None)

    for a, b in zip(jax.tree_util.tree_leaves(y1),
                    jax.tree_util.tree_leaves(y2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(CASES))
def test_layer_roundtrip(name):
    factory, shape, kind = CASES[name]
    _roundtrip(factory, shape, kind)


def test_sweep_covers_every_exported_layer():
    """The reference's SerializerSpec fails when a new layer lacks coverage —
    enforce the same: every public layer class must appear in CASES."""
    import inspect
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    exempt = {
        "Input", "InputLayer", "Lambda",  # graph plumbing, not serializable
        "Merge",                           # covered by test_merge_roundtrip
        "BERT",                            # covered by test_bert_roundtrip
        "GaussianSampler",                 # covered by test_gaussian_sampler
        "Layer",
    }
    covered = {case[0]().__class__.__name__ for case in CASES.values()}
    for name in dir(L):
        obj = getattr(L, name)
        if (inspect.isclass(obj) and issubclass(obj, Layer)
                and name not in exempt):
            assert obj.__name__ in covered, \
                f"layer {name} missing from the serialization sweep"


def test_merge_roundtrip():
    rng = np.random.default_rng(1)
    xs = [jax.numpy.asarray(rng.normal(size=(B, 4)).astype(np.float32))
          for _ in range(2)]
    shapes = [(None, 4), (None, 4)]
    for mode in ("sum", "concat", "mul", "max", "ave"):
        l1 = L.Merge(mode=mode)
        p1 = l1.build(jax.random.key(0), shapes)
        s1 = l1.initial_state(shapes)
        y1, _ = l1.apply(p1, s1, xs, training=False, rng=None)
        l2 = L.Merge(mode=mode)
        p2 = l2.build(jax.random.key(9), shapes)
        s2 = l2.initial_state(shapes)
        y2, _ = l2.apply(p2, s2, xs, training=False, rng=None)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_gaussian_sampler():
    rng = np.random.default_rng(3)
    mean = jax.numpy.asarray(rng.normal(size=(B, 4)).astype(np.float32))
    log_var = jax.numpy.asarray(rng.normal(size=(B, 4)).astype(np.float32))
    l = L.GaussianSampler()
    # deterministic (mean) without rng; reparameterized draw with rng
    out = l.call({}, [mean, log_var])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(mean))
    draw = l.call({}, [mean, log_var], rng=jax.random.key(0))
    assert draw.shape == mean.shape
    assert not np.allclose(np.asarray(draw), np.asarray(mean))


def test_bert_roundtrip():
    t = 6
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 7, (B, t)).astype(np.int32)
    seg = np.zeros((B, t), np.int32)
    pos = np.tile(np.arange(t, dtype=np.int32), (B, 1))
    mask = np.ones((B, t), np.float32)
    x = [jax.numpy.asarray(a) for a in (ids, seg, pos, mask)]
    shapes = [(None, t)] * 4

    def factory():
        return L.BERT(vocab=7, hidden_size=8, n_block=2, n_head=2, seq_len=t,
                      intermediate_size=16)

    l1 = factory()
    p1 = l1.build(jax.random.key(0), shapes)
    y1, _ = l1.apply(p1, {}, x, training=False, rng=None)
    leaves = [np.asarray(v) for v in jax.tree_util.tree_leaves(p1)]
    l2 = factory()
    p2 = l2.build(jax.random.key(7), shapes)
    _, treedef = jax.tree_util.tree_flatten(p2)
    p2 = jax.tree_util.tree_unflatten(treedef, leaves)
    y2, _ = l2.apply(p2, {}, x, training=False, rng=None)
    for a, b in zip(jax.tree_util.tree_leaves(y1),
                    jax.tree_util.tree_leaves(y2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
