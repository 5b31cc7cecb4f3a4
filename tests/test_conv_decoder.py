"""What LFM2-24B-A2B forced into the decoder path, against the benchmark's
plain float32 reference at a size the CPU holds: the gated short-convolution
mixer, q/k normalisation in ``DecoderAttention``, two kinds of mixer in one
``DecoderStack`` (rematerialised or not), a head tied to the token table
through ``fit`` with the fused cross-entropy on and off, the eight chips'
shares of a routed layer, and the configuration file's invariants."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.common.context import reset_zoo_context
from analytics_zoo_tpu.observability import default_registry
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras import fused_loss
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    DecoderAttention, DecoderStack, Dense, GatedFeedForward, LatentAttention,
    RoutedExperts, ShortConvMixer)
from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import (
    TiedHead, decoder_blocks, remat_saved_bytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "LFM2-24B-A2B"
ROTARY = {"rope_type": "default", "rope_theta": 1000000}
C = importlib.import_module("benchmark.reference._blocks_conv")
Dref = importlib.import_module("benchmark.reference._blocks_decoder")


def _normal(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


def _assert_trees_close(got, want, rtol, atol):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", [3, 4, 1])
def test_short_conv_mixer_is_the_references_equations(kernel):
    """Forward and every parameter's gradient (and the input's) against the
    reference's grouped ``conv_general_dilated``; 3 taps as published, and
    4 and 1 so that the taps' order and the padding are seen."""
    init_zoo_context()
    layer = ShortConvMixer(32, kernel=kernel)
    params = layer.build(jax.random.key(kernel), (None, 24, 32))
    assert {k: v.shape for k, v in params.items()} == {
        "Win": (32, 96), "conv": (32, kernel), "Wout": (32, 32)}
    # the start of torch.nn.Conv1d for a depthwise kernel: +-kernel^-1/2
    assert float(jnp.abs(params["conv"]).max()) <= kernel ** -0.5
    assert float(jnp.abs(params["conv"]).max()) > 0.8 * kernel ** -0.5
    assert not hasattr(layer, "tables")
    rng = np.random.default_rng(1)
    x, co = _normal(rng, (2, 24, 32)), _normal(rng, (2, 24, 32))

    def got_fn(p, x):
        return jnp.sum(layer.call(p, x) * co)

    def want_fn(p, x):
        return jnp.sum(C.short_conv(p, x, "f32") * co)
    np.testing.assert_allclose(layer.call(params, x),
                               C.short_conv(params, x, "f32"), rtol=2e-4,
                               atol=2e-5)
    got = jax.grad(got_fn, argnums=(0, 1))(params, x)
    want = jax.grad(want_fn, argnums=(0, 1))(params, x)
    _assert_trees_close(got, want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("t", [0, 5, 22])
def test_short_conv_output_does_not_move_with_later_inputs(t):
    """Output ``t`` is a function of inputs ``t - 2 .. t``: changing every
    input after ``t`` leaves outputs up to ``t`` as they were, and changing
    input ``t - 3`` or earlier does too; input ``t`` itself moves it."""
    init_zoo_context()
    layer = ShortConvMixer(16)
    params = layer.build(jax.random.key(0), (None, 24, 16))
    rng = np.random.default_rng(t)
    x = _normal(rng, (2, 24, 16))
    later = x.at[:, t + 1:].set(_normal(rng, (2, 23 - t, 16)))
    y, y_later = layer.call(params, x), layer.call(params, later)
    np.testing.assert_array_equal(y[:, :t + 1], y_later[:, :t + 1])
    assert float(jnp.abs(y[:, t + 1:] - y_later[:, t + 1:]).max()) > 1e-3
    if t >= 3:
        earlier = x.at[:, :t - 2].set(0.0)
        np.testing.assert_array_equal(layer.call(params, earlier)[:, t],
                                      y[:, t])
    here = layer.call(params, x.at[:, t].add(1.0))
    assert float(jnp.abs(here[:, t] - y[:, t]).max()) > 1e-3


def test_reversed_taps_are_another_layer():
    """The taps' order in time is seen: the same taps reversed give another
    output (what ``benchmark/tests/test_conv.py`` plants as a fault)."""
    init_zoo_context()
    layer = ShortConvMixer(16)
    params = layer.build(jax.random.key(0), (None, 24, 16))
    x = _normal(np.random.default_rng(0), (2, 24, 16))
    flipped = dict(params, conv=params["conv"][:, ::-1])
    assert float(jnp.abs(layer.call(params, x)
                         - layer.call(flipped, x)).max()) > 1e-3


# ---------------------------------------------------------------------------
# q/k normalisation
# ---------------------------------------------------------------------------

GQA = dict(hidden_size=32, n_head=4, n_kv_head=2, head_dim=16)


def _qk_attention(seed=0, eps=1e-5):
    layer = DecoderAttention(rotary=ROTARY, qk_norm=True, epsilon=eps, **GQA)
    params = layer.build(jax.random.key(seed), (None, 40, 32))
    rng = np.random.default_rng(seed)
    # norms away from their start of ones, so that they are seen
    for k in ("q_norm", "k_norm"):
        params[k] = {"gamma": 1.0 + _normal(rng, (16,), 0.2)}
    return layer, params


def _qk_reference(params, x, eps=1e-5):
    tables = Dref.rotary_tables(ROTARY, 16, x.shape[1])
    return C.attention(params, x, tables, n_head=4, n_kv_head=2, head_dim=16,
                       eps=eps, mode="f32")


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_qk_normed_attention_is_the_references_equations(flash):
    """Forward and every parameter's gradient, on the XLA op and on the
    flash kernels (the interpreter here): RMSNorm over each head's 16
    columns of q and of k, one weight vector each, before the rotation."""
    reset_zoo_context()
    init_zoo_context(conf={"zoo.pallas.attention": flash})
    layer, params = _qk_attention()
    assert layer._use_flash(None, 0.0, 40) is flash
    assert params["q_norm"]["gamma"].shape == (16,)
    rng = np.random.default_rng(1)
    x, co = _normal(rng, (2, 40, 32)), _normal(rng, (2, 40, 32))
    got, got_g = jax.value_and_grad(
        lambda p: jnp.sum(layer.call(p, x) * co))(params)
    want, want_g = jax.value_and_grad(
        lambda p: jnp.sum(_qk_reference(p, x) * co))(params)
    out = layer.call(params, x)
    reset_zoo_context()
    np.testing.assert_allclose(out, _qk_reference(params, x), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    _assert_trees_close(got_g, want_g, rtol=2e-3, atol=2e-4)


def test_qk_norm_is_off_by_default_and_the_tree_is_the_four_matrices():
    """Mellum's tree and step do not change: without ``qk_norm`` the layer
    builds ``Wq, Wk, Wv, Wo`` alone and is the un-normed reference; with it
    and norms of ones it is another function."""
    init_zoo_context()
    plain = DecoderAttention(rotary=ROTARY, **GQA)
    params = plain.build(jax.random.key(0), (None, 40, 32))
    assert set(params) == {"Wq", "Wk", "Wv", "Wo"}
    x = _normal(np.random.default_rng(2), (2, 40, 32))
    tables = Dref.rotary_tables(ROTARY, 16, 40)
    want = Dref.attention(params, x, tables, n_head=4, n_kv_head=2,
                          head_dim=16, window=None, mode="f32")
    np.testing.assert_allclose(plain.call(params, x), want, rtol=2e-4,
                               atol=2e-5)
    normed = DecoderAttention(rotary=ROTARY, qk_norm=True, **GQA)
    ones = dict(params, q_norm={"gamma": jnp.ones(16)},
                k_norm={"gamma": jnp.ones(16)})
    assert float(jnp.abs(normed.call(ones, x) - want).max()) > 1e-3


# ---------------------------------------------------------------------------
# two kinds of mixer in one stack
# ---------------------------------------------------------------------------

VOCAB, HIDDEN, T = 50, 32, 40
TYPES = ["conv", "full_attention", "conv"]


def _stack(remat=False, tied=False, **kw):
    return DecoderStack(
        vocab=VOCAB, layer_types=TYPES, hidden_size=HIDDEN, n_head=4,
        n_kv_head=2, head_dim=8, rope_parameters=ROTARY, qk_norm=True,
        ffn=lambda i: GatedFeedForward(48), remat=remat, tied_head=tied,
        input_shape=(T,), **kw)


def _ids(seed=0, rows=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (rows, T)), jnp.int32)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_a_stack_of_conv_and_attention_remat_on_equals_off(flash):
    """``conv, full_attention, conv`` rematerialised against not: the same
    loss and gradients; a conv block's checkpoint keeps its input alone, so
    ``zoo_remat_saved_bytes`` counts ONE layer of three, and only where
    that layer ran on the flash kernels."""
    reset_zoo_context()
    init_zoo_context(conf={"zoo.pallas.attention": flash})
    stacks = {remat: _stack(remat) for remat in (False, True)}
    params = stacks[False].build(jax.random.key(0), (None, T))
    assert set(params["block0"]["attn"]) == {"Win", "conv", "Wout"}
    assert set(params["block1"]["attn"]) == {"Wq", "Wk", "Wv", "Wo",
                                             "q_norm", "k_norm"}
    ids = _ids()

    def loss(stack):
        def fn(p):
            h, _ = stack.apply(p, {}, ids, training=True)
            return jnp.mean(jnp.square(h.astype(jnp.float32)))
        return fn
    remat_saved_bytes({})
    want, want_g = jax.value_and_grad(loss(stacks[False]))(params)
    assert remat_saved_bytes() == {"flash_out": 0, "flash_lse": 0}
    got, got_g = jax.value_and_grad(loss(stacks[True]))(params)
    kept = remat_saved_bytes()
    reset_zoo_context()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    _assert_trees_close(got_g, want_g, rtol=1e-4, atol=1e-6)
    if flash:       # B x heads x T x head_dim floats, B x heads x T floats
        assert kept == {"flash_out": 2 * 4 * T * 8 * 4,
                        "flash_lse": 2 * 4 * T * 4}
    else:
        assert kept == {"flash_out": 0, "flash_lse": 0}


class _Mean(Layer):
    """A mixer without ``tables``: the running mean over earlier tokens."""

    def call(self, params, x, *, training=False, rng=None):
        n = jnp.arange(1, x.shape[1] + 1, dtype=x.dtype)[None, :, None]
        return jnp.cumsum(x, axis=1) / n


def test_decoder_stack_takes_mixers_with_and_without_tables():
    """``attn(i)`` may hand out any mixer: one without ``tables`` is called
    on the hidden states alone, beside one that takes ``[x, (cos, sin)]``;
    a stack of ``conv`` layers alone needs no head counts; the census says
    what was built."""
    init_zoo_context()
    made = []

    def attn(i):
        made.append(_Mean() if i != 1 else DecoderAttention(
            rotary=ROTARY, qk_norm=True, **GQA))
        return made[-1]
    stack = DecoderStack(vocab=VOCAB, layer_types=TYPES, hidden_size=HIDDEN,
                         attn=attn, ffn=lambda i: GatedFeedForward(48),
                         input_shape=(T,))
    assert [b.attn for b in stack.blocks] == made
    params = stack.build(jax.random.key(0), (None, T))
    assert params["block0"]["attn"] == {}
    y = stack.call(params, _ids())
    assert y.shape == (2, T, HIDDEN) and bool(jnp.isfinite(y).all())
    # a layer without mixer_kind is counted under its layer type
    assert stack.mixers == {"conv": 2, "full_attention": 1}

    conv_only = DecoderStack(vocab=VOCAB, layer_types=["conv"] * 2,
                             hidden_size=HIDDEN, conv_kernel=4,
                             ffn=lambda i: GatedFeedForward(48))
    assert [b.attn.kernel for b in conv_only.blocks] == [4, 4]
    assert decoder_blocks() == {"conv": 2, "full_attention": 0,
                                "sliding_attention": 0, "latent": 0}
    with pytest.raises(ValueError, match="n_head"):
        DecoderStack(vocab=VOCAB, layer_types=TYPES, hidden_size=HIDDEN,
                     ffn=lambda i: GatedFeedForward(48))
    with pytest.raises(ValueError, match="layer_types"):
        DecoderStack(vocab=VOCAB, layer_types=["mamba"], hidden_size=HIDDEN,
                     ffn=lambda i: GatedFeedForward(48))


@pytest.mark.parametrize("kind,census", [
    ("lfm2", {"conv": 2, "full_attention": 1}),
    ("window", {"sliding_attention": 1, "full_attention": 1}),
    ("latent", {"latent": 2})])
def test_the_census_of_mixers_is_set_where_the_stack_is_built(kind, census):
    init_zoo_context()
    if kind == "lfm2":
        stack = _stack()
    elif kind == "window":
        stack = DecoderStack(
            vocab=VOCAB, hidden_size=HIDDEN, n_head=4, n_kv_head=2,
            head_dim=8, layer_types=["sliding_attention", "full_attention"],
            sliding_window=16, rope_parameters=ROTARY,
            ffn=lambda i: GatedFeedForward(48))
    else:
        stack = DecoderStack(
            vocab=VOCAB, hidden_size=HIDDEN, layer_types=["full_attention"] * 2,
            attn=lambda i: LatentAttention(
                HIDDEN, 4, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12,
                qk_rope_dim=4, v_dim=16, rotary=ROTARY),
            ffn=lambda i: GatedFeedForward(48))
    assert stack.mixers == census
    gauges = {dict(m.labels)["mixer"]: m.value
              for m in default_registry().metrics()
              if m.name == "zoo_decoder_blocks"}
    assert gauges == {"conv": 0, "full_attention": 0, "sliding_attention": 0,
                      "latent": 0, **census}


# ---------------------------------------------------------------------------
# the head tied to the token table
# ---------------------------------------------------------------------------

def test_a_tied_stack_ends_in_logits_over_its_own_table():
    """``logits = RMSNorm(h) E^T``: the tied stack's output is the untied
    stack's hidden states times the transposed table; there is no head
    leaf; ``find_head`` hands the fused loss the table's path."""
    init_zoo_context()
    tied, plain = _stack(tied=True), _stack()
    params = tied.build(jax.random.key(0), (None, T))
    assert set(params) == set(plain.build(jax.random.key(0), (None, T)))
    ids = _ids()
    np.testing.assert_allclose(
        tied.call(params, ids), plain.call(params, ids) @ params["wte"].T,
        rtol=1e-5, atol=1e-6)
    assert isinstance(tied.head, TiedHead) and tied.head.output_dim == VOCAB
    assert plain.head is None and plain.fused_head() is None
    model = Sequential([tied])
    head, path = fused_loss.find_head(model)
    assert head is tied.head and path == (tied.name, "wte")
    spec = fused_loss.FusedHeadSpec(head, path)
    assert spec.tied and not spec.sharded
    w = spec.head_params({tied.name: params})["W"]
    np.testing.assert_array_equal(w, params["wte"].T)
    # an untied model is found as before, and a stack with a Dense behind
    # it is not tied
    dense = Sequential([_stack(), Dense(VOCAB, bias=False)])
    head, path = fused_loss.find_head(dense)
    assert isinstance(head, Dense) and path == (head.name,)
    assert not fused_loss.FusedHeadSpec(head, path).tied
    assert fused_loss.find_head(Sequential([_stack()])) is None


def test_a_tied_head_under_a_model_axis_says_its_loss_is_unsharded(caplog):
    """A `model` mesh axis that would shard a ``Dense`` head of this width
    does not shard the table: the spec is the unsharded one, and the log
    says so where the loss is resolved."""
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    init_zoo_context(mesh_model=2, conf={"zoo.train.fused_ce": "true"})
    try:
        model = Sequential([_stack(tied=True)])
        with caplog.at_level("WARNING", logger="analytics_zoo_tpu.training"):
            spec = fused_loss.resolve_fused_loss(
                model, objectives.sparse_categorical_crossentropy_from_logits)
        assert spec.tied and not spec.sharded
        assert fused_loss._head_sharded(spec.head)
        said = [r.getMessage() for r in caplog.records]
        assert len(said) == 1 and "UNSHARDED" in said[0], said
    finally:
        init_zoo_context()


def _bench(kind):
    from benchmark.lib import reference_run
    return reference_run.load(kind, NAME)


TRAFFIC = {"kind": "train", "seq": 32, "batch": 8, "chips": 1,
           "epoch_steps": 8, "reference_rows_per_chip": 8,
           "token_ids": "zipf", "zipf_s": 1.0}


def _fused_gauges():
    return {tuple(sorted(dict(m.labels).items())): m.value
            for m in default_registry().metrics()
            if m.name == "zoo_train_fused_ce"}


@pytest.mark.parametrize("fused", [True, False], ids=["fused_ce", "logits"])
def test_lfm2_stack_trains_through_fit_like_the_reference(fused):
    """Three optimizer steps of a tiny LFM2 stack (conv with the dense
    layer, q/k-normed attention and conv with routed layers, the head tied)
    through ``Sequential.compile(...).fit(...)`` (float32 compute here),
    with the fused cross-entropy and on the full-logits oracle, against the
    reference: each loss, every leaf of the first gradient, every leaf's
    change. **The table's gradient is the embedding's part plus the
    head's**: the reference's own two parts, each taken with the other
    use of the table held constant, add up to the leaf the optimizer got."""
    from benchmark.kinds import train
    from benchmark.lib import compare, reference_run
    from benchmark.tests import tiny_lfm2
    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": fused})
    cfg, _ = tiny_lfm2.lfm2()
    model_lib, ref = _bench("models"), _bench("reference")
    model = model_lib.build(cfg, TRAFFIC)
    assert len(model.layers) == 1 and model.layers[0].head is not None
    rng = np.random.default_rng(7)
    batches = [model_lib.features(cfg, TRAFFIC, rng, TRAFFIC["batch"])
               for _ in range(3)]
    got = train.first_steps(model, model_lib, ref, cfg, 7, batches,
                            TRAFFIC["batch"])
    gauges = _fused_gauges()
    report = model.last_fit_report
    evaluated = model.evaluate(*batches[0], batch_size=TRAFFIC["batch"])
    logits = model.predict(batches[0][0], batch_size=TRAFFIC["batch"])
    reset_zoo_context()
    want = reference_run.three_steps(ref, cfg, 7, batches, 8)
    numbers = {k: v[0] for k, v in compare.numbers(got, want).items()}
    assert set(got["grad"]) == set(want["grad"])
    # wte, norm; per block 2 norms; conv 3, attention 6; dense 3, routed 4
    assert len(got["grad"]) == 2 + 3 * 2 + 2 * 3 + 6 + 3 + 2 * 4 == 31
    assert not any("head" in k or "bias" in k for k in got["grad"])
    for i in (1, 2, 3):
        assert numbers[f"loss_step{i}"] < 1e-5, numbers
    assert numbers["grad_error_worst_leaf"] < 2e-3, numbers
    assert numbers["grad_norm_worst_leaf"] < 1e-3, numbers
    assert numbers["change_norm_worst_leaf"] < 5e-2, numbers
    # ONE table, one pair of moments: the leaf's gradient is both parts
    params = jax.jit(lambda k: ref.init_params(cfg, k))(ref.B.seed_key(7))
    x, y = (jnp.asarray(a) for a in batches[0])

    def part(which):
        def fn(table):
            held = jax.lax.stop_gradient(params["wte"])
            embed = table if which == "embedding" else held
            head = table if which == "head" else held
            p = dict(params, wte=embed)
            hid = jnp.take(embed, x, axis=0)
            tables = Dref.rotary_tables(cfg["rope_parameters"], 16,
                                        x.shape[1])
            for i in range(cfg["num_hidden_layers"]):
                hid = ref._block(cfg, i, "f32")(p[f"block{i}"], hid, tables)
            hid = Dref.rms_norm(p["norm"], hid, cfg["norm_eps"])
            logp = jax.nn.log_softmax(  # zoolint: disable=ZL012 the plain form, tiny
                hid @ head.T, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))
        return jax.grad(fn)(params["wte"])
    parts = {which: part(which) for which in ("embedding", "head")}
    for g in parts.values():
        assert float(jnp.linalg.norm(g)) > 0.1 * float(
            jnp.linalg.norm(want["grad_tree"]["wte"]))
    np.testing.assert_allclose(parts["embedding"] + parts["head"],
                               want["grad_tree"]["wte"], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(got["grad_tree"]["wte"],
                               want["grad_tree"]["wte"], rtol=2e-3, atol=1e-6)
    # the reports: the census, the routed layers, the fused loss's labels
    assert report["mixers"] == {"conv": 2, "full_attention": 1}
    assert len(report["moe"]["layers"]) == 2 and report["moe"]["dropped"] == 0
    live = [dict(k) for k, v in gauges.items() if v == 1]
    if fused:
        assert len(live) == 1 and live[0]["tied"] == "1"
        assert live[0]["vocab"] == str(cfg["vocab_size"])
        assert live[0]["sharded"] == "0"
    else:
        assert live == []
    # evaluate and predict run the full-logits oracle over the same table
    assert logits.shape == (TRAFFIC["batch"], TRAFFIC["seq"],
                            cfg["vocab_size"])
    assert abs(evaluated["loss"] - got["loss"][-1]) < 0.2


# ---------------------------------------------------------------------------
# the chip's share of a routed layer
# ---------------------------------------------------------------------------

E, D, H, K = 16, 16, 12, 4


def test_the_eight_chips_parts_of_a_routed_layer_add_up_to_the_uncut_layer():
    """**The shares add up**: the parts that eight ``held`` shares of two
    experts each give (sigmoid scores, a non-zero ``expert_bias``,
    ``norm_topk_prob``, scale 1, top-4 of 16, no shared expert) equal the
    uncut reference layer, which divides by ``sum + 1e-6`` as published
    where the program divides by ``max(sum, 1e-9)``; so do the input
    gradients. One share alone is not the layer."""
    init_zoo_context()
    rng = np.random.default_rng(0)
    params = {"Wg": _normal(rng, (D, E)),
              "Wgate": _normal(rng, (E, D, H), 0.3),
              "Wup": _normal(rng, (E, D, H), 0.3),
              "Wdown": _normal(rng, (E, H, D), 0.3)}
    bias = _normal(np.random.default_rng(5), (E,), 0.5)
    x, co = _normal(rng, (3, 10, D)), _normal(rng, (3, 10, D))
    shares = [(2 * i, 2 * i + 1) for i in range(8)]
    layers = [RoutedExperts(E, H, top_k=K, held=q, scoring="sigmoid",
                            selection_bias=bias, norm_topk=True,
                            routed_scale=1.0) for q in shares]

    def share(q):
        idx = jnp.asarray(q)
        return {"Wg": params["Wg"],
                **{k: params[k][idx] for k in ("Wgate", "Wup", "Wdown")}}

    def parts(x):
        return [layer.call(share(q), x) for layer, q in zip(layers, shares)]

    def reference(x):
        return C.routed(params, x.reshape(-1, D), bias, held=list(range(E)),
                        top_k=K, norm_topk=True, scale=1.0,
                        mode="f32").reshape(x.shape)
    want = reference(x)
    np.testing.assert_allclose(sum(parts(x)), want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(parts(x)[0] - want).max()) > 1e-2
    got_dx = jax.grad(lambda x: jnp.sum(sum(parts(x)) * co))(x)
    want_dx = jax.grad(lambda x: jnp.sum(reference(x) * co))(x)
    np.testing.assert_allclose(got_dx, want_dx, rtol=1e-3, atol=1e-5)
    assert "shared" not in layers[0].build(jax.random.key(0), (None, 10, D))


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(path) as f:
        return next(row for row in map(json.loads, f) if row["name"] == NAME)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size", "max_position_embeddings"]


def test_configuration_is_the_published_one_cut_where_it_says():
    cfg, row = _config(), _catalog_row()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        REDUCED)
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value and cfg["published_" + key] == value, key
        else:
            assert cfg[key] == value, key
    # no width is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["assumed"]["head_dim"],
            cfg["conv_L_cache"], cfg["router_width"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2048, 11776, 1536, 32, 8, 64, 3, 64, 4, 1)
    assert cfg["held_experts"] == list(range(cfg["num_experts"]))
    # one whole period in its 1 : 3 ratio behind the one dense layer kept:
    # published layer 0 and layers 2-5
    published = row["config"]["layer_types"]
    assert cfg["layer_types"] == [published[0]] + published[2:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["assumed"]["tie_embedding"] is True
    cell = next(c for c in manifest["workloads"]
                if c["name"] == "lfm2_train_s8192")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train_s8192_b4", 1)


def test_the_cut_is_469_million_parameters():
    """``tools/size.py``'s count, leaf by leaf, as ISSUE 36 reckoned it by
    hand: the tied table once, no head, no leaf for ``expert_bias``."""
    size = importlib.import_module("benchmark.tools.size")
    n = size.count(_config())
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1536
    routed = 2048 * 64 + 8 * expert + 2 * 2048
    dense = 3 * 2048 * 11776 + 2 * 2048
    assert (conv, attn, expert) == (16_783_360, 10_485_888, 9_437_184)
    assert conv + dense == 89_139_200 and attn + routed == 86_118_528
    assert conv + routed == 92_416_000
    assert n == (8192 * 2048 + 2048 + (conv + dense) + (attn + routed)
                 + 3 * (conv + routed)) == 469_284_992
