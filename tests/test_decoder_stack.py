"""The pre-norm decoder (RMSNorm, rotary / YaRN positions, grouped key/value
heads, a causal window by layer type, routed experts) against the
benchmark's plain float32 reference, at a size the CPU holds: the stack
trained through ``fit`` (loss and every gradient leaf), the rotary tables
against the closed form, and the attention layer's two routes against each
other."""

import copy
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.common.context import reset_zoo_context
from analytics_zoo_tpu.ops import attention as attn_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "Mellum2-12B-A2.5B-Instruct"


def _bench(kind):
    from benchmark.lib import reference_run
    return reference_run.load(kind, NAME)


def tiny_cfg(held=(0, 1, 2, 3)):
    """The configuration's file with two periods of layers and every size
    cut to what a CPU holds: hidden 64, 4 query / 2 key-value heads of 16,
    8 experts top-2 of which ``held`` are computed, window 8."""
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=8, layer_types=cfg["layer_types"] * 2,
               mlp_layer_types=cfg["mlp_layer_types"] * 2, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               router_width=8, num_experts=len(held),
               held_experts=list(held), num_experts_per_tok=2,
               moe_intermediate_size=24, sliding_window=8, vocab_size=128,
               max_position_embeddings=32)
    cfg["rope_parameters"] = copy.deepcopy(cfg["rope_parameters"])
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    cfg["assumed"] = dict(cfg["assumed"], router_init_classes=len(held),
                          moe_token_chunk=64)
    return cfg


TRAFFIC = {"kind": "train", "seq": 32, "batch": 8, "chips": 1,
           "epoch_steps": 8, "reference_rows_per_chip": 8,
           "token_ids": "zipf", "zipf_s": 1.0}


def test_decoder_stack_trains_through_fit_like_the_reference():
    """Three optimizer steps through ``Sequential.compile(...).fit(...)``
    (float32 compute here) against the reference: each loss, every leaf of
    the first gradient, every leaf's change."""
    from benchmark.kinds import train
    from benchmark.lib import compare, reference_run
    init_zoo_context()
    cfg = tiny_cfg()
    model_lib, ref = _bench("models"), _bench("reference")
    model = model_lib.build(cfg, TRAFFIC)
    rng = np.random.default_rng(7)
    batches = [model_lib.features(cfg, TRAFFIC, rng, TRAFFIC["batch"])
               for _ in range(3)]
    assert all(x.max() < cfg["vocab_size"] for x, _ in batches)
    got = train.first_steps(model, model_lib, ref, cfg, 7, batches,
                            TRAFFIC["batch"])
    want = reference_run.three_steps(ref, cfg, 7, batches, 8)
    numbers = {k: v[0] for k, v in compare.numbers(got, want).items()}
    assert set(got["grad"]) == set(want["grad"])
    assert len(got["grad"]) == 3 + 8 * 10
    for i in (1, 2, 3):
        assert numbers[f"loss_step{i}"] < 1e-5, numbers
    assert numbers["grad_error_worst_leaf"] < 2e-3, numbers
    assert numbers["grad_norm_worst_leaf"] < 1e-3, numbers
    assert numbers["change_norm_worst_leaf"] < 5e-2, numbers
    # the routed layers' counters came through the state channel
    report = model.last_fit_report["moe"]
    assert len(report["layers"]) == 8
    n = TRAFFIC["batch"] * TRAFFIC["seq"] * cfg["num_experts_per_tok"]
    for layer in report["layers"].values():
        assert layer["held"] + layer["absent"] == n
        assert layer["dropped"] == 0
        assert sum(layer["expert_tokens"]) == n
        # 256 tokens a step in chunks of 64; 128 assignments a chunk are
        # one tile, so the buffers are never cut
        assert layer["chunk_runs"] == 4 and layer["compact_runs"] == 0
        assert layer["rows_run"] == n
        assert 0 <= layer["choice_passes"] <= 4 * 2
    assert report["dropped"] == 0
    assert report["rows_run_over_held"] == report["rows_run"] / report["held"]
    assert report["compact_share"] == 0.0


def test_fp8_control_of_the_tiny_decoder_is_further_than_the_program():
    """The reference's own control (fp8 products) moves the gradient far
    more than the program's float32 path does: the comparison can tell a
    lower precision from a sound run."""
    from benchmark.lib import compare, reference_run
    cfg = tiny_cfg()
    model_lib, ref = _bench("models"), _bench("reference")
    rng = np.random.default_rng(3)
    batches = [model_lib.features(cfg, TRAFFIC, rng, 8) for _ in range(3)]
    want = reference_run.three_steps(ref, cfg, 3, batches, 8)
    control = reference_run.three_steps(ref, cfg, 3, batches, 8, mode="fp8")
    numbers = {k: v[0] for k, v in compare.numbers(control, want).items()}
    assert numbers["grad_error_median_leaf"] > 2e-2, numbers


SPEC = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


def test_yarn_table_against_the_closed_form():
    """``inv_freq`` at i = 0, low, high and 63 of the published YaRN
    setting: the published frequency up to ``low``, a sixteenth of it from
    ``high`` on, and the correction dimensions themselves."""
    low, high = attn_ops.yarn_correction_range(128, SPEC)
    def dim(beta):
        return 128 * math.log(8192 / (2 * math.pi * beta)) / (
            2 * math.log(500000))
    assert (low, high) == (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    inv_freq, scale = attn_ops.rotary_inv_freq(128, SPEC)
    assert scale == SPEC["attention_factor"]
    plain = [500000 ** (-2 * i / 128) for i in range(64)]
    np.testing.assert_allclose(inv_freq[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[low], plain[low], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[high], plain[high] / 16, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[63], plain[63] / 16, rtol=1e-6)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    np.testing.assert_allclose(
        inv_freq[mid], plain[mid] / 16 * ramp + plain[mid] * (1 - ramp),
        rtol=1e-6)
    # the default kind is the plain table, unscaled
    plain_freq, one = attn_ops.rotary_inv_freq(
        128, {"rope_type": "default", "rope_theta": 500000})
    np.testing.assert_allclose(plain_freq, plain, rtol=1e-6)
    assert one == 1.0
    # and the program's tables are the reference's
    D = importlib.import_module("benchmark.reference._blocks_decoder")
    for got, want in zip(attn_ops.rotary_tables(inv_freq, scale, 40),
                         D.rotary_tables(SPEC, 128, 40)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("heads,width,rope,start", [
    (4, 16, 16, 0),         # a decoder's heads: every lane rotates
    (1, 16, 16, 0),
    (3, 24, 8, 16),         # a latent head: [nope 16 | rope 8]
    (2, 32, 8, 8),          # rotary lanes in the middle of a head
], ids=["whole_heads", "one_head", "latent_tail", "middle"])
def test_rotary_in_place_is_rotary_on_the_heads_view(heads, width, rope,
                                                     start):
    """``apply_rotary_in_place`` on (B, T, heads * width) with the spread
    tables gives ``apply_rotary``'s values on the (B, T, heads, width)
    view's rotary lanes and leaves the others, bit for bit, and its
    backward rule is the transpose autodiff finds for ``apply_rotary``."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 10, heads * width)), jnp.float32)
    co = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    inv_freq, scale = attn_ops.rotary_inv_freq(rope, {"rope_theta": 100.0})
    cos, sin = attn_ops.rotary_tables(inv_freq, scale, 10)

    def on_view(x):
        v = x.reshape(2, 10, heads, width)
        turned = attn_ops.apply_rotary(v[..., start:start + rope], cos, sin,
                                       heads_first=False)
        return jnp.concatenate([v[..., :start], turned,
                                v[..., start + rope:]], -1).reshape(x.shape)

    def in_place(x):
        spread = attn_ops.rotary_tables_in_place(cos, sin, heads, width,
                                                 start)
        assert spread[0].shape == (10, heads * width)
        return attn_ops.apply_rotary_in_place(x, *spread, width, rope, start)

    np.testing.assert_array_equal(in_place(x), on_view(x))
    got = jax.grad(lambda x: jnp.sum(in_place(x) * co))(x)
    want = jax.grad(lambda x: jnp.sum(on_view(x) * co))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window", [None, 8])
def test_decoder_attention_routes_agree(window):
    """Flash forced on (the interpreter) and the XLA op give the same
    layer output and parameter gradients, window and full."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import DecoderAttention
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 40, 32)), jnp.float32)
    layer = DecoderAttention(32, 4, 2, 16, rotary=SPEC, window=window)
    params = layer.build(jax.random.key(0), (None, 40, 32))

    def loss(p):
        return jnp.sum(layer.call(p, x) ** 2)
    out = {}
    for flash in (False, True):
        reset_zoo_context()
        init_zoo_context(conf={"zoo.pallas.attention": flash})
        assert layer._use_flash(None, 0.0, 40) is flash
        out[flash] = (layer.call(params, x), jax.grad(loss)(params))
    reset_zoo_context()
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=2e-4,
                               atol=2e-5)
    for a, b in zip(jax.tree.leaves(out[True][1]),
                    jax.tree.leaves(out[False][1])):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
