"""The system under test for ``LFM2-24B-A2B``: the program's own layers (a
``DecoderStack`` whose blocks hold, by ``layer_types``, a ``ShortConvMixer``
or a ``DecoderAttention`` with q/k normalisation and, by the layer's number,
a dense ``GatedFeedForward`` or sigmoid-routed ``RoutedExperts``; the head
tied to the token table, which the fused cross-entropy takes over), trained
through ``Sequential.compile(...).fit(...)``. The weights come from the
benchmark (``reference/LFM2-24B-A2B.py::init_params``); program and
reference key their trees alike and there is no head to move."""

import numpy as np

from benchmark.lib import reference_run


def build(cfg, traffic):
    import jax
    import optax

    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        DecoderStack, GatedFeedForward, RoutedExperts)
    seq = traffic["seq"]
    if seq > cfg["max_position_embeddings"]:
        raise ValueError(f"traffic seq {seq} > max_position_embeddings")
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("a convolution with a bias, or routing without "
                         "the expert bias, is not built")
    assumed = cfg["assumed"]
    ref = reference_run.load("reference", cfg["reference"])

    def ffn(i):
        if ref.is_dense(cfg, i):
            return GatedFeedForward(cfg["intermediate_size"])
        return RoutedExperts(
            cfg["router_width"], cfg["moe_intermediate_size"],
            top_k=cfg["num_experts_per_tok"], held=cfg["held_experts"],
            norm_topk=cfg["norm_topk_prob"], scoring="sigmoid",
            # the layer's state starts from it; zeros in the cell
            selection_bias=np.asarray(ref.selection_bias(cfg, i)),
            routed_scale=cfg["routed_scaling_factor"],
            token_chunk=assumed.get("moe_token_chunk"))

    model = Sequential([
        DecoderStack(
            vocab=cfg["vocab_size"], layer_types=cfg["layer_types"],
            hidden_size=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"],
            head_dim=assumed["head_dim"], ffn=ffn,
            rope_parameters=cfg["rope_parameters"], qk_norm=True,
            conv_kernel=cfg["conv_L_cache"], tied_head=True,
            epsilon=cfg["norm_eps"],
            initializer_range=assumed["initializer_range"],
            remat=assumed["remat_blocks"], input_shape=(seq,)),
    ])
    o = assumed["optimizer"]
    model.compile(optimizer=optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"],
                                        eps=o["eps"],
                                        weight_decay=o["weight_decay"]),
                  loss="scce_with_logits")
    # the layers' shapes, without a second set of weights: the harness
    # installs its own and resets the layer state, which fit then starts
    # from the layers' initial state (the routed layers' counters and bias)
    jax.eval_shape(lambda key: model.build(key, None), jax.random.key(0))
    return model


def to_program(model, tree):
    """Benchmark-made weights, keyed as the program's parameter tree."""
    return {model.layers[0].name: tree}


def from_program(model, tree):
    return tree[model.layers[0].name]


#: token ids of the slice (uniform, or Zipf by ``token_ids``) with the next
#: token as label, and a row's tokens: the decoder traffic the Mellum
#: configuration's model file defines, taken from there
_decoder = reference_run.load("models", "Mellum2-12B-A2.5B-Instruct")
features, tokens_per_row = _decoder.features, _decoder.tokens_per_row


def weights_a_token_meets(cfg):
    """Multiply-add weights one token passes through, forward: a conv
    mixer's two matrices and its taps (one multiply-add a tap and channel),
    an attention mixer's four matrices, the dense layers' three, in every
    routed layer the router and one held expert's three matrices per
    expected held assignment (``top_k * held / published`` a token: 0.5),
    and the head, which is the token table met a second time."""
    h, d = cfg["hidden_size"], cfg["assumed"]["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    mixer = {"conv": 4 * h * h + h * cfg["conv_L_cache"],
             "full_attention": h * q + 2 * h * kv + q * h}
    expert = 3 * h * cfg["moe_intermediate_size"]
    held_per_token = (cfg["num_experts_per_tok"] * len(cfg["held_experts"])
                      / cfg["router_width"])
    dense_layers = cfg["num_dense_layers"]
    routed_layers = cfg["num_hidden_layers"] - dense_layers
    return (sum(mixer[kind] for kind in cfg["layer_types"])
            + dense_layers * 3 * h * cfg["intermediate_size"]
            + routed_layers * (h * cfg["router_width"]
                               + held_per_token * expert)
            + h * cfg["vocab_size"])


def train_flops_per_row(cfg, traffic):
    """Forward + backward model FLOPs of one sequence, nothing recomputed:
    6 per multiply-add weight a token meets, plus 3 x the two attention
    products (QK^T and PV over the query heads) over the ``T (T + 1) / 2``
    visible pairs of every attention layer. The gates' two multiplications
    a channel are not counted."""
    t = traffic["seq"]
    q = cfg["num_attention_heads"] * cfg["assumed"]["head_dim"]
    attn = 2 * 2 * _decoder.visible_pairs(t) * q
    return (6 * weights_a_token_meets(cfg) * t
            + 3 * cfg["layer_types"].count("full_attention") * attn)
