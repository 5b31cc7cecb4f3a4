"""The system under test for ``GLM-4.7-Flash``: the program's own layers (a
``DecoderStack`` whose blocks hold ``LatentAttention`` and, by the layer's
number, a dense ``GatedFeedForward`` or sigmoid-routed ``RoutedExperts``
with a shared expert; a bias-free ``Dense`` head that the fused
cross-entropy takes over), trained through
``Sequential.compile(...).fit(...)``. The weights come from the benchmark
(``reference/GLM-4.7-Flash.py::init_params``); program and reference key
their trees alike, so only the head moves."""

import numpy as np

from benchmark.lib import reference_run


def build(cfg, traffic):
    import jax
    import optax

    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense, DecoderStack, GatedFeedForward, LatentAttention,
        RoutedExperts)
    seq = traffic["seq"]
    if seq > cfg["max_position_embeddings"]:
        raise ValueError(f"traffic seq {seq} > max_position_embeddings")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited routing is not built")
    assumed = cfg["assumed"]
    ref = reference_run.load("reference", cfg["reference"])
    rotary = {"rope_type": "default", "rope_theta": cfg["rope_theta"]}

    def attn(i):
        return LatentAttention(
            cfg["hidden_size"], cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            rotary=rotary, epsilon=cfg["rms_norm_eps"])

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
            shared_dim=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            token_chunk=assumed.get("moe_token_chunk"))

    model = Sequential([
        DecoderStack(
            vocab=cfg["vocab_size"],
            layer_types=["full_attention"] * cfg["num_hidden_layers"],
            hidden_size=cfg["hidden_size"], attn=attn, ffn=ffn,
            epsilon=cfg["rms_norm_eps"],
            initializer_range=assumed["initializer_range"],
            remat=assumed["remat_blocks"], input_shape=(seq,)),
        Dense(cfg["vocab_size"], bias=False),
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
    trunk, head = (layer.name for layer in model.layers)
    tree = dict(tree)
    return {head: tree.pop("head"), trunk: tree}


def from_program(model, tree):
    trunk, head = (layer.name for layer in model.layers)
    return {**tree[trunk], "head": tree[head]}


#: token ids of the slice (uniform, or Zipf by ``token_ids``) with the next
#: token as label, and a row's tokens: the decoder traffic the Mellum
#: configuration's model file defines, taken from there
_decoder = reference_run.load("models", "Mellum2-12B-A2.5B-Instruct")
features, tokens_per_row = _decoder.features, _decoder.tokens_per_row


def weights_a_token_meets(cfg):
    """Multiply-add weights one token passes through, forward: the five
    attention matrices of every layer, the dense layer's three once, in
    every routed layer the router, the shared expert and one held expert's
    three matrices per expected held assignment (``top_k * held /
    published`` a token: 0.5), and the head."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    attn = (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * n * (nope + rope)
            + h * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * n * (nope + v) + n * v * h)
    expert = 3 * h * cfg["moe_intermediate_size"]
    held_per_token = (cfg["num_experts_per_tok"] * len(cfg["held_experts"])
                      / cfg["router_width"])
    dense_layers = cfg["first_k_dense_replace"]
    routed_layers = cfg["num_hidden_layers"] - dense_layers
    return (cfg["num_hidden_layers"] * attn
            + dense_layers * 3 * h * cfg["intermediate_size"]
            + routed_layers * (h * cfg["router_width"]
                               + cfg["n_shared_experts"] * expert
                               + held_per_token * expert)
            + h * cfg["vocab_size"])


def train_flops_per_row(cfg, traffic):
    """Forward + backward model FLOPs of one sequence, nothing recomputed:
    6 per multiply-add weight a token meets, plus 3 x the two attention
    products (QK^T over heads of nope + rope, PV over heads of v) over the
    ``T (T + 1) / 2`` visible pairs of every layer."""
    t, n = traffic["seq"], cfg["num_attention_heads"]
    pairs = t * (t + 1) // 2
    attn = 2 * pairs * n * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                            + cfg["v_head_dim"])
    return (6 * weights_a_token_meets(cfg) * t
            + 3 * cfg["num_hidden_layers"] * attn)
