"""The system under test for ``Mellum2-12B-A2.5B-Instruct``: the program's
own layers (``DecoderStack`` of ``RoutedExperts`` blocks, a bias-free
``Dense`` head that the fused cross-entropy takes over), trained through
``Sequential.compile(...).fit(...)``. The weights come from the benchmark
(``reference/Mellum2-12B-A2.5B-Instruct.py::init_params``) and are only
re-keyed here into the program's parameter tree."""

import numpy as np


def build(cfg, traffic):
    import jax
    import optax

    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        Dense, DecoderStack, RoutedExperts)
    seq = traffic["seq"]
    if seq > cfg["max_position_embeddings"]:
        raise ValueError(f"traffic seq {seq} > max_position_embeddings")
    if any(kind != "sparse" for kind in cfg["mlp_layer_types"]):
        raise ValueError("only sparse feed-forward layers are built")
    model = Sequential([
        DecoderStack(
            vocab=cfg["vocab_size"], layer_types=cfg["layer_types"],
            hidden_size=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            ffn=lambda i: RoutedExperts(
                cfg["router_width"], cfg["moe_intermediate_size"],
                top_k=cfg["num_experts_per_tok"], held=cfg["held_experts"],
                norm_topk=cfg["norm_topk_prob"],
                token_chunk=cfg["assumed"]["moe_token_chunk"]),
            rope_parameters=cfg["rope_parameters"],
            sliding_window=cfg["sliding_window"],
            epsilon=cfg["rms_norm_eps"],
            initializer_range=cfg["assumed"]["initializer_range"],
            remat=True, input_shape=(seq,)),
        Dense(cfg["vocab_size"], bias=False),
    ])
    o = cfg["assumed"]["optimizer"]
    model.compile(optimizer=optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"],
                                        eps=o["eps"],
                                        weight_decay=o["weight_decay"]),
                  loss="scce_with_logits")
    # the layers' shapes, without a second set of weights: the harness
    # installs its own and resets the layer state, which fit then starts
    # from the layers' initial state (the routed layers' counters)
    jax.eval_shape(lambda key: model.build(key, None), jax.random.key(0))
    return model


def _rekey(tree, old, new):
    return {k: ({new if kk == old else kk: vv for kk, vv in v.items()}
                if k.startswith("block") else v) for k, v in tree.items()}


def to_program(model, tree):
    """Benchmark-made weights, keyed as the program's parameter tree (the
    reference calls a block's routed layer ``moe``, the program ``ffn``)."""
    trunk, head = (layer.name for layer in model.layers)
    tree = _rekey(tree, "moe", "ffn")
    return {head: tree.pop("head"), trunk: tree}


def from_program(model, tree):
    trunk, head = (layer.name for layer in model.layers)
    return {**_rekey(tree[trunk], "ffn", "moe"), "head": tree[head]}


def features(cfg, traffic, rng, rows):
    """``rows`` sequences of token ids of the slice; the label of a position
    is the next token. ``token_ids: zipf`` draws id ``r`` (rank ``r + 1``)
    with probability proportional to ``(r + 1) ** -zipf_s``: real text is
    Zipfian, and it is what makes the experts' loads uneven."""
    shape = (rows, traffic["seq"] + 1)
    if traffic.get("token_ids") == "zipf":
        p = np.arange(1, cfg["vocab_size"] + 1,
                      dtype=np.float64) ** -float(traffic["zipf_s"])
        cdf = np.cumsum(p / p.sum())
        tok = np.minimum(np.searchsorted(cdf, rng.random(shape)),
                         cfg["vocab_size"] - 1).astype(np.int32)
    else:
        tok = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    return tok[:, :-1].copy(), tok[:, 1:].copy()


def tokens_per_row(cfg, traffic):
    return traffic["seq"]


def visible_pairs(seq, window=None):
    """(query, key) pairs a causal layer computes over one sequence:
    ``T (T + 1) / 2`` in a full layer, ``sum_i min(i + 1, window)`` in a
    window layer."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def train_flops_per_row(cfg, traffic):
    """Forward + backward model FLOPs of one sequence, nothing recomputed:
    6 per multiply-add weight a token meets (the attention projections and
    the router of every layer, one held expert's three matrices per
    expected held assignment, ``top_k * held / published`` a token, and the
    head), plus 3 x the two attention products over the visible pairs of
    each layer."""
    t, h, d = traffic["seq"], cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    dense = h * q + 2 * h * kv + q * h + h * cfg["router_width"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    held_per_token = (cfg["num_experts_per_tok"] * len(cfg["held_experts"])
                      / cfg["router_width"])
    weights = (cfg["num_hidden_layers"] * (dense + held_per_token * expert)
               + h * cfg["vocab_size"])
    pairs = sum(visible_pairs(
        t, cfg["sliding_window"] if kind == "sliding_attention" else None)
        for kind in cfg["layer_types"])
    attn = 2 * 2 * pairs * q                  # QK^T and PV, forward
    return 6 * weights * t + 3 * attn
