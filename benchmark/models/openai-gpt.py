"""The system under test for ``openai-gpt``: the program's own layers, built
as ``chip_smoke``'s kernel phase builds its LM. The weights come from the
benchmark (``reference/openai-gpt.py::init_params``) and are only re-keyed
here into the program's parameter tree."""

import numpy as np


def build(cfg, traffic):
    import optax

    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import (Dense,
                                                             TransformerLayer)
    seq = traffic["seq"]
    if seq > cfg["n_positions"]:
        raise ValueError(f"traffic seq {seq} > n_positions")
    model = Sequential([
        TransformerLayer(vocab=cfg["vocab_size"], seq_len=cfg["n_positions"],
                         n_block=cfg["n_layer"], hidden_size=cfg["n_embd"],
                         n_head=cfg["n_head"], hidden_drop=cfg["resid_pdrop"],
                         attn_drop=cfg["attn_pdrop"],
                         embedding_drop=cfg["embd_pdrop"],
                         bidirectional=False,
                         initializer_range=cfg["initializer_range"],
                         input_shape=(seq,)),
        Dense(cfg["vocab_size"]),
    ])
    o = cfg["assumed"]["optimizer"]
    model.compile(optimizer=optax.adam(o["lr"], b1=o["b1"], b2=o["b2"],
                                       eps=o["eps"]),
                  loss="scce_with_logits")
    return model


def to_program(model, tree):
    """Benchmark-made weights, keyed as the program's parameter tree."""
    trunk, head = (layer.name for layer in model.layers)
    tree = dict(tree)
    return {head: tree.pop("head"), trunk: tree}


def from_program(model, tree):
    trunk, head = (layer.name for layer in model.layers)
    return {**tree[trunk], "head": tree[head]}


def features(cfg, traffic, rng, rows):
    """``rows`` sequences of uniform token ids; the label of a position is
    the next token."""
    tok = rng.integers(0, cfg["vocab_size"], (rows, traffic["seq"] + 1),
                       dtype=np.int32)
    return tok[:, :-1].copy(), tok[:, 1:].copy()


def tokens_per_row(cfg, traffic):
    return traffic["seq"]


def train_flops_per_row(cfg, traffic):
    """Forward + backward model FLOPs of one sequence, nothing recomputed:
    6 per multiply-add weight of the blocks and the head, plus causal
    attention's two products (QK^T and PV) over half of the T x T square."""
    t, h, n = traffic["seq"], cfg["n_embd"], cfg["n_layer"]
    weights = n * 12 * h * h + h * cfg["vocab_size"]
    attn = n * 2 * 2 * t * t * h / 2          # forward, causal half
    return 6 * weights * t + 3 * attn
