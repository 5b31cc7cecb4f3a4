"""The system under test for ``bert-base-uncased``: ``tfpark.BERTClassifier``
under AdamW. The weights come from the benchmark
(``reference/bert-base-uncased.py::init_params``) and are only re-keyed."""

import numpy as np

NUM_CLASSES = 2


def build(cfg, traffic):
    import optax

    from analytics_zoo_tpu.tfpark import BERTClassifier
    if traffic["seq"] != cfg["max_position_embeddings"]:
        raise ValueError("BERTClassifier takes whole rows of "
                         "max_position_embeddings tokens")
    model = BERTClassifier(
        num_classes=NUM_CLASSES, vocab=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"], n_block=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], seq_len=traffic["seq"],
        intermediate_size=cfg["intermediate_size"],
        hidden_drop=cfg["hidden_dropout_prob"],
        attn_drop=cfg["attention_probs_dropout_prob"])
    o = cfg["assumed"]["optimizer"]
    model.compile(optimizer=optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"],
                                        eps=o["eps"],
                                        weight_decay=o["weight_decay"]),
                  loss="scce")
    return model


def to_program(model, tree):
    tree = dict(tree)
    return {"cls": tree.pop("cls"), "bert": tree}


def from_program(model, tree):
    return {**tree["bert"], "cls": tree["cls"]}


def features(cfg, traffic, rng, rows):
    """``rows`` full-length sequences of uniform token ids, one segment, no
    padding. Classes: a quarter of every step's rows is class 1, in an order
    from the seed. Rows of random tokens look alike to a fresh model, so a
    step's gradient is close to (rows of class 0 - rows of class 1) times
    one common direction; with classes drawn freely some seeds' steps were
    near balance, their gradient a small remainder, and the program's
    rounding read 5x larger against it than on other seeds (PERF.md
    section 6). A fixed 3:1 split (as MRPC's or CoLA's) makes seeds alike."""
    t, batch = traffic["seq"], traffic["batch"]
    ids = rng.integers(0, cfg["vocab_size"], (rows, t), dtype=np.int32)
    block = np.arange(batch) < batch // 4
    y = np.concatenate([rng.permutation(block) for _ in range(
        -(-rows // batch))])[:rows].astype(np.int32)
    x = [ids, np.zeros((rows, t), np.int32),
         np.tile(np.arange(t, dtype=np.int32), (rows, 1)),
         np.ones((rows, t), np.float32)]
    return x, y


def tokens_per_row(cfg, traffic):
    return traffic["seq"]


def train_flops_per_row(cfg, traffic):
    """Forward + backward model FLOPs of one sequence, nothing recomputed:
    6 per multiply-add weight of the blocks (the pooler and the two-class
    head are under a millionth), plus full attention's two products."""
    t, h, n = traffic["seq"], cfg["hidden_size"], cfg["num_hidden_layers"]
    weights = n * (4 * h * h + 2 * h * cfg["intermediate_size"])
    attn = n * 2 * 2 * t * t * h
    return 6 * weights * t + 3 * attn
