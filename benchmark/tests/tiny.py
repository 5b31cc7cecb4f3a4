"""The cells of the tests: the two configurations' files with depth, widths
and vocabulary cut to what a CPU holds, and traffic to match. Only tests
use them; a cell of ``BENCHMARK.json`` never does."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def gpt():
    cfg = _cfg("openai-gpt")
    cfg.update(n_layer=2, n_embd=128, n_head=2, vocab_size=2048,
               n_positions=128, n_ctx=128)
    traffic = {"kind": "train", "seq": 128, "batch": 8, "chips": 4,
               "epoch_steps": 4000, "reference_rows_per_chip": 1}
    return cfg, traffic


def bert():
    cfg = _cfg("bert-base-uncased")
    cfg.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=2,
               intermediate_size=512, vocab_size=2048,
               max_position_embeddings=64)
    traffic = {"kind": "train", "seq": 64, "batch": 8, "chips": 4,
               "epoch_steps": 8000, "reference_rows_per_chip": 1}
    return cfg, traffic


CELLS = {"gpt": gpt, "bert": bert}
