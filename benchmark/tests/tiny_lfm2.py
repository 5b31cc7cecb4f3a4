"""The LFM2-24B-A2B configuration cut to what a CPU holds, for the tests:
3 layers (conv with the dense layer, full_attention and conv routed), 4
query / 2 key-value heads of 16, 16 router outputs top-2 of which 4 are
held, a random selection bias. Only tests use it; a cell of
``BENCHMARK.json`` never does."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "LFM2-24B-A2B"


def lfm2(bias_std=0.1):
    with open(os.path.join(HERE, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, moe_intermediate_size=24,
               router_width=16, num_experts=4, held_experts=[0, 1, 2, 3],
               num_experts_per_tok=2, num_hidden_layers=3,
               layer_types=["conv", "full_attention", "conv"],
               vocab_size=128, max_position_embeddings=32)
    cfg["assumed"] = dict(cfg["assumed"], head_dim=16,
                          selection_bias_std=bias_std)
    traffic = {"kind": "train", "seq": 32, "batch": 8, "chips": 4,
               "epoch_steps": 4000, "reference_rows_per_chip": 1,
               "token_ids": "zipf", "zipf_s": 1.0}
    return cfg, traffic
