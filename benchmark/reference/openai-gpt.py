"""GPT-1 (Radford et al. 2018; ``openai-gpt`` ``config.json``) in plain
float32 ``jax.numpy``: token + learned position embeddings, twelve post-LN
blocks with causal attention, a linear head and the mean next-token
cross-entropy. Departures from the published model are the configuration
file's ``assumed`` (the head is not tied to the token table)."""

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B


def init_params(cfg, key):
    h, v, std = cfg["n_embd"], cfg["vocab_size"], cfg["initializer_range"]
    ks = jax.random.split(key, cfg["n_layer"] + 3)
    p = {"wte": jax.random.normal(ks[0], (v, h), jnp.float32) * std,
         "wpe": jax.random.normal(ks[1], (cfg["n_positions"], h),
                                  jnp.float32) * std,
         "head": B.init_linear(ks[2], h, v, std)}
    for i in range(cfg["n_layer"]):
        p[f"block{i}"] = B.init_block(ks[i + 3], h, 4 * h, std)
    return p


def loss_sum(params, x, y, cfg, mode="f32"):
    """Summed next-token cross-entropy over a block of rows, and how many
    terms it has. ``x`` and ``y`` are (rows, T) token ids."""
    t = x.shape[1]
    hid = jnp.take(params["wte"], x, axis=0) + params["wpe"][None, :t]
    for i in range(cfg["n_layer"]):
        blk = jax.checkpoint(lambda p, a: B.block(
            p, a, n_head=cfg["n_head"], causal=True, keep=None,
            eps=cfg["layer_norm_epsilon"], gelu_tanh=True, mode=mode))
        hid = blk(params[f"block{i}"], hid)
    logits = B.dense(params["head"], hid, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked), picked.size
