"""BERT-base (Devlin et al. 2018; ``bert-base-uncased`` ``config.json``) as a
sequence classifier, in plain float32 ``jax.numpy``: word + position + type
embeddings under a LayerNorm, twelve post-LN bidirectional blocks with a key
mask, the tanh pooler over the first token, a linear head and the mean
cross-entropy over its classes."""

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B

NUM_CLASSES = 2


def init_params(cfg, key):
    h, std = cfg["hidden_size"], cfg["initializer_range"]
    n = cfg["num_hidden_layers"]
    ks = jax.random.split(key, n + 5)

    def table(k, rows):
        return jax.random.normal(k, (rows, h), jnp.float32) * std

    p = {"word": table(ks[0], cfg["vocab_size"]),
         "position": table(ks[1], cfg["max_position_embeddings"]),
         "token_type": table(ks[2], cfg["type_vocab_size"]),
         "emb_ln": B.init_layer_norm(h),
         "pooler": B.init_linear(ks[3], h, h, std),
         "cls": B.init_linear(ks[4], h, NUM_CLASSES, std)}
    for i in range(n):
        p[f"block{i}"] = B.init_block(ks[i + 5], h, cfg["intermediate_size"],
                                      std)
    return p


def loss_sum(params, x, y, cfg, mode="f32"):
    """Summed class cross-entropy over a block of rows and its number of
    terms. ``x`` is [ids, token_type, position, keep] each (rows, T)."""
    ids, token_type, pos, keep = x
    eps = cfg["layer_norm_eps"]
    hid = (jnp.take(params["word"], ids, axis=0)
           + jnp.take(params["position"], pos, axis=0)
           + jnp.take(params["token_type"], token_type, axis=0))
    hid = B.layer_norm(params["emb_ln"], hid, eps)
    for i in range(cfg["num_hidden_layers"]):
        blk = jax.checkpoint(lambda p, a: B.block(
            p, a, n_head=cfg["num_attention_heads"], causal=False, keep=keep,
            eps=eps, gelu_tanh=False, mode=mode))
        hid = blk(params[f"block{i}"], hid)
    pooled = jnp.tanh(B.dense(params["pooler"], hid[:, 0, :], mode))
    logp = jax.nn.log_softmax(B.dense(params["cls"], pooled, mode), axis=-1)
    picked = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked), picked.size
