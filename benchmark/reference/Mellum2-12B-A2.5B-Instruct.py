"""Mellum2-12B-A2.5B-Instruct (JetBrains; its ``config.json``) in plain
float32 ``jax.numpy``, as one chip of an expert-parallel deployment holds
it: token embedding, pre-norm blocks (RMSNorm; rotary grouped-head causal
attention, windowed or full by ``layer_types``, YaRN on the full layers;
64-way top-8 routing of SiLU-gated experts, of which the ``held_experts``
are computed and the others' part left out), a final RMSNorm, an untied
head over the vocabulary slice, and the mean next-token cross-entropy.

Departures from the published model are the configuration file's ``assumed``
and the share (``reduced``, ``deployment``). Attention runs over blocks of
512 queries inside each layer's ``jax.checkpoint``: blocking so that float32
scores fit, not a kernel (``_blocks_decoder.attention``)."""

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B
from benchmark.reference import _blocks_decoder as D


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _gamma(hidden):
    return {"gamma": jnp.ones((hidden,), jnp.float32)}


def _router(key, hidden, width, classes, std):
    """The router's initial columns, tied in ``classes`` classes: column
    ``e`` starts as random column ``e % classes`` (``assumed.router_init``
    in the configuration's file says why). Every token's top choices are
    then the whole of its best class, one member on each chip's share;
    nothing ties the columns once the optimizer moves them."""
    return jnp.tile(_normal(key, (hidden, classes), std),
                    (1, width // classes))


def init_params(cfg, key):
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    std = cfg["assumed"]["initializer_range"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    ks = jax.random.split(key, cfg["num_hidden_layers"] + 2)
    p = {"wte": _normal(ks[0], (v, h), std),
         "norm": _gamma(h),
         "head": {"W": _normal(ks[1], (h, v), std)}}
    for i in range(cfg["num_hidden_layers"]):
        k = jax.random.split(ks[i + 2], 8)
        p[f"block{i}"] = {
            "ln1": _gamma(h),
            "attn": {"Wq": _normal(k[0], (h, q), std),
                     "Wk": _normal(k[1], (h, kv), std),
                     "Wv": _normal(k[2], (h, kv), std),
                     "Wo": _normal(k[3], (q, h), std)},
            "ln2": _gamma(h),
            "moe": {"Wg": _router(k[4], h, cfg["router_width"],
                                  cfg["assumed"]["router_init_classes"],
                                  cfg["assumed"]["router_init_std"]),
                    "Wgate": _normal(k[5], (held, h, width), std),
                    "Wup": _normal(k[6], (held, h, width), std),
                    "Wdown": _normal(k[7], (held, width, h), std)}}
    return p


def _block(cfg, kind, mode):
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None

    def block(p, x, tables):
        h1 = x + D.attention(
            p["attn"], D.rms_norm(p["ln1"], x, eps), tables,
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            window=window, mode=mode)
        return h1 + D.routed_experts(
            p["moe"], D.rms_norm(p["ln2"], h1, eps),
            held=cfg["held_experts"], top_k=cfg["num_experts_per_tok"],
            norm_topk=cfg["norm_topk_prob"], mode=mode)
    return jax.checkpoint(block)


def loss_sum(params, x, y, cfg, mode="f32"):
    """Summed next-token cross-entropy over a block of rows, and how many
    terms it has. ``x`` and ``y`` are (rows, T) token ids of the slice."""
    t = x.shape[1]
    hid = jnp.take(params["wte"], x, axis=0)
    tables = {kind: D.rotary_tables(spec, cfg["head_dim"], t)
              for kind, spec in cfg["rope_parameters"].items()}
    for i, kind in enumerate(cfg["layer_types"]):
        hid = _block(cfg, kind, mode)(params[f"block{i}"], hid, tables[kind])
    hid = D.rms_norm(params["norm"], hid, cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(B.mm(hid, params["head"]["W"], mode), axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked), picked.size
