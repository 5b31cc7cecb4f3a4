"""GLM-4.7-Flash (zai-org; its ``config.json``) in plain float32
``jax.numpy``, as one chip of an expert-parallel deployment holds it: token
embedding, pre-norm blocks (RMSNorm; latent attention with rotary positions
on a 64-wide slice and one rotary key head shared by all heads; layer 0 a
dense SiLU-gated feed-forward layer, the others 64-way sigmoid-scored top-4
routing of gated experts, of which the ``held_experts`` are computed and
the others' part left out, beside one shared expert), a final RMSNorm, an
untied head over the vocabulary slice, and the mean next-token
cross-entropy.

Departures from the published model are the configuration file's ``assumed``
and the share (``reduced``, ``deployment``): half-split rotary pairs (a
column permutation of the random ``Wqb`` / ``Wkva``), the selection bias
``e_score_correction_bias`` held fixed (zeros in the cell; ``assumed.
selection_bias_std`` > 0 gives the tests a random one, which is no
parameter: no optimizer touches it), no multi-token-prediction layer.
Attention runs over blocks of 512 queries inside each layer's
``jax.checkpoint``: blocking so that float32 scores fit, not a kernel
(``_blocks_latent.latent_attention``)."""

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B
from benchmark.reference import _blocks_decoder as D
from benchmark.reference import _blocks_latent as L


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _gamma(n):
    return {"gamma": jnp.ones((n,), jnp.float32)}


def _router(key, hidden, width, top_k, layer, std, lo_scale, lead):
    """The router's initial columns (``assumed.router_init`` in the
    configuration's file says why). ``width / (2 top_k)`` classes of
    ``2 top_k`` columns each, class ``c`` the columns ``e % classes == c``
    (so its first column is expert ``c``, one of this chip's); the classes
    come in antipodal pairs, ``+w_d`` and ``-w_d`` of ``classes / 2`` random
    directions, so the best class's logit is ``max_d |x w_d|`` and positive
    for every token. Half of a class's columns are its ``top_k`` chosen ones
    whenever the class is best: the first of them the direction itself, the
    others ``1 + lead`` times it, so that where two classes tie it is the
    first that gives way and no token ever holds two of the firsts. The
    other half is ``lo_scale`` times the direction and never chosen. The
    chosen half is the columns ``e < width / 2`` in odd layers, the others
    in even layers. Nothing ties the columns once the optimizer moves
    them; ``lead`` and ``1 - lo_scale`` are far more than it moves them."""
    classes = width // (2 * top_k)
    if classes * 2 * top_k != width or classes % 2:
        raise ValueError(f"router width {width} is not an even number of "
                         f"classes of {2 * top_k} columns")
    e = jnp.arange(width)
    c, member = e % classes, e // classes
    sign = jnp.where(c < classes // 2, 1.0, -1.0)
    odd = bool(layer % 2)
    chosen = (member < top_k) == odd
    first = member == (0 if odd else top_k)
    scale = jnp.where(chosen, jnp.where(first, 1.0, 1.0 + lead), lo_scale)
    w = _normal(key, (hidden, classes // 2), std)
    return w[:, c % (classes // 2)] * (sign * scale)[None, :]


def _gated(keys, shape_in, shape_out, std):
    return {"Wgate": _normal(keys[0], shape_in, std),
            "Wup": _normal(keys[1], shape_in, std),
            "Wdown": _normal(keys[2], shape_out, std)}


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def selection_bias(cfg, i):
    """Layer ``i``'s ``e_score_correction_bias`` (router_width,): zeros, or
    for the tests normal(0, ``assumed.selection_bias_std``) from a key of
    the layer's number alone. A constant of the step, not a parameter."""
    std = cfg["assumed"].get("selection_bias_std", 0.0)
    if not std:
        return jnp.zeros((cfg["router_width"],), jnp.float32)
    return _normal(jax.random.key(1000 + i, impl="threefry2x32"),
                   (cfg["router_width"],), std)


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    a = cfg["assumed"]
    std = a["initializer_range"]
    n = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    ks = jax.random.split(key, cfg["num_hidden_layers"] + 2)
    p = {"wte": _normal(ks[0], (v, h), std),
         "norm": _gamma(h),
         "head": {"W": _normal(ks[1], (h, v), std)}}
    for i in range(cfg["num_hidden_layers"]):
        k = jax.random.split(ks[i + 2], 12)
        blk = {
            "ln1": _gamma(h),
            "attn": {
                "Wqa": _normal(k[0], (h, q_rank), std),
                "q_norm": _gamma(q_rank),
                "Wqb": _normal(k[1], (q_rank, n * qk), std),
                "Wkva": _normal(k[2], (h, kv_rank + cfg["qk_rope_head_dim"]),
                                std),
                "kv_norm": _gamma(kv_rank),
                "Wkvb": _normal(k[3], (kv_rank, n * (
                    cfg["qk_nope_head_dim"] + cfg["v_head_dim"])), std),
                "Wo": _normal(k[4], (n * cfg["v_head_dim"], h), std)},
            "ln2": _gamma(h)}
        if is_dense(cfg, i):
            blk["ffn"] = _gated(k[5:8], (h, cfg["intermediate_size"]),
                                (cfg["intermediate_size"], h), std)
        else:
            blk["ffn"] = {
                "Wg": _router(k[8], h, cfg["router_width"],
                              cfg["num_experts_per_tok"], i,
                              a["router_init_std"],
                              a["router_init_lo_scale"],
                              a["router_init_lead"]),
                **_gated(k[5:8], (held, h, width), (held, width, h), std),
                "shared": _gated(k[9:12], (
                    h, width * cfg["n_shared_experts"]), (
                    width * cfg["n_shared_experts"], h), std)}
        p[f"block{i}"] = blk
    return p


def _block(cfg, i, mode):
    eps = cfg["rms_norm_eps"]
    bias = None if is_dense(cfg, i) else selection_bias(cfg, i)

    def block(p, x, tables):
        h1 = x + L.latent_attention(
            p["attn"], D.rms_norm(p["ln1"], x, eps), tables,
            n_head=cfg["num_attention_heads"], kv_rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], eps=eps, mode=mode)
        a = D.rms_norm(p["ln2"], h1, eps)
        if bias is None:
            return h1 + L.gated(p["ffn"], a, mode)
        return h1 + L.routed_and_shared(
            p["ffn"], a, bias, held=cfg["held_experts"],
            top_k=cfg["num_experts_per_tok"],
            norm_topk=cfg["norm_topk_prob"],
            scale=cfg["routed_scaling_factor"], mode=mode)
    return jax.checkpoint(block)


def loss_sum(params, x, y, cfg, mode="f32"):
    """Summed next-token cross-entropy over a block of rows, and how many
    terms it has. ``x`` and ``y`` are (rows, T) token ids of the slice."""
    t = x.shape[1]
    hid = jnp.take(params["wte"], x, axis=0)
    tables = D.rotary_tables(
        {"rope_type": "default", "rope_theta": cfg["rope_theta"]},
        cfg["qk_rope_head_dim"], t)
    for i in range(cfg["num_hidden_layers"]):
        hid = _block(cfg, i, mode)(params[f"block{i}"], hid, tables)
    hid = D.rms_norm(params["norm"], hid, cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(B.mm(hid, params["head"]["W"], mode), axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked), picked.size
