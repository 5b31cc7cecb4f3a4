"""LFM2-24B-A2B (LiquidAI; its ``config.json``, ``model_type`` ``lfm2_moe``)
in plain float32 ``jax.numpy``, as one chip of an expert-parallel deployment
holds it: token embedding, pre-norm blocks (RMSNorm; by ``layer_types`` a
gated short convolution or grouped attention with an RMSNorm over each head
of q and k and rotary positions; below ``num_dense_layers`` a dense
SiLU-gated feed-forward layer, in the others 64-way sigmoid-scored top-4
routing of gated experts, of which the ``held_experts`` are computed and
the others' part left out), a final RMSNorm (the published
``embedding_norm``), a head that IS the token table, and the mean next-token
cross-entropy.

Departures from the published model are the configuration file's ``assumed``
and the share (``reduced``, ``deployment``): the head tied to the table
(the family's convention; the catalog's row has no key for it), half-split
rotary pairs, ``expert_bias`` held fixed (zeros in the cell;
``assumed.selection_bias_std`` > 0 gives the tests a random one, which is no
parameter: no optimizer touches it). The routing weights are divided by
``sum + 1e-6`` as published; the program's ``top_k_routing`` divides by
``max(sum, 1e-9)``, 5e-7 of a weight away. A tree is keyed as the program's
(a block's mixer under ``attn``, whatever its kind). Attention runs over
blocks of 512 queries inside each layer's ``jax.checkpoint``: blocking so
that float32 scores fit, not a kernel (``_blocks_conv.attention``)."""

import jax
import jax.numpy as jnp

from benchmark.lib import reference_run
from benchmark.reference import _blocks as B
from benchmark.reference import _blocks_conv as C
from benchmark.reference import _blocks_decoder as D
from benchmark.reference import _blocks_latent as L

#: the router's start and the gated matrices' are the GLM configuration's
#: (``assumed.router_init`` there says why): taken from its reference
_glm = reference_run.load("reference", "GLM-4.7-Flash")
_normal, _gamma, _gated, _router = (_glm._normal, _glm._gamma, _glm._gated,
                                    _glm._router)


def is_dense(cfg, i):
    return i < cfg["num_dense_layers"]


def head_dim(cfg):
    """``config.json`` has no ``head_dim``: the model's code takes
    ``hidden_size / num_attention_heads``, which ``assumed`` states."""
    return cfg["assumed"]["head_dim"]


def selection_bias(cfg, i):
    """Layer ``i``'s ``expert_bias`` (router_width,): zeros, or for the
    tests normal(0, ``assumed.selection_bias_std``) from a key of the
    layer's number alone. A constant of the step, not a parameter."""
    std = cfg["assumed"].get("selection_bias_std", 0.0)
    if not std:
        return jnp.zeros((cfg["router_width"],), jnp.float32)
    return _normal(jax.random.key(1000 + i, impl="threefry2x32"),
                   (cfg["router_width"],), std)


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    a = cfg["assumed"]
    std = a["initializer_range"]
    d = head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    taps = cfg["conv_L_cache"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    ks = jax.random.split(key, cfg["num_hidden_layers"] + 1)
    p = {"wte": _normal(ks[0], (v, h), std), "norm": _gamma(h)}
    for i, kind in enumerate(cfg["layer_types"]):
        k = jax.random.split(ks[i + 1], 8)
        if kind == "conv":
            bound = taps ** -0.5
            mixer = {"Win": _normal(k[0], (h, 3 * h), std),
                     "conv": jax.random.uniform(k[1], (h, taps), jnp.float32,
                                                -bound, bound),
                     "Wout": _normal(k[2], (h, h), std)}
        else:
            mixer = {"Wq": _normal(k[0], (h, q), std),
                     "Wk": _normal(k[1], (h, kv), std),
                     "Wv": _normal(k[2], (h, kv), std),
                     "Wo": _normal(k[3], (q, h), std),
                     "q_norm": _gamma(d), "k_norm": _gamma(d)}
        blk = {"ln1": _gamma(h), "attn": mixer, "ln2": _gamma(h)}
        if is_dense(cfg, i):
            blk["ffn"] = _gated(k[4:7], (h, cfg["intermediate_size"]),
                                (cfg["intermediate_size"], h), std)
        else:
            blk["ffn"] = {
                "Wg": _router(k[7], h, cfg["router_width"],
                              cfg["num_experts_per_tok"], i,
                              a["router_init_std"],
                              a["router_init_lo_scale"],
                              a["router_init_lead"]),
                **_gated(k[4:7], (held, h, width), (held, width, h), std)}
        p[f"block{i}"] = blk
    return p


def _block(cfg, i, mode):
    eps = cfg["norm_eps"]
    conv = cfg["layer_types"][i] == "conv"
    bias = None if is_dense(cfg, i) else selection_bias(cfg, i)

    def block(p, x, tables):
        a = D.rms_norm(p["ln1"], x, eps)
        if conv:
            h1 = x + C.short_conv(p["attn"], a, mode)
        else:
            h1 = x + C.attention(
                p["attn"], a, tables, n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
                eps=eps, mode=mode)
        a = D.rms_norm(p["ln2"], h1, eps)
        if bias is None:
            return h1 + L.gated(p["ffn"], a, mode)
        return h1 + C.routed(
            p["ffn"], a, bias, held=cfg["held_experts"],
            top_k=cfg["num_experts_per_tok"],
            norm_topk=cfg["norm_topk_prob"],
            scale=cfg["routed_scaling_factor"], mode=mode)
    return jax.checkpoint(block)


def head_matrix(params):
    """The head IS the token table: (H, vocab), its transpose."""
    return params["wte"].T


def loss_sum(params, x, y, cfg, mode="f32"):
    """Summed next-token cross-entropy over a block of rows, and how many
    terms it has. ``x`` and ``y`` are (rows, T) token ids of the slice."""
    t = x.shape[1]
    hid = jnp.take(params["wte"], x, axis=0)
    tables = D.rotary_tables(cfg["rope_parameters"], head_dim(cfg), t)
    for i in range(cfg["num_hidden_layers"]):
        hid = _block(cfg, i, mode)(params[f"block{i}"], hid, tables)
    hid = D.rms_norm(params["norm"], hid, cfg["norm_eps"])
    logp = jax.nn.log_softmax(B.mm(hid, head_matrix(params), mode), axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.sum(picked), picked.size
