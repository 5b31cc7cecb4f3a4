"""Plain float32 ``jax.numpy`` pieces of a pre-norm decoder with rotary
positions, grouped key/value heads, a causal window and routed experts, for
the references that need them. Nothing here imports the program; every
matrix product goes through ``_blocks.mm``, so the fp8 control reaches all
of them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B

#: queries per block of the blocked attention below
QUERY_BLOCK = 512


def rms_norm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["gamma"]


def yarn_correction_dim(beta, head_dim, spec):
    return (head_dim * math.log(spec["original_max_position_embeddings"]
                                / (beta * 2 * math.pi))
            / (2 * math.log(spec["rope_theta"])))


def rotary_tables(spec, head_dim, t):
    """float32 (cos, sin), each (t, head_dim), of one ``rope_parameters``
    entry: ``default``, or ``yarn`` as ``transformers`` computes it
    statically."""
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    extrap = spec["rope_theta"] ** (-2.0 * i / head_dim)
    scale = 1.0
    if spec["rope_type"] == "yarn":
        low = max(math.floor(yarn_correction_dim(spec["beta_fast"], head_dim,
                                                 spec)), 0)
        high = min(math.ceil(yarn_correction_dim(spec["beta_slow"], head_dim,
                                                 spec)), head_dim - 1)
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        inv_freq = extrap / spec["factor"] * ramp + extrap * (1.0 - ramp)
        scale = spec["attention_factor"]
    elif spec["rope_type"] == "default":
        inv_freq = extrap
    else:
        raise ValueError(spec["rope_type"])
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate(x, cos, sin):
    """``rotate_half`` pairs: dimension i with i + d/2."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(p, x, tables, *, n_head, n_kv_head, head_dim, window, mode):
    """Causal grouped-head attention on (B, T, H) with rotary positions;
    ``window`` None is full attention, else query i sees keys
    ``i - window < j <= i``. The scores of one row at T = 8192 are 8.6 GB in
    float32, so the queries go in blocks of ``QUERY_BLOCK`` (``lax.map``,
    each block rematerialised in the backward pass): blocking of the plain
    whole-matrix softmax, not a kernel."""
    b, t, _ = x.shape
    group = n_head // n_kv_head
    cos, sin = tables

    def heads(w, n):
        return B.mm(x, w, mode).reshape(b, t, n, head_dim).transpose(
            0, 2, 1, 3)
    q = rotate(heads(p["Wq"], n_head), cos, sin)
    k = rotate(heads(p["Wk"], n_kv_head), cos, sin)
    v = heads(p["Wv"], n_kv_head)
    # query head h attends key/value head h // group
    q = q.reshape(b, n_kv_head, group, t, head_dim)
    kt = k.transpose(0, 1, 3, 2)[:, :, None]            # (b, kv, 1, d, t)
    v = v[:, :, None]                                   # (b, kv, 1, t, d)
    blk = min(QUERY_BLOCK, t)
    if t % blk:
        raise ValueError(f"T = {t} is not a whole number of query blocks")
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(args):
        qb, start = args                                # (b, kv, g, blk, d)
        s = B.mm(qb, kt, mode) / math.sqrt(head_dim)
        i = start + jnp.arange(blk)[:, None]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        s = jnp.where(seen, s, B.NEG)
        return B.mm(jax.nn.softmax(s, axis=-1), v, mode)

    qs = q.reshape(b, n_kv_head, group, t // blk, blk, head_dim)
    o = jax.lax.map(one, (jnp.moveaxis(qs, 3, 0),
                          jnp.arange(0, t, blk)))       # (nb, b, kv, g, blk, d)
    o = jnp.moveaxis(o, 0, 3).reshape(b, n_head, t, head_dim)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, n_head * head_dim)
    return B.mm(o, p["Wo"], mode)


def routed_experts(p, x, *, held, top_k, norm_topk, mode):
    """The held experts' part of a token-choice top-k layer, in its plainest
    form: softmax over every router output, the ``top_k`` largest chosen
    (weights renormalised over the chosen where ``norm_topk``), and every
    held expert applied to every token, times a weight that is zero where
    the expert was not chosen. ``held[i]`` is the router output that
    ``Wgate[i]``, ``Wup[i]``, ``Wdown[i]`` belong to."""
    probs = jax.nn.softmax(B.mm(x, p["Wg"], mode), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1, keepdims=True)
        h = (jax.nn.silu(B.mm(x, p["Wgate"][i], mode))
             * B.mm(x, p["Wup"][i], mode))
        y = y + w_e * B.mm(h, p["Wdown"][i], mode)
    return y
