"""Plain float32 ``jax.numpy`` pieces of a decoder whose mixers are gated
short convolutions in most layers and q/k-normed grouped attention in the
others (the ``lfm2_moe`` models), for the references that need them. Nothing
here imports the program; every matrix product goes through ``_blocks.mm``,
so the fp8 control reaches all of them. ``_blocks_decoder``'s ``rms_norm``,
``rotary_tables`` and ``rotate`` and ``_blocks_latent``'s ``gated`` are used
as they are.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B
from benchmark.reference import _blocks_decoder as D
from benchmark.reference import _blocks_latent as L

#: queries per block of the blocked attention below
QUERY_BLOCK = 512


def short_conv(p, x, mode):
    """The gated short convolution on (B, T, H): ``[B, C, u] = x Win``
    (H -> 3 H, split in that order); ``v = B * u``; a causal depthwise
    convolution ``c[t] = sum_j conv[:, j] * v[t - (K - 1) + j]`` with ``v``
    zero before position 0 (``torch.nn.Conv1d(H, H, K, groups=H,
    padding=K - 1)`` cut to its first T outputs: ``conv`` (H, K) is that
    layer's weight without its middle axis); ``y = (C * c) Wout``. No
    activation, no bias, no positions."""
    t = x.shape[1]
    gate_b, gate_c, u = jnp.split(B.mm(x, p["Win"], mode), 3, axis=-1)
    k = p["conv"].shape[1]
    c = jax.lax.conv_general_dilated(
        gate_b * u, p["conv"].T[:, None, :], window_strides=(1,),
        padding=[(k - 1, k - 1)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=p["conv"].shape[0],
        precision=jax.lax.Precision.HIGHEST)[:, :t]
    return B.mm(gate_c * c, p["Wout"], mode)


def head_norm(p, x, eps):
    """RMSNorm over each head's columns, (..., head_dim), one weight vector
    shared by the heads."""
    return D.rms_norm(p, x, eps)


def attention(p, x, tables, *, n_head, n_kv_head, head_dim, eps, mode):
    """Causal grouped-head attention on (B, T, H): ``q``, ``k``, ``v`` in
    heads of ``head_dim``; RMSNorm over each head of q (``q_norm``) and of
    k (``k_norm``); rotary positions (half-split pairs) on both; query head
    h on key/value head ``h // (n_head / n_kv_head)`` at scale
    ``head_dim ** -0.5``; ``Wo``. The queries go in blocks of
    ``QUERY_BLOCK`` (``lax.map``, each block rematerialised in the backward
    pass) so that float32 scores fit: blocking of the plain whole-matrix
    softmax, not a kernel."""
    b, t, _ = x.shape
    group = n_head // n_kv_head
    cos, sin = tables

    def heads(w, n):
        return B.mm(x, w, mode).reshape(b, t, n, head_dim).transpose(
            0, 2, 1, 3)
    q = D.rotate(head_norm(p["q_norm"], heads(p["Wq"], n_head), eps),
                 cos, sin)
    k = D.rotate(head_norm(p["k_norm"], heads(p["Wk"], n_kv_head), eps),
                 cos, sin)
    v = heads(p["Wv"], n_kv_head)
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
        s = jnp.where(j <= i, s, B.NEG)
        return B.mm(jax.nn.softmax(s, axis=-1), v, mode)

    qs = q.reshape(b, n_kv_head, group, t // blk, blk, head_dim)
    o = jax.lax.map(one, (jnp.moveaxis(qs, 3, 0), jnp.arange(0, t, blk)))
    o = jnp.moveaxis(o, 0, 3).reshape(b, n_head, t, head_dim)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, n_head * head_dim)
    return B.mm(o, p["Wo"], mode)


def routed(p, x, bias, *, held, top_k, norm_topk, scale, mode):
    """The held experts' part of ``sum_e w_e E_e(x)`` as ``lfm2_moe``
    routes: ``s = sigmoid(x Wg)``; the chosen are the ``top_k`` of ``s +
    bias`` (``expert_bias``: no gradient reaches it); their weights are
    ``s`` WITHOUT the bias, over ``(their sum + 1e-6)`` where
    ``norm_topk`` (the published epsilon), times ``scale``. Every held
    expert on every token, times a weight that is zero where the expert
    was not chosen; ``held[i]`` is the router output that ``Wgate[i]``,
    ``Wup[i]``, ``Wdown[i]`` belong to. No shared expert."""
    s = jax.nn.sigmoid(B.mm(x, p["Wg"], mode))
    _, idx = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scale
    y = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1, keepdims=True)
        y = y + w_e * L.gated({k: p[k][i] for k in ("Wgate", "Wup", "Wdown")},
                              x, mode)
    return y
