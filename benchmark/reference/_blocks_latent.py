"""Plain float32 ``jax.numpy`` pieces of a decoder with latent attention
(MLA), a sigmoid-scored router with a selection bias, a shared expert and a
dense gated feed-forward layer, for the references that need them. Nothing
here imports the program; every matrix product goes through ``_blocks.mm``,
so the fp8 control reaches all of them. ``_blocks_decoder``'s ``rms_norm``,
``rotary_tables`` and ``rotate`` are used as they are.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import _blocks as B
from benchmark.reference import _blocks_decoder as D

#: queries per block of the blocked attention below
QUERY_BLOCK = 512


def gated(p, x, mode):
    """``(silu(x Wgate) * (x Wup)) Wdown``: the dense layer, the shared
    expert and (with a leading expert axis sliced off) every routed one."""
    h = jax.nn.silu(B.mm(x, p["Wgate"], mode)) * B.mm(x, p["Wup"], mode)
    return B.mm(h, p["Wdown"], mode)


def latent_attention(p, x, tables, *, n_head, kv_rank, nope, rope, v_dim,
                     eps, mode):
    """Causal multi-head latent attention on (B, T, H), the expanded form:
    ``c_q = RMSNorm(x Wqa)``, ``q = c_q Wqb`` in heads of ``[nope, rope]``;
    ``[c_kv, k_pe] = x Wkva``, ``c_kv = RMSNorm(c_kv)``, ``c_kv Wkvb`` in
    heads of ``[k_nope (nope), v (v_dim)]``; rotary (half-split pairs) on
    the ``rope`` slice of every query head and on the ONE ``k_pe`` that all
    heads share; ``softmax(q k^T / sqrt(nope + rope) + causal) v``; ``Wo``.
    The scores of one row at T = 8192 and 20 heads are 5.4 GB in float32,
    so the queries go in blocks of ``QUERY_BLOCK`` (``lax.map``, each block
    rematerialised in the backward pass): blocking of the plain
    whole-matrix softmax, not a kernel."""
    b, t, _ = x.shape
    cos, sin = tables
    qk = nope + rope

    def heads(a, width):
        return a.reshape(b, t, n_head, width).transpose(0, 2, 1, 3)
    c_q = D.rms_norm(p["q_norm"], B.mm(x, p["Wqa"], mode), eps)
    q = heads(B.mm(c_q, p["Wqb"], mode), qk)
    kva = B.mm(x, p["Wkva"], mode)
    c_kv = D.rms_norm(p["kv_norm"], kva[..., :kv_rank], eps)
    k_pe = D.rotate(kva[..., kv_rank:], cos, sin)           # (b, t, rope)
    kv = heads(B.mm(c_kv, p["Wkvb"], mode), nope + v_dim)
    q = jnp.concatenate([q[..., :nope], D.rotate(q[..., nope:], cos, sin)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None], (b, n_head, t, rope))], axis=-1)
    v = kv[..., nope:]
    kt = k.transpose(0, 1, 3, 2)                            # (b, n, qk, t)
    blk = min(QUERY_BLOCK, t)
    if t % blk:
        raise ValueError(f"T = {t} is not a whole number of query blocks")
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one(args):
        qb, start = args                                    # (b, n, blk, qk)
        s = B.mm(qb, kt, mode) / math.sqrt(qk)
        i = start + jnp.arange(blk)[:, None]
        s = jnp.where(j <= i, s, B.NEG)
        return B.mm(jax.nn.softmax(s, axis=-1), v, mode)

    qs = q.reshape(b, n_head, t // blk, blk, qk)
    o = jax.lax.map(one, (jnp.moveaxis(qs, 2, 0), jnp.arange(0, t, blk)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, n_head, t, v_dim)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, n_head * v_dim)
    return B.mm(o, p["Wo"], mode)


def sigmoid_routing(p, x, bias, *, top_k, norm_topk, scale, mode):
    """``(weights (..., k), experts (..., k))`` of a sigmoid-scored
    token-choice router: ``s = sigmoid(x Wg)``; the chosen are the
    ``top_k`` of ``s + bias``; their weights are ``s`` WITHOUT the bias,
    divided by their sum where ``norm_topk``, times ``scale``."""
    s = jax.nn.sigmoid(B.mm(x, p["Wg"], mode))
    _, idx = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * scale, idx


def routed_and_shared(p, x, bias, *, held, top_k, norm_topk, scale, mode,
                      shared=True):
    """The held experts' part of ``sum_e w_e E_e(x)`` in its plainest form
    (every held expert on every token, times a weight that is zero where
    the expert was not chosen) plus, once, the shared expert every token
    passes through. ``held[i]`` is the router output that ``Wgate[i]``,
    ``Wup[i]``, ``Wdown[i]`` belong to."""
    w, idx = sigmoid_routing(p, x, bias, top_k=top_k, norm_topk=norm_topk,
                             scale=scale, mode=mode)
    y = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1, keepdims=True)
        y = y + w_e * gated({k: p[k][i] for k in ("Wgate", "Wup", "Wdown")},
                            x, mode)
    return y + gated(p["shared"], x, mode) if shared else y
