"""Plain float32 ``jax.numpy`` pieces shared by the configurations' references.

Nothing here imports the program. Every matrix product goes through ``mm``,
whose ``mode`` is the reference's precision: ``"f32"`` is the reference proper
(float32 operands at ``Precision.HIGHEST``), ``"fp8"`` is the control of "How
``correct`` is decided" (the nearest precision below the bf16 the
configurations state): both operands of every product rounded to float8
e4m3's four significant bits, straight-through for the gradient, the product
and everything else in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e9


def _fp8_round(x):
    """Round to the 4 significant bits of float8 e4m3 (round-to-nearest-even
    on the float32 bit pattern), with a straight-through gradient. The
    exponent is left as it is, which a per-tensor scale would see to; that
    makes this control no coarser than a real fp8 product."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    drop = 23 - 3
    bits = bits + ((1 << (drop - 1)) - 1) + ((bits >> drop) & 1)
    q = jax.lax.bitcast_convert_type(bits & ~jnp.uint32((1 << drop) - 1),
                                     jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, mode: str):
    if mode == "fp8":
        a, b = _fp8_round(a), _fp8_round(b)
    elif mode != "f32":
        raise ValueError(f"unknown reference precision {mode!r}")
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def dense(p, x, mode):
    return mm(x, p["W"], mode) + p["b"]


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def gelu(x, tanh_form: bool):
    if tanh_form:
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def attention(p, x, n_head, causal, keep, mode):
    """Multi-head self-attention on (B, T, H); ``keep`` is a (B, T) 1/0 key
    mask or None."""
    b, t, h = x.shape
    d = h // n_head
    qkv = dense(p["qkv"], x, mode)
    q, k, v = (a.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    s = mm(q, k.transpose(0, 1, 3, 2), mode) / math.sqrt(d)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, NEG)
    if keep is not None:
        s = s + (1.0 - keep[:, None, None, :]) * NEG
    o = mm(jax.nn.softmax(s, axis=-1), v, mode)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h)
    return dense(p["proj"], o, mode)


def block(p, x, *, n_head, causal, keep, eps, gelu_tanh, mode):
    """Post-LayerNorm transformer block (GPT-1 and BERT share it)."""
    x = layer_norm(p["ln1"], x + attention(p["attn"], x, n_head, causal,
                                           keep, mode), eps)
    f = dense(p["out"], gelu(dense(p["fc"], x, mode), gelu_tanh), mode)
    return layer_norm(p["ln2"], x + f, eps)


def init_linear(key, n_in, n_out, std):
    return {"W": jax.random.normal(key, (n_in, n_out), jnp.float32) * std,
            "b": jnp.zeros((n_out,), jnp.float32)}


def init_layer_norm(hidden):
    return {"gamma": jnp.ones((hidden,), jnp.float32),
            "beta": jnp.zeros((hidden,), jnp.float32)}


def init_block(key, hidden, ffn, std):
    ks = jax.random.split(key, 4)
    return {"attn": {"qkv": init_linear(ks[0], hidden, 3 * hidden, std),
                     "proj": init_linear(ks[1], hidden, hidden, std)},
            "ln1": init_layer_norm(hidden),
            "fc": init_linear(ks[2], hidden, ffn, std),
            "out": init_linear(ks[3], ffn, hidden, std),
            "ln2": init_layer_norm(hidden)}


def seed_key(seed: int):
    """A threefry key from any non-negative seed, the same on every platform
    (the program switches the default generator to ``rbg`` on a TPU). Made
    outside ``jit`` and passed in: a seed traced as a constant would make
    every seed a program of its own, compiled anew in every run."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32")
    return jax.random.fold_in(key, seed >> 31)


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adam_step(params, grads, state, opt):
    """Adam / AdamW as published (bias-corrected, decoupled decay); ``opt``
    holds ``lr``, ``b1``, ``b2``, ``eps`` and ``weight_decay``."""
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)

    def upd(p, m, v):
        step = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf))
                                        + opt["eps"])
        return p - opt["lr"] * (step + opt["weight_decay"] * p)
    return jax.tree.map(upd, params, m, v), {"m": m, "v": v, "t": t}
