"""Attention core ops — the compute kernel behind TransformerLayer/BERT
(reference: ``pipeline/api/keras/layers/TransformerLayer.scala:56``,
``BERT.scala:66``, pyzoo ``layers/self_attention.py``).

Kept separate from the layer classes so the same interface can be served by
(a) this fused XLA softmax-attention, (b) a Pallas flash-attention kernel, or
(c) ring attention over the ``seq`` mesh axis (``parallel/ring_attention``) —
swap happens at the layer level without touching model code.

Logits/softmax run in float32 regardless of compute dtype (bfloat16 QKV is
fine into the MXU; accumulating attention weights in bf16 is not).
"""

from __future__ import annotations

import functools
import math
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e9


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None,
                          causal: bool = False,
                          dropout_rate: float = 0.0,
                          dropout_rng: Optional[jax.Array] = None,
                          window: Optional[int] = None,
                          ) -> jax.Array:
    """Multi-head scaled dot-product attention.

    q: (B, n_head, T, d_head); k, v: (B, n_kv_head, T, d_head) with
    ``n_head % n_kv_head == 0`` (grouped heads: query head ``h`` attends
    key/value head ``h // (n_head / n_kv_head)``); ``mask``: broadcastable
    to (B, n_head, Tq, Tk), 1.0 = attend / 0.0 = hide. ``window`` (causal
    calls only): a query sees the ``window`` latest keys up to and with its
    own position, ``i - window < j <= i``. Returns (B, n_head, T, d_head).
    """
    d_head = q.shape[-1]
    if window is not None and not causal:
        raise ValueError("a window is defined for causal attention only")
    if q.shape[1] != k.shape[1]:
        if q.shape[1] % k.shape[1]:
            raise ValueError(f"{q.shape[1]} query heads do not divide over "
                             f"{k.shape[1]} key/value heads")
        group = q.shape[1] // k.shape[1]
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(d_head, jnp.float32))
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), jnp.bool_), k=tk - tq)
        if window is not None:
            cm = cm & ~jnp.tril(jnp.ones((tq, tk), jnp.bool_),
                                k=tk - tq - window)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = logits + (1.0 - mask.astype(jnp.float32)) * NEG_INF
    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def split_heads(x: jax.Array, n_head: int) -> jax.Array:
    """(B, T, H) → (B, n_head, T, H/n_head)."""
    b, t, h = x.shape
    return x.reshape(b, t, n_head, h // n_head).transpose(0, 2, 1, 3)


def merge_heads(x: jax.Array) -> jax.Array:
    """(B, n_head, T, d) → (B, T, n_head*d)."""
    b, nh, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, nh * d)


# ---------------------------------------------------------------------------
# rotary positions (Su et al. 2021), plain and YaRN (Peng et al. 2023)
# ---------------------------------------------------------------------------

def rotary_inv_freq(head_dim: int, spec: Mapping) -> Tuple[np.ndarray, float]:
    """``(inv_freq, attention_factor)`` of one kind of layer, from a
    ``rope_parameters`` entry as ``transformers`` writes it: ``rope_type``
    ``default`` (``inv_freq_i = theta^(-2i/d)``) or ``yarn``, computed
    statically (the same table at every length): below the correction
    dimension of ``beta_fast`` the frequencies stay, above that of
    ``beta_slow`` they are divided by ``factor``, between them a linear
    ramp; cos and sin are both scaled by ``attention_factor``. Host
    arithmetic in float64, made once per layer kind at build."""
    half = head_dim // 2
    base = float(spec["rope_theta"])
    extrap = base ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    kind = spec.get("rope_type", "default")
    if kind == "default":
        return extrap.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rotary positions of type {kind!r} are not "
                         f"implemented (default, yarn)")
    factor = float(spec["factor"])
    low, high = yarn_correction_range(head_dim, spec)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = extrap / factor * ramp + extrap * (1.0 - ramp)
    scale = spec.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(scale)


def yarn_correction_range(head_dim: int, spec: Mapping) -> Tuple[int, int]:
    """``(low, high)``: the rotary dimensions between which YaRN ramps from
    the published frequencies to the interpolated ones."""
    def dim(beta):
        return (head_dim * math.log(
            spec["original_max_position_embeddings"] / (beta * 2 * math.pi))
            / (2 * math.log(spec["rope_theta"])))
    low = math.floor(dim(spec.get("beta_fast", 32)))
    high = math.ceil(dim(spec.get("beta_slow", 1)))
    return max(low, 0), min(high, head_dim - 1)


def rotary_tables(inv_freq, scale: float, t: int):
    """float32 ``(cos, sin)`` of positions ``0..t-1``, each (t, head_dim):
    the half-split layout, frequencies repeated over both halves."""
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 heads_first: bool = True) -> jax.Array:
    """Rotate (B, n_head, T, d_head), or with ``heads_first=False``
    (B, T, n_head, d_head), by the (T, d_head) tables, which broadcast over
    the head axis wherever it stands: ``rotate_half`` pairs (dimension i
    with i + d/2), in float32; the result has x's dtype."""
    if not heads_first:
        cos, sin = cos[:, None, :], sin[:, None, :]
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def rotary_tables_in_place(cos: jax.Array, sin: jax.Array, n_head: int,
                           head_dim: int, start: int = 0):
    """The (T, r) tables spread over a (T, n_head * head_dim) plane, for
    ``apply_rotary_in_place``: every head's lanes ``[start, start + r)``
    carry ``cos`` and ``sin`` with the sign ``rotate_half`` gives its
    partner (minus on the first half), its other lanes 1 and 0. Made once
    a step per kind of layer and shared by the layers of that kind."""
    t, r = cos.shape
    pad = ((0, 0), (start, head_dim - start - r))
    signed = jnp.concatenate([-sin[:, :r // 2], sin[:, r // 2:]], axis=-1)
    return (jnp.tile(jnp.pad(cos, pad, constant_values=1.0), (1, n_head)),
            jnp.tile(jnp.pad(signed, pad), (1, n_head)))


def apply_rotary_in_place(x: jax.Array, cos_full: jax.Array,
                          sin_signed: jax.Array, head_dim: int, rope_dim: int,
                          start: int = 0) -> jax.Array:
    """``apply_rotary`` on (B, T, n_head * head_dim), the heads side by
    side along the last axis where a projection wrote them, with the
    tables of ``rotary_tables_in_place``: the same products and sums, and
    no (B, T, n_head, head_dim) view of ``x`` (on a TPU that view is
    another tiling of the same bytes, so every operation on it costs a
    copy of the tensor). A lane's partner is ``rope_dim / 2`` lanes up in
    the first half of a head's rotary lanes and as many down in the
    second; lanes outside them meet a sine of 0. The rotation is
    orthogonal and its transpose is the rotation by the opposite angle,
    so the backward rule is this function again with the sine negated
    (what autodiff makes of the two rolls is slices of the cotangent that
    XLA materialises whole); the tables get no gradient."""
    return _rotate_in_place(x, cos_full, sin_signed,
                            (head_dim, rope_dim, start))


def _rotated(x, cos_full, sin_signed, geom, sign: float):
    head_dim, rope_dim, start = geom
    half = rope_dim // 2
    xf = x.astype(jnp.float32)
    first = (np.arange(x.shape[-1]) % head_dim - start) < half
    # a permutation: exact in x's own dtype
    partner = jnp.where(jnp.asarray(first), jnp.roll(x, -half, axis=-1),
                        jnp.roll(x, half, axis=-1))
    turned = partner.astype(jnp.float32) * sin_signed
    return (xf * cos_full + (turned if sign > 0 else -turned)
            ).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate_in_place(x, cos_full, sin_signed, geom):
    return _rotated(x, cos_full, sin_signed, geom, 1.0)


def _rotate_fwd(x, cos_full, sin_signed, geom):
    return (_rotate_in_place(x, cos_full, sin_signed, geom),
            (cos_full, sin_signed))


def _rotate_bwd(geom, tables, g):
    cos_full, sin_signed = tables
    return (_rotated(g, cos_full, sin_signed, geom, -1.0),
            jnp.zeros_like(cos_full), jnp.zeros_like(sin_signed))


_rotate_in_place.defvjp(_rotate_fwd, _rotate_bwd)
