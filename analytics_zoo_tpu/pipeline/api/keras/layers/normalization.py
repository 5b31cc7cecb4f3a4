"""Normalization layers — parity with ``keras/layers/BatchNormalization.scala``
and ``keras/layers/LayerNorm.scala``.

BatchNorm carries its moving statistics as non-trainable *state* threaded
functionally through ``apply`` (no mutation — jit/shard safe). Under data
parallelism the batch-axis reduction runs *inside* the sharded program, so
XLA's SPMD partitioner turns it into a global (all-reduced) mean/var — i.e.
sync-BatchNorm: statistics are identical for dp=1 and dp=N (asserted by
``tests/test_layers.py::test_batchnorm_dp_invariant``). This is a deliberate
improvement over the reference, whose per-replica modules keep local stats
(``Topology.scala:1150-1158``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..engine import Layer, param_dtype


class BatchNormalization(Layer):
    """``BatchNormalization(epsilon, momentum, beta_init, gamma_init,
    dim_ordering)`` — normalizes the channel axis (last axis here; the
    reference's default NCHW maps to NHWC on TPU, where channels-last is the
    layout XLA tiles best)."""

    def __init__(self, epsilon: float = 1e-3, momentum: float = 0.99,
                 axis: int = -1, scale: bool = True, center: bool = True, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon
        self.momentum = momentum
        self.axis = axis
        self.scale = scale
        self.center = center

    def _dim(self, input_shape):
        return input_shape[self.axis]

    def build(self, rng, input_shape):
        d = self._dim(input_shape)
        p = {}
        if self.scale:
            p["gamma"] = jnp.ones((d,), param_dtype())
        if self.center:
            p["beta"] = jnp.zeros((d,), param_dtype())
        return p

    def initial_state(self, input_shape):
        d = self._dim(input_shape)
        return {
            "moving_mean": jnp.zeros((d,), jnp.float32),
            "moving_var": jnp.ones((d,), jnp.float32),
        }

    def apply(self, params, state, x, *, training=False, rng=None):
        axis = x.ndim + self.axis if self.axis < 0 else self.axis
        reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
        # broadcast (d,)-shaped stats/params against the normalized axis, not
        # blindly against the last axis — axis=1 on (B, C, L) must work
        bshape = tuple(x.shape[axis] if i == axis else 1 for i in range(x.ndim))
        if training:
            mean = jnp.mean(x.astype(jnp.float32), axis=reduce_axes)
            var = jnp.var(x.astype(jnp.float32), axis=reduce_axes)
            m = self.momentum
            new_state = {
                "moving_mean": m * state["moving_mean"] + (1 - m) * mean,
                "moving_var": m * state["moving_var"] + (1 - m) * var,
            }
        else:
            mean, var = state["moving_mean"], state["moving_var"]
            new_state = state
        inv = jax.lax.rsqrt(var + self.epsilon)
        y = (x - mean.astype(x.dtype).reshape(bshape)) \
            * inv.astype(x.dtype).reshape(bshape)
        if self.scale:
            y = y * params["gamma"].astype(x.dtype).reshape(bshape)
        if self.center:
            y = y + params["beta"].astype(x.dtype).reshape(bshape)
        return y, new_state


class LayerNorm(Layer):
    """``keras/layers/LayerNorm.scala`` — normalize over the last axis."""

    def __init__(self, epsilon: float = 1e-5, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon

    def build(self, rng, input_shape):
        d = input_shape[-1]
        return {"gamma": jnp.ones((d,), param_dtype()),
                "beta": jnp.zeros((d,), param_dtype())}

    def call(self, params, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * params["gamma"] + params["beta"]
        return y.astype(x.dtype)


class RMSNorm(Layer):
    """Root-mean-square normalization over the last axis (Zhang & Sennrich
    2019): ``x / sqrt(mean(x^2) + epsilon) * gamma``, no mean subtracted and
    no bias. The statistics are taken in float32 whatever the compute
    dtype."""

    def __init__(self, epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon

    def build(self, rng, input_shape):
        return {"gamma": jnp.ones((input_shape[-1],), param_dtype())}

    def call(self, params, x, *, training=False, rng=None):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.epsilon)
        return (y * params["gamma"]).astype(x.dtype)


class L2Normalize(Layer):
    """autograd ``l2Normalize`` as a layer (``autograd/math.scala``)."""

    def __init__(self, axis: int = -1, epsilon: float = 1e-12, **kwargs):
        super().__init__(**kwargs)
        self.axis, self.epsilon = axis, epsilon

    def call(self, params, x, *, training=False, rng=None):
        norm = jnp.sqrt(jnp.sum(x * x, axis=self.axis, keepdims=True) + self.epsilon)
        return x / norm
