"""Core layers — parity with the reference's Keras-1 core layer set
(``pipeline/api/keras/layers/``: Dense.scala, Dropout.scala, Flatten.scala,
Merge.scala, Reshape.scala, Permute.scala, RepeatVector.scala, ...), built as
functional JAX modules so XLA fuses the elementwise chains into the matmuls.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..engine import (
    Layer, compute_dtype, get_initializer, param_dtype, unique_name,
)

# --------------------------------------------------------------------------
# activations (keras/layers/Activation.scala registry)
# --------------------------------------------------------------------------

ACTIVATIONS: Dict[str, Callable] = {
    "relu": jax.nn.relu,
    "relu6": jax.nn.relu6,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    # Keras-1/BigDL hard_sigmoid is clip(0.2x+0.5, 0, 1) — NOT jax.nn's
    # relu6(x+3)/6 variant; the reference's RNN defaults depend on this
    "hard_sigmoid": lambda x: jnp.clip(0.2 * x + 0.5, 0.0, 1.0),
    "softmax": jax.nn.softmax,
    "log_softmax": jax.nn.log_softmax,
    "softplus": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "elu": jax.nn.elu,
    "selu": jax.nn.selu,
    "gelu": jax.nn.gelu,
    "swish": jax.nn.silu,
    "silu": jax.nn.silu,
    "linear": lambda x: x,
    "exp": jnp.exp,
}


def get_activation(act: Union[str, Callable, None]) -> Optional[Callable]:
    if act is None or callable(act):
        return act
    if act not in ACTIVATIONS:
        raise ValueError(f"unknown activation: {act}")
    return ACTIVATIONS[act]


class Activation(Layer):
    def __init__(self, activation: Union[str, Callable], **kwargs):
        super().__init__(**kwargs)
        self.activation_name = activation if isinstance(activation, str) else None
        self.fn = get_activation(activation)

    def call(self, params, x, *, training=False, rng=None):
        return self.fn(x)


class Dense(Layer):
    """Fully connected — ``keras/layers/Dense.scala``. Keras-1 signature:
    ``Dense(output_dim, init, activation, W_regularizer..., bias)``.
    Matmul accumulates in float32 on the MXU regardless of compute dtype."""

    def __init__(self, output_dim: int, init: str = "glorot_uniform",
                 activation=None, bias: bool = True, **kwargs):
        super().__init__(**kwargs)
        self.output_dim = output_dim
        self.init = init
        self.activation = get_activation(activation)
        self.bias = bias

    def build(self, rng, input_shape):
        in_dim = input_shape[-1]
        w_key, _ = jax.random.split(rng)
        params = {"W": get_initializer(self.init)(
            w_key, (in_dim, self.output_dim), param_dtype())}
        if self.bias:
            params["b"] = jnp.zeros((self.output_dim,), param_dtype())
        return params

    def param_sharding(self, params):
        """Column-parallel TP: the kernel's output dim splits over the
        ``model`` axis (Megatron-style); GSPMD propagates the resulting
        feature sharding through the activation graph."""
        from jax.sharding import PartitionSpec as P
        from .....parallel.mesh import MODEL_AXIS
        spec = {"W": P(None, MODEL_AXIS)}
        if "b" in params:
            spec["b"] = P(MODEL_AXIS)
        return spec

    def call(self, params, x, *, training=False, rng=None):
        cd = compute_dtype()
        y = jnp.matmul(x.astype(cd), params["W"].astype(cd),
                       preferred_element_type=jnp.float32)
        y = y.astype(cd)
        if self.bias:
            y = y + params["b"].astype(cd)
        if self.activation is not None:
            y = self.activation(y)
        return y

    def quantized_call(self, qp, x):
        """Static int8 path (inference runtime): activations quantize to the
        calibrated ``x_scale``, the matmul runs int8 x int8 -> int32 on the
        MXU, and one fused rescale restores float — the native replacement
        for OpenVINO's calibrated int8 FC (SURVEY §2.3)."""
        xq = jnp.clip(jnp.round(x / qp["x_scale"]), -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            xq, qp["W"], (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * (qp["x_scale"] * qp["w_scale"])
        if self.bias:
            y = y + qp["b"]
        if self.activation is not None:
            y = self.activation(y)
        return y


def gated_feed_forward(params, x, cd):
    """``(silu(x Wgate) * (x Wup)) Wdown`` on (..., d) in compute dtype
    ``cd``, products accumulated in float32. No scope of its own: the
    callers name what it is part of."""
    x = x.astype(cd)

    def mm(a, w):
        return jnp.matmul(a, w.astype(cd),
                          preferred_element_type=jnp.float32).astype(cd)
    act = jax.nn.silu(mm(x, params["Wgate"])) * mm(x, params["Wup"])
    return mm(act, params["Wdown"])


class GatedFeedForward(Layer):
    """The dense SiLU-gated feed-forward layer of today's decoders
    (SwiGLU): ``(silu(x Wgate) * (x Wup)) Wdown`` at width ``hidden_dim``,
    no biases. Input and output (..., d). A ``DecoderBlock(ffn=)`` for a
    model's dense layers; ``RoutedExperts(shared_dim=)`` holds one as its
    shared expert. Device time shows under the scope ``zoo_ffn.gated``."""

    layer_scope = False

    def __init__(self, hidden_dim: int, init: str = "glorot_uniform",
                 **kwargs):
        super().__init__(**kwargs)
        self.hidden_dim = hidden_dim
        self.init = init

    def build(self, rng, input_shape):
        d, h = input_shape[-1], self.hidden_dim
        init = get_initializer(self.init)
        k = jax.random.split(rng, 3)
        return {"Wgate": init(k[0], (d, h), param_dtype()),
                "Wup": init(k[1], (d, h), param_dtype()),
                "Wdown": init(k[2], (h, d), param_dtype())}

    def param_sharding(self, params):
        """Megatron's split: gate and up column-parallel over ``model``,
        down row-parallel."""
        from jax.sharding import PartitionSpec as P
        from .....parallel.mesh import MODEL_AXIS
        return {"Wgate": P(None, MODEL_AXIS), "Wup": P(None, MODEL_AXIS),
                "Wdown": P(MODEL_AXIS, None)}

    def call(self, params, x, *, training=False, rng=None):
        with jax.named_scope("zoo_ffn.gated"):
            return gated_feed_forward(params, x, compute_dtype())


class Dropout(Layer):
    """``keras/layers/Dropout.scala`` — inverted dropout, active only in
    training; a no-op under jit at inference so XLA removes it entirely."""

    def __init__(self, p: float, **kwargs):
        super().__init__(**kwargs)
        self.p = p

    def call(self, params, x, *, training=False, rng=None):
        if not training or self.p <= 0.0:
            return x
        if rng is None:
            raise ValueError(f"{self.name}: training dropout needs an rng")
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(rng, keep, shape=x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


class Flatten(Layer):
    """``keras/layers/Flatten.scala``."""

    def call(self, params, x, *, training=False, rng=None):
        return x.reshape(x.shape[0], -1)


class Reshape(Layer):
    """``keras/layers/Reshape.scala`` — target_shape excludes batch."""

    def __init__(self, target_shape: Tuple[int, ...], **kwargs):
        super().__init__(**kwargs)
        self.target_shape = tuple(target_shape)

    def call(self, params, x, *, training=False, rng=None):
        return x.reshape((x.shape[0],) + self.target_shape)


class Permute(Layer):
    """``keras/layers/Permute.scala`` — dims are 1-based over non-batch axes."""

    def __init__(self, dims: Tuple[int, ...], **kwargs):
        super().__init__(**kwargs)
        self.dims = tuple(dims)

    def call(self, params, x, *, training=False, rng=None):
        perm = (0,) + tuple(d for d in self.dims)
        return jnp.transpose(x, perm)


class RepeatVector(Layer):
    """``keras/layers/RepeatVector.scala`` — (B, D) -> (B, n, D)."""

    def __init__(self, n: int, **kwargs):
        super().__init__(**kwargs)
        self.n = n

    def call(self, params, x, *, training=False, rng=None):
        return jnp.repeat(x[:, None, :], self.n, axis=1)


class Merge(Layer):
    """``keras/layers/Merge.scala`` — combine a list of inputs.
    modes: sum, mul, ave, max, min, concat, dot, cos."""

    def __init__(self, mode: str = "sum", concat_axis: int = -1, **kwargs):
        super().__init__(**kwargs)
        self.mode = mode
        self.concat_axis = concat_axis

    def call(self, params, xs, *, training=False, rng=None):
        if not isinstance(xs, (list, tuple)):
            raise ValueError(f"{self.name}: Merge expects a list of inputs")
        m = self.mode
        if m == "sum":
            out = xs[0]
            for x in xs[1:]:
                out = out + x
            return out
        if m == "mul":
            out = xs[0]
            for x in xs[1:]:
                out = out * x
            return out
        if m == "ave":
            return sum(xs) / len(xs)
        if m == "max":
            out = xs[0]
            for x in xs[1:]:
                out = jnp.maximum(out, x)
            return out
        if m == "min":
            out = xs[0]
            for x in xs[1:]:
                out = jnp.minimum(out, x)
            return out
        if m == "concat":
            return jnp.concatenate(xs, axis=self.concat_axis)
        if m == "dot":
            a, b = xs
            return jnp.sum(a * b, axis=-1, keepdims=True)
        if m == "cos":
            a, b = xs
            an = a / (jnp.linalg.norm(a, axis=-1, keepdims=True) + 1e-8)
            bn = b / (jnp.linalg.norm(b, axis=-1, keepdims=True) + 1e-8)
            return jnp.sum(an * bn, axis=-1, keepdims=True)
        raise ValueError(f"unknown merge mode {m}")


def merge(inputs, mode: str = "sum", concat_axis: int = -1, name=None):
    """Functional helper mirroring pyzoo's ``merge`` (layers/topology)."""
    return Merge(mode=mode, concat_axis=concat_axis, name=name)(inputs)


class Select(Layer):
    """``keras/layers/Select.scala`` — pick index along a dim (1-based dims in
    the reference; here 0 = batch, negatives allowed)."""

    def __init__(self, dim: int, index: int, **kwargs):
        super().__init__(**kwargs)
        self.dim = dim
        self.index = index

    def call(self, params, x, *, training=False, rng=None):
        return jnp.take(x, self.index, axis=self.dim)


class Squeeze(Layer):
    def __init__(self, dim: int, **kwargs):
        super().__init__(**kwargs)
        self.dim = dim

    def call(self, params, x, *, training=False, rng=None):
        return jnp.squeeze(x, axis=self.dim)


class ExpandDim(Layer):
    def __init__(self, dim: int, **kwargs):
        super().__init__(**kwargs)
        self.dim = dim

    def call(self, params, x, *, training=False, rng=None):
        return jnp.expand_dims(x, axis=self.dim)


class Narrow(Layer):
    """``keras/layers/Narrow.scala`` — slice `length` elems from `offset`
    along `dim`."""

    def __init__(self, dim: int, offset: int, length: int = 1, **kwargs):
        super().__init__(**kwargs)
        self.dim, self.offset, self.length = dim, offset, length

    def call(self, params, x, *, training=False, rng=None):
        sl = [slice(None)] * x.ndim
        sl[self.dim] = slice(self.offset, self.offset + self.length)
        return x[tuple(sl)]


class Masking(Layer):
    """``keras/layers/Masking.scala`` — zero out timesteps equal to
    mask_value (soft masking; XLA-friendly, no ragged shapes)."""

    def __init__(self, mask_value: float = 0.0, **kwargs):
        super().__init__(**kwargs)
        self.mask_value = mask_value

    def call(self, params, x, *, training=False, rng=None):
        keep = jnp.any(x != self.mask_value, axis=-1, keepdims=True)
        return jnp.where(keep, x, 0.0)


class GaussianNoise(Layer):
    """``keras/layers/GaussianNoise.scala``."""

    def __init__(self, sigma: float, **kwargs):
        super().__init__(**kwargs)
        self.sigma = sigma

    def call(self, params, x, *, training=False, rng=None):
        if not training:
            return x
        return x + self.sigma * jax.random.normal(rng, x.shape, x.dtype)


class GaussianDropout(Layer):
    """``keras/layers/GaussianDropout.scala``."""

    def __init__(self, p: float, **kwargs):
        super().__init__(**kwargs)
        self.p = p

    def call(self, params, x, *, training=False, rng=None):
        if not training or self.p <= 0:
            return x
        stddev = (self.p / (1.0 - self.p)) ** 0.5
        return x * (1.0 + stddev * jax.random.normal(rng, x.shape, x.dtype))


class TimeDistributed(Layer):
    """``keras/layers/TimeDistributed.scala`` — apply an inner layer to every
    timestep. Implemented by folding time into batch (static reshape keeps
    XLA happy and the MXU batched), not a Python loop."""

    def __init__(self, layer: Layer, **kwargs):
        super().__init__(**kwargs)
        self.layer = layer

    def build(self, rng, input_shape):
        inner = (input_shape[0],) + tuple(input_shape[2:])
        return {self.layer.name: self.layer.build(rng, inner)}

    def initial_state(self, input_shape):
        inner = (input_shape[0],) + tuple(input_shape[2:])
        s = self.layer.initial_state(inner)
        return {self.layer.name: s} if s else {}

    def apply(self, params, state, x, *, training=False, rng=None):
        b, t = x.shape[0], x.shape[1]
        flat = x.reshape((b * t,) + x.shape[2:])
        y, ns = self.layer.apply(params[self.layer.name],
                                 state.get(self.layer.name, {}) if state else {},
                                 flat, training=training, rng=rng)
        y = y.reshape((b, t) + y.shape[1:])
        return y, ({self.layer.name: ns} if ns else state)


class Highway(Layer):
    """``keras/layers/Highway.scala`` — y = t*h + (1-t)*x."""

    def __init__(self, activation="tanh", bias: bool = True, **kwargs):
        super().__init__(**kwargs)
        self.activation = get_activation(activation)
        self.bias = bias

    def build(self, rng, input_shape):
        d = input_shape[-1]
        k1, k2 = jax.random.split(rng)
        init = get_initializer("glorot_uniform")
        p = {"W": init(k1, (d, d), param_dtype()),
             "W_t": init(k2, (d, d), param_dtype())}
        if self.bias:
            p["b"] = jnp.zeros((d,), param_dtype())
            # negative transform-gate bias: start as identity (standard highway init)
            p["b_t"] = jnp.full((d,), -2.0, param_dtype())
        return p

    def call(self, params, x, *, training=False, rng=None):
        t = x @ params["W_t"]
        h = x @ params["W"]
        if self.bias:
            t = t + params["b_t"]
            h = h + params["b"]
        t = jax.nn.sigmoid(t)
        if self.activation is not None:
            h = self.activation(h)
        return t * h + (1.0 - t) * x


class SparseDense(Layer):
    """``keras/layers/SparseDense.scala`` — dense layer accepting one-hot /
    multi-hot sparse rows. TPU-native: the "sparse" input is a dense 0/1
    matrix; XLA maps the matmul onto the MXU which beats gather-scatter."""

    def __init__(self, output_dim: int, init="glorot_uniform", activation=None,
                 bias: bool = True, **kwargs):
        super().__init__(**kwargs)
        self._dense = None
        self.output_dim, self.init = output_dim, init
        self.activation, self.bias = activation, bias

    def build(self, rng, input_shape):
        self._dense = Dense(self.output_dim, init=self.init,
                            activation=self.activation, bias=self.bias,
                            name=self.name + "_d")
        return self._dense.build(rng, input_shape)

    def call(self, params, x, *, training=False, rng=None):
        return self._dense.call(params, x, training=training, rng=rng)
