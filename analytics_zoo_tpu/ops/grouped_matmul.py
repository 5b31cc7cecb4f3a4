"""Grouped matrix product — the experts' matmul of a routed layer
(``layers/moe.py::RoutedExperts``).

``grouped_matmul(x, w, group_sizes)``: ``x`` (rows, d) holds the rows of
group 0 first, then group 1's, and so on; ``w`` (G, d, h) one matrix a
group; ``group_sizes`` (G,) int32, whose sum may be less than ``rows``. Row
``r`` of the result is ``x[r] @ w[g(r)]``; rows past the last group come
back zero, and no work is due for them.

Both the forward and the two backward products (``dx`` with the transposed
weights, ``dW_g = x_g^T dy_g``) are ``jax.lax.ragged_dot`` /
``ragged_dot_general``: on a TPU XLA lowers them to a Mosaic kernel of its
own whose tile loop is bounded by the group sizes (compiled for a v5e,
PR 28: ``ragged-dot-*`` custom calls with a dynamic iteration bound), so
work follows the rows held; elsewhere they are the dense masked reference,
which is also the oracle of the tests. The custom VJP is there so that the
backward is these two products and nothing else, and so that rows past the
groups are defined (zero) whatever a backend leaves in them.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul"]

#: dW: contract the ragged row dimension of x (rows, d) and dy (rows, h)
_DW_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _in_groups(rows: int, group_sizes):
    """(rows, 1) bool: the row belongs to some group."""
    return (jnp.arange(rows, dtype=jnp.int32)
            < jnp.sum(group_sizes))[:, None]


def _product(x, w, group_sizes, out_dtype):
    # the result leaves the product in its own dtype (the accumulation
    # inside it is float32 on the MXU either way): a float32 copy of the
    # rows would be the largest buffer of the layer
    y = jax.lax.ragged_dot(x, w, group_sizes,
                           preferred_element_type=out_dtype)
    return jnp.where(_in_groups(x.shape[0], group_sizes), y,
                     jnp.zeros((), out_dtype))


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """``(rows, d) x (G, d, h) -> (rows, h)`` by groups of rows; float32
    accumulation, the result in ``x``'s dtype."""
    return _product(x, w, group_sizes, x.dtype)


def _fwd(x, w, group_sizes):
    return _product(x, w, group_sizes, x.dtype), (x, w, group_sizes)


def _bwd(res, dy):
    x, w, group_sizes = res
    dx = _product(dy, jnp.swapaxes(w, 1, 2), group_sizes, x.dtype)
    dw = jax.lax.ragged_dot_general(
        x, dy, group_sizes, _DW_DIMS,
        preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw, None


grouped_matmul.defvjp(_fwd, _bwd)
