"""Grouped matrix product — the experts' matmul of a routed layer
(``layers/moe.py::RoutedExperts``).

``grouped_matmul(x, w, group_sizes)``: ``x`` (rows, d) holds the rows of
group 0 first, then group 1's, and so on; ``w`` (G, d, h) one matrix a
group; ``group_sizes`` (G,) int32, whose sum may be less than ``rows``. Row
``r`` of the result is ``x[r] @ w[g(r)]``; rows past the last group come
back zero, and no work is due for them. ``gated_grouped_matmul(x, wgate,
wup, group_sizes)`` is the SiLU-gated pair of an expert's first two
matrices, ``silu(x Wgate) * (x Wup)`` with each of the three rounded to
``x``'s dtype.

Which path runs where (``_impl``):

* on a TPU, for bf16 or float32 rows whose widths are whole 128-lane tiles,
  the Pallas kernels of ``ops/pallas/grouped_matmul.py`` (``zoo_moe_gmm*``):
  the forward and ``dx`` one kernel (``dx`` reads ``w`` transposed through
  its index map), ``dW`` a second, and the gated pair fused, forward (the
  rows read once, ``act`` written beside the rounded ``gate`` and ``up`` the
  backward keeps) and backward (``d_gate``, ``d_up`` formed in the ``dx``
  kernel's prologue and shared by one ``dW`` call). The kernels write the
  rows past the groups as zeros themselves. On a v5e at the decoder cell's
  shapes (32768 rows, half of them held, 8 groups, 2304 x 896, bf16; PR 31)
  the twelve products a layer's step runs take 5.7 ms in seven calls, 73 %
  of the matrix unit for the rows held.
* everywhere else (the CPU, odd widths) ``jax.lax.ragged_dot`` /
  ``ragged_dot_general`` with a mask over the rows past the groups: the
  dense masked reference off the TPU, and the oracle of the tests; on a TPU
  XLA lowers them to a Mosaic kernel of its own (``ragged-dot-*`` custom
  calls with a dynamic iteration bound), which took 18.2 ms for the same
  twelve products (1.3-1.9 ms a call, 23 %).

The custom VJP is there so that the backward is the two products and nothing
else, and so that rows past the groups are defined (zero) whatever a backend
leaves in them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas import grouped_matmul as kernels
from .pallas.common import LANES

__all__ = ["grouped_matmul", "gated_grouped_matmul", "visited_tile_rows"]

#: dW: contract the ragged row dimension of x (rows, d) and dy (rows, h)
_DW_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _impl(x, *ws) -> str:
    """``"pallas"`` where the kernels run, else ``"xla"``. (``"interpret"``,
    the kernels under the Pallas interpreter, is the tests'.)"""
    if (jax.default_backend() == "tpu"
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and all(w.dtype == x.dtype
                    and w.shape[1] % LANES == 0 and w.shape[2] % LANES == 0
                    for w in ws)):
        return "pallas"
    return "xla"


def _in_groups(rows: int, group_sizes):
    """(rows, 1) bool: the row belongs to some group."""
    return (jnp.arange(rows, dtype=jnp.int32)
            < jnp.sum(group_sizes))[:, None]


def _product(x, w, group_sizes, impl, transpose_w=False):
    """``x @ w[g]`` by groups, or ``x @ w[g]^T`` of ``w`` (G, h, d)."""
    if impl != "xla":
        return kernels.gmm(x, w, group_sizes, transpose_rhs=transpose_w,
                           interpret=impl == "interpret")
    # the result leaves the product in its own dtype (the accumulation
    # inside it is float32 on the MXU either way): a float32 copy of the
    # rows would be the largest buffer of the layer
    y = jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transpose_w else w,
                           group_sizes, preferred_element_type=x.dtype)
    return jnp.where(_in_groups(x.shape[0], group_sizes), y,
                     jnp.zeros((), x.dtype))


def _dws(x, dys, group_sizes, impl):
    """``x_g^T dy_g`` in float32 for each of ``dys``."""
    if impl != "xla":
        return kernels.gmm_dw(x, dys, group_sizes,
                              interpret=impl == "interpret")
    return tuple(jax.lax.ragged_dot_general(
        x, dy, group_sizes, _DW_DIMS, preferred_element_type=jnp.float32)
        for dy in dys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def product(x, w, group_sizes, impl):
    """``grouped_matmul`` on the path ``impl`` names."""
    return _product(x, w, group_sizes, impl)


def _product_fwd(x, w, group_sizes, impl):
    return _product(x, w, group_sizes, impl), (x, w, group_sizes)


def _product_bwd(impl, res, dy):
    x, w, group_sizes = res
    dx = _product(dy, w, group_sizes, impl, transpose_w=True)
    dw, = _dws(x, (dy,), group_sizes, impl)
    return dx, dw.astype(w.dtype), None


product.defvjp(_product_fwd, _product_bwd)


def _silu_gate(gate, up):
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gated_kernels(x, wgate, wup, group_sizes, impl):
    return kernels.gated_gmm(x, wgate, wup, group_sizes,
                             interpret=impl == "interpret")[0]


def _gated_kernels_fwd(x, wgate, wup, group_sizes, impl):
    act, gate, up = kernels.gated_gmm(x, wgate, wup, group_sizes,
                                      residuals=True,
                                      interpret=impl == "interpret")
    return act, (x, wgate, wup, group_sizes, gate, up)


def _gated_kernels_bwd(impl, res, d_act):
    x, wgate, wup, group_sizes, gate, up = res
    dx, d_gate, d_up = kernels.gated_gmm_dx(
        d_act, gate, up, wgate, wup, group_sizes,
        interpret=impl == "interpret")
    dwgate, dwup = _dws(x, (d_gate, d_up), group_sizes, impl)
    return dx, dwgate.astype(wgate.dtype), dwup.astype(wup.dtype), None


_gated_kernels.defvjp(_gated_kernels_fwd, _gated_kernels_bwd)


def gated_product(x, wgate, wup, group_sizes, impl):
    """``gated_grouped_matmul`` on the path ``impl`` names: off the kernels
    the plain composition of two products and the gate, which autodiff
    takes apart as it is."""
    if impl == "xla":
        return _silu_gate(product(x, wgate, group_sizes, impl),
                          product(x, wup, group_sizes, impl))
    return _gated_kernels(x, wgate, wup, group_sizes, impl)


def grouped_matmul(x, w, group_sizes):
    """``(rows, d) x (G, d, h) -> (rows, h)`` by groups of rows; float32
    accumulation, the result in ``x``'s dtype."""
    return product(x, w, group_sizes, _impl(x, w))


def gated_grouped_matmul(x, wgate, wup, group_sizes):
    """``silu(x Wgate) * (x Wup)`` by groups of rows, ``(rows, d) x 2 (G,
    d, h) -> (rows, h)`` in ``x``'s dtype: the two products and their
    product each rounded to it, float32 between."""
    return gated_product(x, wgate, wup, group_sizes, _impl(x, wgate, wup))


def visited_tile_rows(w, group_sizes, rows: int):
    """Rows of the row tiles the kernels visit for ``group_sizes`` in a
    buffer of ``rows`` rows of ``w``'s dtype and width (visits x tile rows:
    the rows held plus the padding at group boundaries), or 0 where the
    kernels do not run."""
    if _impl(w, w) == "xla":
        return jnp.int32(0)
    return kernels.visited_tile_rows(group_sizes, rows)
