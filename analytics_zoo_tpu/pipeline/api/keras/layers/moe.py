"""Mixture-of-experts layers. The reference has none (`pipeline/api/keras/
layers/` contains no MoE; SURVEY §2.4: EP is "absent in the reference;
greenfield"), so both are designed TPU-first. Which one to take:

* ``SparseMoE`` — the layer that makes the ``expert`` mesh axis real: the
  GShard einsum formulation, capacity-bounded token dispatch expressed as
  one-hot matmuls. Every shape is static and the FLOPs sit on the MXU; the
  expert-stacked weights ``(E, d_in, d_h)`` shard over the ``expert`` axis
  (their hidden dim additionally over ``model``), so GSPMD inserts the
  dispatch/combine all-to-alls over ICI. Tokens over an expert's capacity
  are dropped, and the ``(k, N, E, C)`` assignment tensor grows with
  tokens x experts x capacity: a layer for tens of experts and thousands of
  tokens a step, across chips.
* ``RoutedExperts`` — the dropless, share-aware layer for published expert
  counts (64 routed, top-8, 32k tokens a step): the router keeps its
  published width, the layer is told which experts it ``held``s (one chip's
  share of an expert-parallel deployment; default all), the N x k
  assignments are sorted by expert, rows gathered in that order and run
  through ``ops/grouped_matmul`` over the held groups' sizes: no capacity,
  no dropped assignment, work proportional to the assignments held. What
  the absent experts would have added is left out. SiLU-gated experts.

Both define routing in one place, ``top_k_routing`` (scores, top-k,
renormalise): softmax scores, or sigmoid scores with a selection bias and a
scale on the routed sum (the aux-loss-free routers of 2025's open models).
``RoutedExperts`` can carry a shared expert every token passes through
(``GatedFeedForward``, added to the routed sum once, whatever ``held`` is).

Auxiliary losses (``SparseMoE``: load-balance + router z-loss) ride the
layer-state channel: ``apply`` returns them under the reserved state key
``aux_loss``, which the training loop adds to the task loss *inside* the
differentiated function — see ``training.py`` ``_aux_loss_sum`` — so the
router receives gradient. ``RoutedExperts`` leaves its per-expert token
counts in the same channel (``moe_*`` keys), accumulated on the device and
read once per ``fit`` (:func:`fit_report`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.grouped_matmul import (
    gated_grouped_matmul, grouped_matmul, visited_tile_rows)
from ..engine import Layer, compute_dtype, get_initializer, param_dtype
from .core import GatedFeedForward, gated_feed_forward, get_activation


def top_k_routing(logits, k: int, renormalize: bool = True,
                  scoring: str = "softmax", bias=None, scale: float = 1.0):
    """The one definition of token-choice routing: float32 scores over
    every router output (``scoring``: ``"softmax"``, or ``"sigmoid"``, each
    output scored on its own), the ``k`` largest and their experts, the
    chosen weights renormalised to sum to 1 (``norm_topk_prob``) and
    multiplied by ``scale`` (``routed_scaling_factor``). ``bias`` (E,), if
    given, enters the CHOICE and not the weight: the chosen are the top-k
    of ``scores + bias`` and their weights the scores without it (the
    ``e_score_correction_bias`` of routers balanced without an auxiliary
    loss; it takes no gradient). Returns ``(scores (N, E), weights (N, k),
    experts (N, k) int32)``."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring={scoring!r}: softmax or sigmoid")
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        weights = weights * scale
    return scores, weights, experts


class SparseMoE(Layer):
    """Token-choice top-k sparse MoE with expert capacity.

    Each token's router picks its ``top_k`` experts out of ``num_experts``;
    every expert processes at most ``capacity`` tokens per batch
    (``capacity = ceil(top_k * n_tokens / num_experts) * capacity_factor``),
    overflow tokens are dropped (contribute zero — pair with a residual
    connection, as in Switch/GShard). Input ``(B, d)`` or ``(B, T, d)``;
    output has ``output_dim`` features (default: same as input).

    The load-balance loss is the Switch-Transformer form
    ``E * dot(frac_tokens_per_expert, mean_router_prob)`` scaled by
    ``aux_loss_weight``; ``router_z_weight`` optionally adds the ST-MoE
    z-loss ``mean(logsumexp(logits)^2)`` to keep router logits small.
    """

    def __init__(self, num_experts: int, hidden_dim: int,
                 output_dim: Optional[int] = None, top_k: int = 2,
                 capacity_factor: float = 1.25, activation="relu",
                 aux_loss_weight: float = 1e-2, router_z_weight: float = 0.0,
                 router_noise: float = 0.0, init: str = "glorot_uniform",
                 **kwargs):
        super().__init__(**kwargs)
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k={top_k} not in [1, {num_experts}]")
        self.num_experts = num_experts
        self.hidden_dim = hidden_dim
        self.output_dim = output_dim
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = get_activation(activation)
        self.aux_loss_weight = aux_loss_weight
        self.router_z_weight = router_z_weight
        self.router_noise = router_noise
        self.init = init

    def build(self, rng, input_shape):
        d = input_shape[-1]
        out = self.output_dim or d
        E, h = self.num_experts, self.hidden_dim
        init = get_initializer(self.init)
        k = jax.random.split(rng, 3)
        return {
            # router kept in the param dtype; routing math runs in f32
            "Wg": init(k[0], (d, E), param_dtype()),
            "W1": init(k[1], (E, d, h), param_dtype()),
            "b1": jnp.zeros((E, h), param_dtype()),
            "W2": init(k[2], (E, h, out), param_dtype()),
            "b2": jnp.zeros((E, out), param_dtype()),
        }

    def initial_state(self, input_shape):
        return {"aux_loss": jnp.zeros((), jnp.float32)}

    def param_sharding(self, params):
        """Expert-stacked weights shard over the ``expert`` axis; their
        hidden dim additionally over ``model`` (EP x TP). The router stays
        replicated — every token needs all expert scores."""
        from jax.sharding import PartitionSpec as P
        from .....parallel.mesh import EXPERT_AXIS, MODEL_AXIS
        return {
            "Wg": None,
            "W1": P(EXPERT_AXIS, None, MODEL_AXIS),
            "b1": P(EXPERT_AXIS, MODEL_AXIS),
            "W2": P(EXPERT_AXIS, MODEL_AXIS, None),
            "b2": P(EXPERT_AXIS, None),
        }

    # -- routing ------------------------------------------------------------
    def _route(self, logits):
        """Top-k gates + capacity-bounded positions, all static shapes.

        Returns ``(dispatch, combine, aux)``: dispatch ``(N, E, C)`` is the
        0/1 token->(expert, slot) assignment, combine is dispatch weighted by
        the renormalized gate values."""
        N, E = logits.shape
        k = self.top_k
        cap = max(1, int(-(-k * N // E) * self.capacity_factor))
        cap = min(cap, N)

        probs, gate_vals, idx = top_k_routing(logits, k)     # (N, E), (N, k)

        # (k, N, E) one-hot choices; choice rank 0 has dispatch priority —
        # positions count choice-0 tokens before any choice-1 token, so a
        # token's primary expert is the last to drop it under overflow
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32).transpose(1, 0, 2)
        flat = mask.reshape(k * N, E)
        pos_flat = jnp.cumsum(flat, axis=0) - flat           # (k*N, E)
        pos = (pos_flat.reshape(k, N, E) * mask).sum(-1).astype(jnp.int32)
        kept = mask * (pos_flat < cap).reshape(k, N, E)      # (k, N, E)

        slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)   # (k, N, C)
        assign = kept[..., None] * slot[:, :, None, :]       # (k, N, E, C)
        dispatch = assign.sum(0)                             # (N, E, C)
        combine = (assign * gate_vals.T[..., None, None]).sum(0)

        # Switch load-balance loss on the primary choice + optional z-loss
        frac_tokens = mask[0].mean(0)                        # (E,)
        frac_probs = probs.mean(0)
        aux = self.aux_loss_weight * E * jnp.dot(frac_tokens, frac_probs)
        if self.router_z_weight:
            z = jax.scipy.special.logsumexp(logits, axis=-1)
            aux = aux + self.router_z_weight * jnp.mean(z * z)
        return dispatch, combine, aux.astype(jnp.float32)

    def _expert_constraint(self, a, spec):
        """Pin the per-expert tensors to the ``expert`` axis when one exists,
        forcing GSPMD to place the dispatch/combine all-to-all here."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .....parallel.mesh import EXPERT_AXIS, global_mesh
        mesh = global_mesh()
        if (mesh.shape[EXPERT_AXIS] > 1
                and self.num_experts % mesh.shape[EXPERT_AXIS] == 0):
            return jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P(EXPERT_AXIS, *spec)))
        return a

    def apply(self, params, state, x, *, training=False, rng=None):
        cd = compute_dtype()
        lead = x.shape[:-1]
        d = x.shape[-1]
        tokens = x.reshape(-1, d)
        N = tokens.shape[0]

        logits = jnp.matmul(tokens.astype(jnp.float32),
                            params["Wg"].astype(jnp.float32))
        if training and self.router_noise > 0.0:
            if rng is None:
                raise ValueError(f"{self.name}: router noise needs an rng")
            logits = logits * jax.random.uniform(
                rng, logits.shape, minval=1.0 - self.router_noise,
                maxval=1.0 + self.router_noise)
        dispatch, combine, aux = self._route(logits)

        xin = jnp.einsum("nec,nd->ecd", dispatch.astype(cd),
                         tokens.astype(cd),
                         preferred_element_type=jnp.float32).astype(cd)
        xin = self._expert_constraint(xin, (None, None))
        h = jnp.einsum("ecd,edh->ech", xin, params["W1"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        h = self.activation(h + params["b1"].astype(cd)[:, None, :])
        out = jnp.einsum("ech,eho->eco", h, params["W2"].astype(cd),
                         preferred_element_type=jnp.float32).astype(cd)
        out = out + params["b2"].astype(cd)[:, None, :]
        out = self._expert_constraint(out, (None, None))
        y = jnp.einsum("nec,eco->no", combine.astype(cd), out,
                       preferred_element_type=jnp.float32).astype(cd)
        return y.reshape(*lead, y.shape[-1]), {"aux_loss": aux}

    def call(self, params, x, *, training=False, rng=None):
        y, _ = self.apply(params, {}, x, training=training, rng=rng)
        return y


# ---------------------------------------------------------------------------
# the dropless, share-aware layer
# ---------------------------------------------------------------------------

#: the wide counters of the layer state are (hi, lo) int32 pairs, base 2**30
#: (x64 is off): a step adds N x k < 2**30, a fit may add more than 2**31
_WIDE_BITS = 30


#: what a run of the layer counts -> the wide counter of the layer state
WIDE_COUNTERS = {"held": "moe_held", "absent": "moe_absent",
                 "dropped": "moe_dropped", "rows_run": "moe_rows_run",
                 "choice_passes": "moe_choice_passes",
                 "chunk_runs": "moe_chunk_runs",
                 "compact_runs": "moe_compact_runs",
                 "gmm_tile_rows": "moe_gmm_tile_rows"}


def _wide_add(acc, n):
    lo = acc[1] + jnp.asarray(n, jnp.int32)
    return jnp.stack([acc[0] + (lo >> _WIDE_BITS),
                      lo & ((1 << _WIDE_BITS) - 1)])


def wide_value(pair) -> int:
    """The Python int a ``(hi, lo)`` counter of the layer state holds."""
    return (int(pair[0]) << _WIDE_BITS) + int(pair[1])


#: the row buffers are a whole number of these rows (a tile of the grouped
#: products)
_ROW_TILE = 128


def _take(rows, idx, whole=True):
    """Rows by index. A ``whole`` buffer holds every assignment, and every
    index here comes from a permutation of them, so none is out of bounds.
    A buffer cut to the rows held (``_held_rows``) reads zero past its end:
    the rows that would stand there are absent experts', which are zero in
    the whole buffer too."""
    if whole:
        return rows.at[idx].get(mode="promise_in_bounds")
    return rows.at[idx].get(mode="fill", fill_value=0)


def _loop_passes(k):
    """The most passes for which a gather-sum is a loop over them. A loop
    keeps its float32 sum in memory between passes, which each pass reads
    and writes beside the gathered rows; all ``k`` choices summed at once
    read each gathered buffer once. Measured at 16384 tokens x 8 choices
    of 2304 on a v5e (PR 29): all 8 at once 5.9 ms, the loop 0.6 ms and
    0.97 ms a pass, so the loop is the cheaper up to 5 passes of 8, three
    quarters of ``k`` less a half."""
    return (3 * k - 2) // 4


def _passes_run(passes, k):
    """The passes a gather-sum runs where ``passes`` are due: those, or all
    ``k`` past ``_loop_passes(k)``."""
    return jnp.where(passes <= _loop_passes(k), passes, k)


def _choice_rows(scope, rows, pos):
    """``idx -> rows[idx]`` under ``scope``, for the indices of one choice of
    every token (``pos`` says how many assignments there are in all)."""
    whole = rows.shape[0] >= pos.size

    def take(idx):
        with jax.named_scope(scope):
            return _take(rows, idx, whole)
    return take


def _sum_over_choices(scope, rows, pos, passes, weights=None):
    """``sum_{j < passes} [weights[n, j]] * rows[pos[n, j]]``, summed in
    float32, the result in ``rows``' dtype. The choices from ``passes`` on
    are absent experts' in every token (``RoutedExperts._run`` orders them
    so) and add nothing. ``passes`` None is all ``k``, in plain code; else
    a traced count, and the sum a ``while`` loop over the passes due where
    they are few, all ``k`` in one fused sum where they are not. Each opens
    its ``zoo_moe.*`` scope inside, on the operations: a conditional or a
    loop shows in a device trace as a whole event around what it runs, and
    a scope around it would count that time twice
    (``benchmark/lib/scopes.py``)."""
    n_tok, k = pos.shape
    take = _choice_rows(scope, rows, pos)

    def term(picked, j, weights_t):
        with jax.named_scope(scope):
            picked = picked.astype(jnp.float32)
            return (picked if weights_t is None
                    else picked * weights_t[j][:, None])

    def few(passes, pos_t, weights_t):
        with jax.named_scope(scope):
            total = jnp.zeros((n_tok, rows.shape[1]), jnp.float32)
        total = jax.lax.fori_loop(
            0, passes,
            lambda j, total: total + term(take(pos_t[j]), j, weights_t), total)
        with jax.named_scope(scope):
            return total.astype(rows.dtype)

    def every(looped, pos_t, weights_t):
        # XLA keeps a gather an operation of its own either way; looped,
        # the program holds one of them
        picked = (jax.lax.map(take, pos_t) if looped
                  else [take(idx) for idx in pos_t])
        total = sum(term(picked[j], j, weights_t) for j in range(k))
        with jax.named_scope(scope):
            return total.astype(rows.dtype)

    by_choice = (pos.T, None if weights is None else weights.T)     # (k, N)
    if passes is None:
        return every(False, *by_choice)
    return jax.lax.cond(passes <= _loop_passes(k),
                        functools.partial(few, passes),
                        functools.partial(every, True), *by_choice)


@jax.custom_vjp
def _dispatch(tokens, order, pos, passes):
    """``rows[r] = tokens[order[r] // k]``: token rows in sorted-assignment
    order. ``order`` (R,) maps a sorted row to its assignment ``n*k + j``
    (the first R of them), ``pos`` (N, k) is the whole order's inverse. The
    transpose of a gather is a scatter-add; because ``order`` is a
    permutation it is also the gather ``d tokens[n] = sum_j d rows[pos[n,
    j]]``, which is what the backward runs, over the first ``passes``
    choices."""
    with jax.named_scope("zoo_moe.dispatch"):
        return _take(tokens, order // pos.shape[1])


def _dispatch_fwd(tokens, order, pos, passes):
    return _dispatch(tokens, order, pos, passes), (pos, passes)


def _dispatch_bwd(res, d_rows):
    pos, passes = res
    return (_sum_over_choices("zoo_moe.dispatch", d_rows, pos, passes),
            None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, order, pos, passes):
    """``out[n] = sum_{j < passes} weights[n, j] * rows[pos[n, j]]`` in
    float32, the result in ``rows``' dtype; the backward again gathers and
    never scatters."""
    return _sum_over_choices("zoo_moe.combine", rows, pos, passes, weights)


def _combine_fwd(rows, weights, order, pos, passes):
    return (_combine(rows, weights, order, pos, passes),
            (rows, weights, order, pos, passes))


def _combine_bwd(res, d_out):
    rows, weights, order, pos, passes = res
    k = pos.shape[1]
    with jax.named_scope("zoo_moe.combine"):
        d_rows = (_take(d_out, order // k).astype(jnp.float32)
                  * _take(weights.reshape(-1), order)[:, None]
                  ).astype(rows.dtype)
    take = _choice_rows("zoo_moe.combine", rows, pos)

    def one_pass(j, by_choice):
        # a choice no token holds is not passed over: its weight is 0 and
        # takes no gradient
        picked = take(pos.T[j])
        with jax.named_scope("zoo_moe.combine"):
            return by_choice.at[j].set(jnp.sum(
                picked.astype(jnp.float32) * d_out.astype(jnp.float32),
                axis=-1))
    with jax.named_scope("zoo_moe.combine"):
        by_choice = jnp.zeros((k, pos.shape[0]), jnp.float32)
    if passes is None:
        for j in range(k):
            by_choice = one_pass(j, by_choice)
    else:       # the sums kept between passes are small: a loop whatever
        by_choice = jax.lax.fori_loop(0, passes, one_pass, by_choice)
    d_weights = by_choice.T
    return d_rows, d_weights, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.jit, static_argnums=(0,))
def _held_rows(n_rows, tokens, wgate, wup, wdown, weights, order, pos, sizes,
               passes):
    """The layer's row side over the first ``n_rows`` (static) sorted
    assignments, which have to include every held one: token rows gathered
    in that order, the SiLU-gated experts' three grouped products, the
    weighted sum back to tokens. Every buffer here holds ``n_rows`` rows.
    Under ``jit`` so that a model's layers, which call it with the same
    shapes forward and backward, trace and lower it once a shape."""
    order = order[:n_rows]
    rows = _dispatch(tokens, order, pos, passes)
    with jax.named_scope("zoo_moe.experts"):
        act = gated_grouped_matmul(rows, wgate, wup, sizes)
        rows = grouped_matmul(act, wdown, sizes)
    return _combine(rows, weights, order, pos, passes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows_or_all(compact, tokens, wgate, wup, wdown, weights, order,
                      pos, sizes, passes):
    """``_held_rows`` over ``compact`` rows where the held assignments fit
    in them, over all ``N x k`` where they do not: the same result either
    way. ``grad`` of a plain ``lax.cond`` would hand the backward both
    branches' residuals, the untaken one's as zeros (row buffers again);
    this saves the inputs, and the backward is a ``cond`` over ``jax.vjp``
    of the two, which recomputes the forward it differentiates (as the
    ``jax.checkpoint`` around a chunk does anyway)."""
    return jax.lax.cond(
        jnp.sum(sizes) <= compact, functools.partial(_held_rows, compact),
        functools.partial(_held_rows, pos.size),
        tokens, wgate, wup, wdown, weights, order, pos, sizes, passes)


def _held_rows_or_all_fwd(compact, *args):
    return _held_rows_or_all(compact, *args), args


def _held_rows_or_all_bwd(compact, args, d_y):
    diff, rest = args[:5], args[5:]          # ..., weights | order, ...
    _, pos, sizes, _ = rest

    def back(n_rows, diff, rest, d_y):
        return jax.vjp(lambda *d: _held_rows(n_rows, *d, *rest), *diff)[1](d_y)
    grads = jax.lax.cond(
        jnp.sum(sizes) <= compact, functools.partial(back, compact),
        functools.partial(back, pos.size), diff, rest, d_y)
    return (*grads, None, None, None, None)


_held_rows_or_all.defvjp(_held_rows_or_all_fwd, _held_rows_or_all_bwd)


class RoutedExperts(Layer):
    """Token-choice top-k experts without capacity and without dropped
    assignments, for one chip's share of the experts.

    ``num_experts`` is the published router width; ``held`` the expert ids
    this layer computes (a range or list; default all). Routing runs over
    all ``num_experts`` (``top_k_routing``: ``scoring`` softmax or sigmoid,
    ``norm_topk``, ``routed_scale`` on the chosen weights;
    ``selection_bias`` adds a per-expert bias to the CHOICE alone, kept in
    the layer state as ``moe_select_bias`` so that no optimizer touches
    it: ``num_experts`` values to start it at (zeros are the published
    start), ``None`` for a router without one; a step passes it through
    unchanged, and whoever balances the router sets it). ``shared_dim`` adds a shared
    expert of that width (``GatedFeedForward`` under the parameter key
    ``shared``, scope ``zoo_moe.shared``) that every token passes through:
    it is added to the routed sum ONCE, whatever ``held`` is, so of the
    layers that share the experts one carries it. The N x k assignments are
    sorted by expert with the absent experts' after the held ones, the rows
    gathered in that order, and the three grouped products of the
    SiLU-gated experts (``silu(x Wgate) * (x Wup)) Wdown``) run over the
    held groups' sizes, so tiles past the last held row do no work. What
    the absent experts would have added is left out: the layer returns the
    held experts' part of ``sum_e w_e expert_e(x)``, and the parts of a set
    of layers whose ``held`` partition the experts add up to the whole
    (``tests/test_routed_experts.py``). Input ``(B, d)`` or ``(B, T, d)``.

    Shapes are static and the work follows the routing all the same. The
    sorted index is N x k int32. Every pass over rows or over a token's
    choices is bounded by what the routing of these tokens decided, in two
    places, both exact:

    * **choices.** A token's ``k`` choices are ordered held-first, so the
      gather-sums (the combine, the backward of the row gather, the
      weights' gradient) need the first ``passes`` only: the most held
      choices any token has. The later ones are absent experts' in every
      token: weight 0, zero rows. Where the passes due are few the sum is
      a loop over them; past ``_loop_passes(k)`` (5 of 8) the loop's
      float32 sum, kept in memory between passes, would cost more than
      all ``k`` choices summed at once, and those run.
    * **rows.** Sorted assignments put the held ones first, so the row
      buffers (the gathered rows, the products' operands and results, the
      gate) hold the first ``C`` rows and not ``N x k``, where ``C`` is
      twice the expected share ``N k len(held) / num_experts`` (a balanced
      router's share with as much room again: a router drifts, and a
      buffer twice the rows held still costs a quarter of the worst case
      at a share of an eighth), rounded up to a tile of the products and
      capped at ``N k``. A chunk that holds more than ``C`` assignments
      runs the same program over all ``N x k`` rows (a ``lax.cond`` on
      the held count), which costs what every chunk cost before the
      buffers were cut: nothing is dropped either way.

    A layer that holds every expert has nothing to bound and lowers with
    no conditional. The layer keeps no capacity to size buffers by;
    ``token_chunk`` bounds them besides: N tokens are routed and run
    ``token_chunk`` at a time, one chunk after the other (``lax.map``, each
    chunk rematerialised in the backward pass), so ``N`` above is
    ``token_chunk``. Routing is per token, so the result is the same; a
    token count that is not a whole number of chunks runs whole.

    Layer state (accumulated on the device, published per ``fit``):
    ``moe_expert_tokens`` (num_experts,) the last step's assignments per
    router output, ``moe_held_tokens`` (len(held),) those of the held
    experts in ``held``'s order, and the wide counters (``wide_value``)
    ``moe_held`` / ``moe_absent`` / ``moe_dropped``: assignments computed
    here, left to absent experts, and placed nowhere (0 by construction);
    ``moe_rows_run``: rows the row buffers held (``C`` or ``N k`` a chunk),
    ``moe_choice_passes``: gather-sum passes run, summed over chunks,
    ``moe_chunk_runs`` and ``moe_compact_runs``: chunks run, and those of
    them that ran over ``C`` rows, ``moe_gmm_tile_rows``: rows of the row
    tiles the grouped products' kernels visited (0 where XLA's run)."""

    layer_scope = False

    def __init__(self, num_experts: int, hidden_dim: int, top_k: int = 2,
                 held=None, norm_topk: bool = True,
                 token_chunk: Optional[int] = None,
                 init: str = "glorot_uniform", scoring: str = "softmax",
                 selection_bias=None, routed_scale: float = 1.0,
                 shared_dim: Optional[int] = None, **kwargs):
        super().__init__(**kwargs)
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k={top_k} not in [1, {num_experts}]")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={scoring!r}: softmax or sigmoid")
        held = tuple(range(num_experts) if held is None
                     else (int(e) for e in held))
        if (not held or len(set(held)) != len(held)
                or not all(0 <= e < num_experts for e in held)):
            raise ValueError(f"held={held!r}: distinct expert ids in "
                             f"[0, {num_experts}) expected")
        self.num_experts = num_experts
        self.hidden_dim = hidden_dim
        self.top_k = top_k
        self.held = held
        self.norm_topk = norm_topk
        self.token_chunk = token_chunk
        self.init = init
        self.scoring = scoring
        self.selection_bias = (None if selection_bias is None else
                               np.asarray(selection_bias, np.float32))
        if (self.selection_bias is not None
                and self.selection_bias.shape != (num_experts,)):
            raise ValueError(f"selection_bias: ({num_experts},) values "
                             f"expected")
        self.routed_scale = float(routed_scale)
        self.shared = (GatedFeedForward(shared_dim, init=init,
                                        name=f"{self.name}_shared")
                       if shared_dim else None)
        # an assignment's sort key by expert: the expert's place among the
        # held ones, or len(held) for every absent expert
        self._place = [len(held)] * num_experts
        for i, e in enumerate(held):
            self._place[e] = i

    def build(self, rng, input_shape):
        d, h, n = input_shape[-1], self.hidden_dim, len(self.held)
        init = get_initializer(self.init)
        k = jax.random.split(rng, 4)
        p = {"Wg": init(k[0], (d, self.num_experts), param_dtype()),
             "Wgate": init(k[1], (n, d, h), param_dtype()),
             "Wup": init(k[2], (n, d, h), param_dtype()),
             "Wdown": init(k[3], (n, h, d), param_dtype())}
        if self.shared is not None:
            p["shared"] = self.shared.build(jax.random.fold_in(rng, 4),
                                             input_shape)
        return p

    def initial_state(self, input_shape=None):
        pair = jnp.zeros((2,), jnp.int32)
        state = {"moe_expert_tokens": jnp.zeros((self.num_experts,),
                                                jnp.int32),
                 "moe_held_tokens": jnp.zeros((len(self.held),), jnp.int32),
                 **{key: pair for key in WIDE_COUNTERS.values()}}
        if self.selection_bias is not None:
            state["moe_select_bias"] = jnp.asarray(self.selection_bias)
        return state

    def apply(self, params, state, x, *, training=False, rng=None):
        from .....parallel.mesh import EXPERT_AXIS, global_mesh
        if global_mesh().shape[EXPERT_AXIS] > 1:
            raise NotImplementedError(
                f"{self.name}: RoutedExperts is one chip's share of the "
                f"experts (held=...); it has no exchange over an `expert` "
                f"mesh axis. SparseMoE shards over that axis.")
        state = {**self.initial_state(), **(state or {})}
        lead, d = x.shape[:-1], x.shape[-1]
        tokens = x.reshape(-1, d).astype(compute_dtype())
        n_tok, chunk = tokens.shape[0], self.token_chunk
        bias = (state["moe_select_bias"] if self.selection_bias is not None
                else None)
        if chunk and n_tok > chunk and n_tok % chunk == 0:
            run = jax.checkpoint(self._run)
            y, sizes, per_expert, ran = jax.lax.map(
                lambda t: run(params, t, bias), tokens.reshape(-1, chunk, d))
            y = y.reshape(n_tok, d)
            sizes, per_expert = sizes.sum(0), per_expert.sum(0)
            ran = {key: n.sum(0) for key, n in ran.items()}
            ran["chunk_runs"] = n_tok // chunk
        else:
            y, sizes, per_expert, ran = self._run(params, tokens, bias)
            ran["chunk_runs"] = 1
        if self.shared is not None:
            with jax.named_scope("zoo_moe.shared"):
                y = y + gated_feed_forward(params["shared"], tokens,
                                           tokens.dtype)

        held = jnp.sum(sizes)
        placed = jnp.sum(per_expert)
        ran.update(held=held, absent=placed - held,
                   dropped=n_tok * self.top_k - placed)
        new_state = {"moe_expert_tokens": per_expert,
                     "moe_held_tokens": sizes}
        if bias is not None:
            new_state["moe_select_bias"] = bias
        for name, key in WIDE_COUNTERS.items():
            new_state[key] = _wide_add(state[key], ran[name])
        return y.reshape(*lead, d), new_state

    def compact_rows(self, n_tok: int) -> int:
        """``C``: the rows the row buffers hold when ``n_tok`` tokens are
        routed at once and the held assignments fit: twice the expected
        share, a whole number of tiles, at most ``n_tok x top_k``."""
        n_rows = n_tok * self.top_k
        twice = -(-2 * n_rows * len(self.held) // self.num_experts)
        return min(-(-twice // _ROW_TILE) * _ROW_TILE, n_rows)

    def _run(self, params, tokens, bias=None):
        """Route ``tokens`` (n, d) and run the held experts on them: ``(y
        (n, d), held group sizes, assignments per router output, {rows_run,
        choice_passes, compact_runs, gmm_tile_rows} of this run)``."""
        cd = tokens.dtype
        n_tok, k, n_held = tokens.shape[0], self.top_k, len(self.held)
        n_rows, compact = n_tok * k, self.compact_rows(n_tok)

        with jax.named_scope("zoo_moe.route"):
            logits = jnp.matmul(tokens, params["Wg"].astype(cd),
                                preferred_element_type=jnp.float32)
            _, weights, experts = top_k_routing(
                logits, k, self.norm_topk, scoring=self.scoring, bias=bias,
                scale=self.routed_scale)
            key = jnp.take(jnp.asarray(self._place, jnp.int32),
                           experts)                             # (N, k)
            passes = None
            if n_held < self.num_experts:
                # each token's held choices first, in their order (a stable
                # partition); autodiff carries it back to the router
                here = key < n_held
                before = jnp.cumsum(here, axis=1, dtype=jnp.int32)
                slot = jnp.where(here, before - 1,
                                 before[:, -1:] + jnp.arange(k) - before)
                move = slot[:, :, None] == jnp.arange(k)   # (N, from, to)
                key = jnp.sum(jnp.where(move, key[:, :, None], 0), axis=1)
                weights = jnp.sum(jnp.where(move, weights[:, :, None], 0.0),
                                  axis=1)
                passes = jnp.max(before[:, -1])
            order = jnp.argsort(key.reshape(-1), stable=True)
            pos = jnp.argsort(order).reshape(n_tok, k).astype(jnp.int32)
            order = order.astype(jnp.int32)
            sizes = jnp.sum(key.reshape(-1, 1) == jnp.arange(n_held),
                            axis=0, dtype=jnp.int32)
            weights = jnp.where(key < n_held, weights, 0.0)
            per_expert = jax.lax.stop_gradient(jnp.sum(
                experts.reshape(-1, 1) == jnp.arange(self.num_experts),
                axis=0, dtype=jnp.int32))

        # no zoo_moe.* scope around the conditionals and loops: each opens
        # its own inside, on the operations (_sum_over_choices)
        args = (tokens, *(params[w].astype(cd)
                          for w in ("Wgate", "Wup", "Wdown")),
                weights, order, pos, sizes, passes)
        tile_rows = functools.partial(visited_tile_rows, args[1], sizes)
        if compact < n_rows:
            y = _held_rows_or_all(compact, *args)
            fits = (jnp.sum(sizes) <= compact).astype(jnp.int32)
            visited = jnp.where(fits == 1, tile_rows(compact),
                                tile_rows(n_rows))
        else:
            y = _held_rows(n_rows, *args)
            fits = jnp.int32(0)
            visited = tile_rows(n_rows)
        ran = {"rows_run": n_rows - fits * (n_rows - compact),
               "gmm_tile_rows": visited,
               "choice_passes": (jnp.int32(k) if passes is None
                                 else _passes_run(passes, k)),
               "compact_runs": fits}
        return y, sizes, per_expert, ran

    def call(self, params, x, *, training=False, rng=None):
        y, _ = self.apply(params, {}, x, training=training, rng=rng)
        return y


def routed_layer_states(state, path=()):
    """``{layer path: state dict}`` of every ``RoutedExperts`` in a network
    state tree (found by the reserved key ``moe_expert_tokens``)."""
    if not isinstance(state, dict):
        return {}
    if "moe_expert_tokens" in state:
        return {"/".join(path): state}
    found = {}
    for key, sub in state.items():
        found.update(routed_layer_states(sub, path + (str(key),)))
    return found


def routed_layer_totals(state):
    """The wide counters of every routed layer as Python ints, and the last
    step's per-expert tokens: one small readback, for the ends of a
    ``fit``."""
    layers = routed_layer_states(state)
    if not layers:
        return {}
    out = {}
    for name, s in jax.device_get(layers).items():
        out[name] = {**{count: wide_value(s[key])
                        for count, key in WIDE_COUNTERS.items()},
                     "expert_tokens": [int(n) for n in
                                       s["moe_expert_tokens"]],
                     "held_tokens": [int(n) for n in s["moe_held_tokens"]]}
    return out


def bound_ratios(counts):
    """What bounded the layer's passes, from its counters over some span:
    ``rows_run_over_held`` (rows the row buffers held over the assignments
    held: 1 is no waste, ``num_experts / len(held)`` the static worst case
    under a balanced router), ``choice_passes_mean`` (gather-sum passes a
    chunk; ``top_k`` is the static worst case), ``compact_share`` (share of
    chunks whose buffers were cut to ``C`` rows) and ``gmm_tile_fill``
    (assignments held over the rows of the row tiles the grouped products'
    kernels visited: what is short of 1 is padding at group boundaries; 0
    where XLA's kernels run)."""
    runs = max(counts["chunk_runs"], 1)
    return {"rows_run_over_held": (counts["rows_run"] / counts["held"]
                                   if counts["held"] else 0.0),
            "choice_passes_mean": counts["choice_passes"] / runs,
            "compact_share": counts["compact_runs"] / runs,
            "gmm_tile_fill": (counts["held"] / counts["gmm_tile_rows"]
                              if counts["gmm_tile_rows"] else 0.0)}


def fit_report(before, net_state, registry):
    """The routed layers' counters over the fit that just ended
    (``model.last_fit_report["moe"]``): ``before`` is
    :func:`routed_layer_totals` of the state the fit started from,
    ``net_state`` the state it left, read here once, after the fit's last
    drain (no readback a step). Published to ``registry``:
    ``zoo_moe_assignments_total{layer=,held=}``,
    ``zoo_moe_dropped_assignments_total``, and of the fit's last step
    ``zoo_moe_expert_tokens{layer=,expert=}`` and
    ``zoo_moe_load_max_over_mean{layer=}`` (largest held expert's tokens
    over the held experts' mean); what bounded the layers' work,
    ``zoo_moe_rows_run_total{layer=}``, ``zoo_moe_choice_passes_total
    {layer=}``, ``zoo_moe_chunk_runs_total{layer=,compact=}``, and in the
    report their ratios (:func:`bound_ratios`). ``None`` for a model
    without such layers."""
    after = routed_layer_totals(net_state)
    if not after:
        return None
    counts = tuple(WIDE_COUNTERS)
    report = {"layers": {}, **dict.fromkeys(counts, 0)}
    for name, now in after.items():
        was = before.get(name, {})
        layer = {k: now[k] - was.get(k, 0) for k in counts}
        for key in counts:
            report[key] += layer[key]
        layer.update(bound_ratios(layer))
        layer["expert_tokens"] = now["expert_tokens"]
        mean = sum(now["held_tokens"]) / max(len(now["held_tokens"]), 1)
        layer["load_max_over_mean"] = (
            max(now["held_tokens"]) / mean if mean > 0 else 0.0)
        report["layers"][name] = layer
        registry.counter(  # zoolint: disable=ZL015 one series a layer
            "zoo_moe_rows_run_total",
            "rows a RoutedExperts layer's row buffers held: the cut "
            "size C a chunk whose held assignments fit in it, tokens x "
            "top_k a chunk else",
            labels={"layer": name}).inc(layer["rows_run"])
        registry.counter(  # zoolint: disable=ZL015 one series a layer
            "zoo_moe_choice_passes_total",
            "gather-sum passes over a token's choices a RoutedExperts "
            "layer ran, a chunk: the most held choices any token had, "
            "or all top_k where those are many",
            labels={"layer": name}).inc(layer["choice_passes"])
        for cut, n in (("true", layer["compact_runs"]),
                       ("false", layer["chunk_runs"]
                        - layer["compact_runs"])):
            registry.counter(  # zoolint: disable=ZL015 one series a layer
                "zoo_moe_chunk_runs_total",
                "chunks of tokens a RoutedExperts layer ran, by whether "
                "its row buffers were cut to the rows held (compact="
                "true) or held the worst case",
                labels={"layer": name, "compact": cut}).inc(n)
        for held, key in (("true", "held"), ("false", "absent")):
            registry.counter(  # zoolint: disable=ZL015 one series a layer
                "zoo_moe_assignments_total",
                "token-to-expert assignments routed by a RoutedExperts "
                "layer: computed here (held=true) or left to absent "
                "experts (held=false)",
                labels={"layer": name, "held": held}).inc(layer[key])
        for e, n in enumerate(now["expert_tokens"]):
            registry.gauge(  # zoolint: disable=ZL015 bounded: router width
                "zoo_moe_expert_tokens",
                "assignments per router output in the last step of "
                "the last fit",
                labels={"layer": name, "expert": str(e)}).set(n)
        registry.gauge(  # zoolint: disable=ZL015 one series a layer
            "zoo_moe_load_max_over_mean",
            "largest held expert's tokens over the held experts' mean, "
            "last step of the last fit",
            labels={"layer": name}).set(layer["load_max_over_mean"])
    registry.counter(
        "zoo_moe_dropped_assignments_total",
        "assignments a RoutedExperts layer placed with no expert "
        "(0 by construction: the layer has no capacity)"
    ).inc(report["dropped"])
    report.update(bound_ratios(report))
    return report
