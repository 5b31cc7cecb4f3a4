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

Both define routing in one place, ``top_k_routing`` (softmax, top-k,
renormalise).

Auxiliary losses (``SparseMoE``: load-balance + router z-loss) ride the
layer-state channel: ``apply`` returns them under the reserved state key
``aux_loss``, which the training loop adds to the task loss *inside* the
differentiated function — see ``training.py`` ``_aux_loss_sum`` — so the
router receives gradient. ``RoutedExperts`` leaves its per-expert token
counts in the same channel (``moe_*`` keys), accumulated on the device and
read once per ``fit`` (``training.py`` ``_moe_report``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.grouped_matmul import grouped_matmul
from ..engine import Layer, compute_dtype, get_initializer, param_dtype
from .core import get_activation


def top_k_routing(logits, k: int, renormalize: bool = True):
    """The one definition of token-choice routing: float32 softmax over
    every router output, the ``k`` largest probabilities and their experts,
    the chosen weights renormalised to sum to 1 (``norm_topk_prob``).
    Returns ``(probs (N, E), weights (N, k), experts (N, k) int32)``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if renormalize:
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-9)
    return probs, weights, experts


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


def _wide_add(acc, n):
    lo = acc[1] + n.astype(jnp.int32)
    return jnp.stack([acc[0] + (lo >> _WIDE_BITS),
                      lo & ((1 << _WIDE_BITS) - 1)])


def wide_value(pair) -> int:
    """The Python int a ``(hi, lo)`` counter of the layer state holds."""
    return (int(pair[0]) << _WIDE_BITS) + int(pair[1])


def _take(rows, idx):
    """Rows by index; every index here comes from a permutation of the
    assignments, so none is out of bounds."""
    return rows.at[idx].get(mode="promise_in_bounds")


def _sum_over_choices(rows, pos, weights=None):
    """``sum_j [weights[n, j]] * rows[pos[n, j]]`` in float32, one choice at
    a time: the (N, k, d) gather of all choices at once would be as large
    as the row buffer itself."""
    total = None
    for j in range(pos.shape[1]):
        term = _take(rows, pos[:, j]).astype(jnp.float32)
        if weights is not None:
            term = term * weights[:, j, None]
        total = term if total is None else total + term
    return total


@jax.custom_vjp
def _dispatch(tokens, order, pos):
    """``rows[r] = tokens[order[r] // k]``: token rows in sorted-assignment
    order. ``order`` (N*k,) maps a sorted row to its assignment ``n*k + j``,
    ``pos`` (N, k) is its inverse. The transpose of a gather is a
    scatter-add; because ``order`` is a permutation it is also the gather
    ``d tokens[n] = sum_j d rows[pos[n, j]]``, which is what the backward
    runs."""
    return _take(tokens, order // pos.shape[1])


def _dispatch_fwd(tokens, order, pos):
    return _dispatch(tokens, order, pos), (order, pos)


def _dispatch_bwd(res, d_rows):
    order, pos = res
    return _sum_over_choices(d_rows, pos).astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, order, pos):
    """``out[n] = sum_j weights[n, j] * rows[pos[n, j]]`` in float32, the
    result in ``rows``' dtype; the backward again gathers and never
    scatters."""
    return _sum_over_choices(rows, pos, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, order, pos):
    return _combine(rows, weights, order, pos), (rows, weights, order, pos)


def _combine_bwd(res, d_out):
    rows, weights, order, pos = res
    k = pos.shape[1]
    d_rows = (_take(d_out, order // k).astype(jnp.float32)
              * _take(weights.reshape(-1), order)[:, None]
              ).astype(rows.dtype)
    g = d_out.astype(jnp.float32)
    d_weights = jnp.stack(
        [jnp.sum(_take(rows, pos[:, j]).astype(jnp.float32) * g, axis=-1)
         for j in range(k)], axis=1)
    return d_rows, d_weights, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class RoutedExperts(Layer):
    """Token-choice top-k experts without capacity and without dropped
    assignments, for one chip's share of the experts.

    ``num_experts`` is the published router width; ``held`` the expert ids
    this layer computes (a range or list; default all). Routing runs over
    all ``num_experts`` (``top_k_routing``), the N x k assignments are
    sorted by expert with the absent experts' after the held ones, the rows
    gathered in that order, and the three grouped products of the
    SiLU-gated experts (``silu(x Wgate) * (x Wup)) Wdown``) run over the
    held groups' sizes, so tiles past the last held row do no work. What
    the absent experts would have added is left out: the layer returns the
    held experts' part of ``sum_e w_e expert_e(x)``, and the parts of a set
    of layers whose ``held`` partition the experts add up to the whole
    (``tests/test_routed_experts.py``). Input ``(B, d)`` or ``(B, T, d)``.

    Shapes are static: the sorted index is N x k int32 and the row buffers
    hold N x k rows, the worst case (every choice of every token a held
    expert); the grouped products skip what the group sizes leave empty,
    the gathers do not. The layer keeps no capacity to size them by;
    ``token_chunk`` bounds them instead: N tokens are routed and run
    ``token_chunk`` at a time, one chunk after the other (``lax.map``, each
    chunk rematerialised in the backward pass), so the buffers hold
    ``token_chunk x k`` rows. Routing is per token, so the result is the
    same; a token count that is not a whole number of chunks runs whole.

    Layer state (accumulated on the device, published per ``fit``):
    ``moe_expert_tokens`` (num_experts,) the last step's assignments per
    router output, ``moe_held_tokens`` (len(held),) those of the held
    experts in ``held``'s order, and the wide counters ``moe_held`` / ``moe_absent`` /
    ``moe_dropped`` (``wide_value``): assignments computed here, left to
    absent experts, and placed nowhere (0 by construction)."""

    def __init__(self, num_experts: int, hidden_dim: int, top_k: int = 2,
                 held=None, norm_topk: bool = True,
                 token_chunk: Optional[int] = None,
                 init: str = "glorot_uniform", **kwargs):
        super().__init__(**kwargs)
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k={top_k} not in [1, {num_experts}]")
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
        # an assignment's sort key by expert: the expert's place among the
        # held ones, or len(held) for every absent expert
        self._place = [len(held)] * num_experts
        for i, e in enumerate(held):
            self._place[e] = i

    def build(self, rng, input_shape):
        d, h, n = input_shape[-1], self.hidden_dim, len(self.held)
        init = get_initializer(self.init)
        k = jax.random.split(rng, 4)
        return {"Wg": init(k[0], (d, self.num_experts), param_dtype()),
                "Wgate": init(k[1], (n, d, h), param_dtype()),
                "Wup": init(k[2], (n, d, h), param_dtype()),
                "Wdown": init(k[3], (n, h, d), param_dtype())}

    def initial_state(self, input_shape=None):
        pair = jnp.zeros((2,), jnp.int32)
        return {"moe_expert_tokens": jnp.zeros((self.num_experts,),
                                               jnp.int32),
                "moe_held_tokens": jnp.zeros((len(self.held),), jnp.int32),
                "moe_held": pair, "moe_absent": pair, "moe_dropped": pair}

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
        if chunk and n_tok > chunk and n_tok % chunk == 0:
            run = jax.checkpoint(self._run)
            y, sizes, per_expert = jax.lax.map(
                lambda t: run(params, t), tokens.reshape(-1, chunk, d))
            y = y.reshape(n_tok, d)
            sizes, per_expert = sizes.sum(0), per_expert.sum(0)
        else:
            y, sizes, per_expert = self._run(params, tokens)

        held = jnp.sum(sizes)
        placed = jnp.sum(per_expert)
        new_state = {
            "moe_expert_tokens": per_expert,
            "moe_held_tokens": sizes,
            "moe_held": _wide_add(state["moe_held"], held),
            "moe_absent": _wide_add(state["moe_absent"], placed - held),
            "moe_dropped": _wide_add(state["moe_dropped"],
                                     n_tok * self.top_k - placed),
        }
        return y.reshape(*lead, d), new_state

    def _run(self, params, tokens):
        """Route ``tokens`` (n, d) and run the held experts on them:
        ``(y (n, d), held group sizes, assignments per router output)``."""
        cd = tokens.dtype
        n_tok, k, n_held = tokens.shape[0], self.top_k, len(self.held)

        with jax.named_scope("zoo_moe.route"):
            logits = jnp.matmul(tokens, params["Wg"].astype(cd),
                                preferred_element_type=jnp.float32)
            _, weights, experts = top_k_routing(logits, k, self.norm_topk)
            key = jnp.take(jnp.asarray(self._place, jnp.int32),
                           experts)                             # (N, k)
            order = jnp.argsort(key.reshape(-1), stable=True)
            pos = jnp.argsort(order).reshape(n_tok, k).astype(jnp.int32)
            order = order.astype(jnp.int32)
            sizes = jnp.sum(key.reshape(-1, 1) == jnp.arange(n_held),
                            axis=0, dtype=jnp.int32)
            weights = jnp.where(key < n_held, weights, 0.0)
            per_expert = jax.lax.stop_gradient(jnp.sum(
                experts.reshape(-1, 1) == jnp.arange(self.num_experts),
                axis=0, dtype=jnp.int32))

        with jax.named_scope("zoo_moe.dispatch"):
            rows = _dispatch(tokens, order, pos)                # (N*k, d)
        with jax.named_scope("zoo_moe.experts"):
            gate = grouped_matmul(rows, params["Wgate"].astype(cd), sizes)
            up = grouped_matmul(rows, params["Wup"].astype(cd), sizes)
            act = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(cd)
            rows = grouped_matmul(act, params["Wdown"].astype(cd), sizes)
        with jax.named_scope("zoo_moe.combine"):
            y = _combine(rows, weights, order, pos)
        return y, sizes, per_expert

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
        out[name] = {"held": wide_value(s["moe_held"]),
                     "absent": wide_value(s["moe_absent"]),
                     "dropped": wide_value(s["moe_dropped"]),
                     "expert_tokens": [int(n) for n in
                                       s["moe_expert_tokens"]],
                     "held_tokens": [int(n) for n in s["moe_held_tokens"]]}
    return out
