"""Pipeline parallelism — GPipe microbatch schedule over the ``pipe`` mesh
axis (SURVEY §2.4: PP "NO — no stage partitioner / microbatch scheduler
exists" in the reference; designed fresh for TPU).

The TPU-native shape of pipeline parallelism: stage weights are STACKED into
one ``(S, ...)`` tree sharded over the ``pipe`` axis, and the schedule is a
single ``lax.scan`` of ``n_micro + S - 1`` ticks inside ``shard_map`` — each
tick every pipe rank runs its stage on its current microbatch and the
activations rotate one hop with ``lax.ppermute`` over ICI. No host-side
scheduler, no per-stage processes: XLA sees one fused program, and autodiff
through scan+ppermute yields the backward pipeline for free (1F1B-style
memory tricks are a future refinement; GPipe semantics first).

Two schedulers share the schedule: ``gpipe_apply`` for HOMOGENEOUS stages
(same layer config, shape-preserving — the stacked transformer-block case,
cheapest representation) and ``hetero_gpipe_apply`` for ARBITRARY stage cuts
(per-stage distinct param trees and activation shapes: ``embedding → blocks
→ head`` as one pipelined model, via a packed param buffer + common
activation wire format + ``lax.switch`` per rank). On a mesh without a
``pipe`` axis the same models run sequentially — portable from 1 chip to a
pipelined slice unchanged.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib


def _rotate_perm(size: int):
    return [(i, (i + 1) % size) for i in range(size)]


def _pin_replicated(tree, mesh):
    """Commit a replicated layout on shard_map operands computed INSIDE
    an enclosing jit (the training-step path stacks stage params at
    trace time). Without the pin, GSPMD is free to pick any layout for
    the intermediate, and a layout that disagrees with the shard_map
    in_specs enters the manual region UNREDUCED on this jax version —
    measured as every stage's params arriving multiplied by the
    data-axis size (data^S after S stages). Eager callers and jit
    arguments already carry committed layouts; the pin is a no-op for
    them.

    Replicated, NOT ``P(pipe)``: the memory-preserving stage-sharded pin
    was tried and hits the same unreduced-entry bug (a P(pipe)-committed
    in-jit stack still arrived ×data-size per stage on jax 0.4.37, see
    ``tests/test_pipeline_parallel.py``'s in-jit regression test's
    history), so per-rank stage-param memory scaling from inside a jit
    waits on the upstream fix. The training-loop path replicates these
    params anyway (no layer declares a pipe param spec), so today this
    costs nothing it wasn't already paying.

    zoolint's ZL026 caller prong enforces this bug class: a trace-time
    stacked tree passed into a shard_map site must route through a
    ``with_sharding_constraint`` pin (this helper qualifies), so new
    step builders that skip the pin fail lint instead of training
    ×data-size."""
    repl = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, repl), tree)


def gpipe_apply(stage_fn: Callable, stacked_params, x, *, mesh,
                n_micro: int, rng=None, stages_per_rank: int = 1):
    """Run ``x`` through ``S`` stacked stages with the GPipe schedule.

    ``stage_fn(params, x, rng) -> y`` is one stage; ``stacked_params`` has
    leading dim ``total_stages`` on every leaf, sharded over ``pipe``; ``x``
    is the global batch ``(B, ...)`` (sharded over ``data``). With
    ``stages_per_rank`` k > 1 each pipe rank owns k consecutive stages and
    applies them back-to-back per tick (a deeper pipeline than chips). The
    per-data-shard batch must divide by ``n_micro``; wall-clock per batch
    is ``(n_micro + P - 1)`` superstage times (P = pipe size), the classic
    GPipe bubble — raise ``n_micro`` to amortize it.
    """
    S = mesh.shape[mesh_lib.PIPE_AXIS]
    dp = mesh.shape[mesh_lib.DATA_AXIS]
    B = x.shape[0]
    if B % dp != 0:
        raise ValueError(
            f"batch {B} not divisible by the data axis size {dp}")
    if (B // dp) % n_micro != 0:
        raise ValueError(
            f"per-shard batch {B // dp} not divisible by n_micro={n_micro}")

    # one PartitionSpec prefix per argument: params split stage-wise over
    # pipe, batch split over data (replicated over pipe)
    pspec = jax.tree.map(lambda _: P(mesh_lib.PIPE_AXIS), stacked_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspec, P(mesh_lib.DATA_AXIS)),
        out_specs=P(mesh_lib.DATA_AXIS),
        check_vma=False)
    def run(params_loc, x_loc):
        r = jax.lax.axis_index(mesh_lib.PIPE_AXIS)
        mbs = x_loc.reshape(n_micro, x_loc.shape[0] // n_micro,
                            *x_loc.shape[1:])

        def super_stage(h, t):
            """The rank's k consecutive stages applied back-to-back."""
            def body(h, sp):
                p_j, j = sp
                # unique key per (tick, rank, local stage) = per
                # (microbatch, stage): stochastic stages decorrelate across
                # the schedule (exact rng-stream parity with the sequential
                # path is impossible — it draws once per stage for the
                # whole batch)
                srng = (jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(rng, t), r), j)
                    if rng is not None else None)
                return stage_fn(p_j, h, srng), None

            h, _ = jax.lax.scan(
                body, h, (params_loc, jnp.arange(stages_per_rank)))
            return h

        def tick(carry, t):
            state, out = carry
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(r == 0, feed, state)
            y = super_stage(inp, t)
            # the last rank retires microbatch t-(S-1) at tick t
            widx = jnp.clip(t - (S - 1), 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(out, widx, 0, keepdims=False)
            keep = jnp.logical_and(r == S - 1, t >= S - 1)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(keep, y, cur), widx, 0)
            state = jax.lax.ppermute(y, mesh_lib.PIPE_AXIS, _rotate_perm(S))
            return (state, out), None

        out0 = jnp.zeros_like(mbs)
        (_, out), _ = jax.lax.scan(tick, (jnp.zeros_like(mbs[0]), out0),
                                   jnp.arange(n_micro + S - 1))
        # results live on the last rank only; masked psum broadcasts them so
        # every pipe rank returns the same (replicated) value
        out = jax.lax.psum(jnp.where(r == S - 1, out, jnp.zeros_like(out)),
                           mesh_lib.PIPE_AXIS)
        return out.reshape(x_loc.shape)

    return run(_pin_replicated(stacked_params, mesh), x)


def hetero_gpipe_apply(stage_fns, stacked_vec, x_wire, *, mesh,
                       n_micro: int, rng=None):
    """GPipe schedule over HETEROGENEOUS stages (VERDICT r4 missing #2:
    ``embedding → blocks → head`` as ONE pipelined model, arbitrary layer
    cuts, per-stage distinct param trees and activation shapes).

    SPMD can't run different programs per rank, so heterogeneity is encoded
    data-side: every stage's params are raveled into one row of the
    ``(S, L)`` ``stacked_vec`` (padded to the longest stage; sharded over
    ``pipe`` so each rank holds ONLY its stage's weights), activations
    travel in a common ``(B_micro, W)`` float32 wire format (padded to the
    widest stage boundary; f32 carries bf16 activations and int token ids
    exactly — ids are < 2^24), and each tick every rank runs
    ``lax.switch(rank, stage_fns)`` — all S branches are compiled
    everywhere, each rank executes exactly one, the XLA-native equivalent
    of per-stage programs.

    ``stage_fns[j](vec_row, h_wire, rng) -> h_wire`` unpacks its own slice
    layout statically. Schedule, bubble, and autodiff story are identical
    to ``gpipe_apply``.
    """
    S = mesh.shape[mesh_lib.PIPE_AXIS]
    dp = mesh.shape[mesh_lib.DATA_AXIS]
    B, W = x_wire.shape
    if B % dp != 0:
        raise ValueError(f"batch {B} not divisible by data axis size {dp}")
    if (B // dp) % n_micro != 0:
        raise ValueError(
            f"per-shard batch {B // dp} not divisible by n_micro={n_micro}")

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(mesh_lib.PIPE_AXIS), P(mesh_lib.DATA_AXIS)),
        out_specs=P(mesh_lib.DATA_AXIS),
        check_vma=False)
    def run(vec_loc, x_loc):
        r = jax.lax.axis_index(mesh_lib.PIPE_AXIS)
        vec = vec_loc[0]                                    # (L,)
        mbs = x_loc.reshape(n_micro, x_loc.shape[0] // n_micro, W)

        def tick(carry, t):
            state, out = carry
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(r == 0, feed, state)
            trng = jax.random.fold_in(rng, t) if rng is not None else None
            y = jax.lax.switch(
                r, [functools.partial(fn, rng=trng) for fn in stage_fns],
                vec, inp)
            widx = jnp.clip(t - (S - 1), 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(out, widx, 0, keepdims=False)
            keep = jnp.logical_and(r == S - 1, t >= S - 1)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(keep, y, cur), widx, 0)
            state = jax.lax.ppermute(y, mesh_lib.PIPE_AXIS, _rotate_perm(S))
            return (state, out), None

        out0 = jnp.zeros_like(mbs)
        (_, out), _ = jax.lax.scan(tick, (jnp.zeros_like(mbs[0]), out0),
                                   jnp.arange(n_micro + S - 1))
        out = jax.lax.psum(jnp.where(r == S - 1, out, jnp.zeros_like(out)),
                           mesh_lib.PIPE_AXIS)
        return out.reshape(x_loc.shape)

    return run(_pin_replicated(stacked_vec, mesh), x_wire)


def sequential_apply(stage_fn: Callable, stacked_params, x, n_stages: int,
                     rng=None):
    """Portability fallback (pipe axis == 1): the same stacked tree runs as
    a sequential ``lax.scan`` over stages — identical math for deterministic
    stages, one device. ``n_stages`` comes from the caller: the param tree
    may be empty (parameter-less stages like Dropout)."""
    def body(h, sp):
        p_stage, i = sp
        trng = jax.random.fold_in(rng, i) if rng is not None else None
        return stage_fn(p_stage, h, trng), None

    y, _ = jax.lax.scan(body, x, (stacked_params, jnp.arange(n_stages)))
    return y
