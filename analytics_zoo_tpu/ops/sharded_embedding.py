"""Out-of-core sharded embedding engine — row-partitioned tables,
dedup'd gathers, sparse scatter-add gradients, and a host-RAM cold tier.

The DLRM-style big-embedding problem (Naumov et al.): recommender tables
outgrow one chip long before the dense trunk does. This module solves it
with the Megatron-style idiom PR 14 proved on the vocab-sharded fused CE
— shard the parameter over the ``model`` mesh axis and own every
collective explicitly — plus two memory-motion optimizations and a host
tier:

* **Row partitioning** (:func:`sharded_embedding_lookup`): the
  ``(V, D)`` table splits row-wise ``P(model, None)`` under
  ``shard_map``; each rank gathers only the rows it owns and ONE
  ``psum`` over the ``model`` axis merges them (every non-owner
  contributes exact zeros, so the merge is bit-exact, not an
  accumulation). The custom VJP sits OUTSIDE the shard_map exactly like
  ``fused_cross_entropy._sharded_rows`` — both directions are explicit
  shard_map calls owning every cross-rank reduction; nothing rides
  shard_map's transpose conventions.
* **Dedup'd unique-lookup gathers**: ids are deduplicated per step with
  a fixed-``size`` ``jnp.unique`` (:func:`dedup_capacity` buckets the
  capacity to powers of two so compiled shapes stay stable — the PR-13
  retrace guard), so each *distinct* row crosses the interconnect once;
  the ``(capacity, D)`` unique-row block replaces the
  ``(batch·pooling, D)`` naive gather whenever the table (or the bucket)
  is smaller than the id stream.
* **Sparse scatter-add gradients**: the backward never forms a dense
  ``(V, D)`` cotangent. The row cotangents scatter-add onto the
  ``(capacity, D)`` unique block (repeated ids collide additively —
  f32 accumulation per the ZL021 discipline), then rank-locally onto the
  owned ``(V/n, D)`` slice via the dump-row trick, and the only
  collective is the data/seq-axis allreduce of the still-sharded blocks
  — reduced BEFORE the shard_map returns, so ``out_specs`` claims
  exactly what the body produced (ZL026).
* **Host-RAM cold tier** (:class:`OutOfCoreEmbeddingCache`): the table's
  cold tail lives in pinned host numpy (the TPU-native answer to the
  reference platform's PMEM FeatureSet tier), a device-resident hot set
  serves the head, and an async prefetch thread (the
  ``feature_set._ThreadedIterator`` machinery) stages the NEXT batch's
  missing rows while the current step runs. Hit/miss/prefetch/dedup
  counters export through the metrics registry and a
  :class:`~..observability.goodput.GoodputLedger` charges ``data_wait``
  whenever a step actually blocks on a fetch (the
  ``prefetch_to_device`` seam discipline).

Out-of-range ids clamp into ``[0, V)`` — ``jnp.take``'s clip mode, which
is also what the ``Embedding`` layer's gather compiles to.

The optional Pallas expand-gather kernel (``ops/pallas/embedding.py``,
``zoo.pallas.embed_gather``) accelerates the unique-block → row-stream
expansion on the MXU; it is priced through the shared
``ops/pallas/common.py`` VMEM estimator like every other kernel.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Iterable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pallas.common import round_up

__all__ = ["sharded_embedding_lookup", "dedup_embedding_lookup",
           "model_row_shard_count", "dedup_capacity", "oocore_gather",
           "EmbeddingFetchPlan", "OutOfCoreEmbeddingCache"]


def _conf(key: str, default):
    """Config read through the zoo context when one is constructible,
    else the default (keeps the op usable standalone)."""
    try:
        from ..common.context import get_zoo_context
        return get_zoo_context().get(key, default)
    except Exception:  # zoolint: disable=ZL007 no context constructible
        return default


def model_row_shard_count(mesh=None) -> int:
    """Size of the ``model`` mesh axis — the row shard count the sharded
    lookup splits the table over (1 = no tensor parallelism, the
    unsharded dedup'd lookup applies)."""
    from ..parallel import mesh as mesh_lib
    mesh = mesh or mesh_lib.global_mesh()
    return int(mesh.shape[mesh_lib.MODEL_AXIS])


def dedup_capacity(n_ids: int, vocab: int) -> int:
    """The static unique-id capacity for a ``(n_ids,)`` id block over a
    ``vocab``-row table: the exact unique count is data-dependent, so
    the compiled shape uses the safe ceiling ``min(n_ids, vocab)``
    bucketed up to a power of two — nearby problem sizes share one
    compiled shape (the PR-13 retrace guard) and ``jnp.unique`` can
    never truncate. Capped at the (sublane-rounded) id count: a bucket
    larger than the id stream would gather MORE rows than no dedup at
    all."""
    need = max(min(int(n_ids), int(vocab)), 1)
    cap = 1 << (need - 1).bit_length()
    return max(min(cap, round_up(int(n_ids), 8)), 8)


def _unique_ids(ids, capacity: int, fill: int):
    """Fixed-shape dedup: ``(uniq, inv)`` with ``uniq`` padded to
    ``capacity`` with ``fill`` (an id no shard owns — fill slots are
    never referenced by ``inv`` and gather exact zeros)."""
    uniq, inv = jnp.unique(ids, size=capacity, fill_value=fill,
                           return_inverse=True)
    return uniq, inv.reshape(-1)


def _expand_rows(rows, inv, use_pallas: bool, interpret: Optional[bool]):
    """``rows[inv]`` — the unique-block → row-stream expansion, routed
    through the Pallas one-hot MXU gather when enabled."""
    if use_pallas:
        from .pallas.embedding import embed_expand
        return embed_expand(rows, inv, interpret=interpret)
    return jnp.take(rows, inv, axis=0)


# ---------------------------------------------------------------------------
# unsharded dedup'd lookup (model == 1), sparse-grad custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _dedup_take(table, ids, capacity, use_pallas, interpret):
    out, _ = _dedup_take_fwd(table, ids, capacity, use_pallas, interpret)
    return out


def _dedup_take_fwd(table, ids, capacity, use_pallas, interpret):
    v = table.shape[0]
    uniq, inv = _unique_ids(ids, capacity, fill=v)
    rows = jnp.take(table, jnp.clip(uniq, 0, v - 1), axis=0)
    out = _expand_rows(rows, inv, use_pallas, interpret)
    return out, (uniq, inv, jnp.zeros((), table.dtype), v)


def _dedup_take_bwd(capacity, use_pallas, interpret, res, g):
    uniq, inv, dtype_token, v = res
    d = g.shape[-1]
    # dedup'd scatter-add: repeated ids collide additively on the unique
    # block first (f32 accumulation), then one scatter onto the table —
    # cost proportional to touched rows, never a dense (V, D) cotangent
    d_rows = jnp.zeros((capacity, d), jnp.float32).at[inv].add(
        g.astype(jnp.float32))
    # dump-row trick: fill slots (uniq == v) land on the sliced-off row
    safe = jnp.clip(uniq, 0, v)
    dw = jnp.zeros((v + 1, d), jnp.float32).at[safe].add(d_rows)[:v]
    dids = np.zeros(inv.shape, dtype=jax.dtypes.float0)
    return dw.astype(dtype_token.dtype), dids


_dedup_take.defvjp(_dedup_take_fwd, _dedup_take_bwd)


def dedup_embedding_lookup(table, ids, capacity: Optional[int] = None,
                           use_pallas: Optional[bool] = None,
                           interpret: Optional[bool] = None):
    """Single-shard dedup'd gather with the sparse scatter-add VJP —
    numerically identical to ``jnp.take(table, ids, axis=0)`` (f32
    bit-exact; grads are the same scatter-adds the dense transpose
    performs, accumulated in f32)."""
    v, d = table.shape
    orig = ids.shape
    flat = jnp.clip(ids.reshape(-1).astype(jnp.int32), 0, v - 1)
    if capacity is None:
        capacity = dedup_capacity(flat.shape[0], v)
    if use_pallas is None:
        from .pallas.embedding import pallas_embed_gather_enabled
        use_pallas = pallas_embed_gather_enabled()
    out = _dedup_take(table, flat, int(capacity), bool(use_pallas),
                      interpret)
    return out.reshape(*orig, d)


# ---------------------------------------------------------------------------
# row-sharded lookup (model > 1) — explicit-collective custom VJP
# ---------------------------------------------------------------------------

def _row_specs(mesh):
    """(id/row spec, table spec): ids/rows shard over (data, seq) — the
    flattened (B·T) layout the training step produces — and the table
    rows over ``model``."""
    from jax.sharding import PartitionSpec as P

    from ..parallel import mesh as mesh_lib
    row_spec = P((mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS))
    table_spec = P(mesh_lib.MODEL_AXIS, None)
    return row_spec, table_spec


def _sharded_fwd_local(table, ids, capacity, n_model, use_pallas,
                       interpret):
    """Per-rank forward half. ``table`` is the rank-local ``(V/n, D)``
    row block, ``ids`` the rank-local id slice (replicated over
    ``model``). Dedup → masked local gather of owned rows → ONE psum
    over ``model`` (each distinct row crosses the interconnect once;
    non-owners contribute exact zeros) → expand back to the id stream.
    The psum/axis_index pair rides PARALLELISM.md's collective-catalog
    rows for the ``model`` axis (ZL025 reconciles both directions)."""
    from ..parallel import mesh as mesh_lib

    vs = table.shape[0]
    rank = jax.lax.axis_index(mesh_lib.MODEL_AXIS)
    uniq, inv = _unique_ids(ids, capacity, fill=vs * n_model)
    loc = uniq - rank * vs
    own = (loc >= 0) & (loc < vs)
    rows_local = jnp.where(
        own[:, None],
        jnp.take(table, jnp.clip(loc, 0, vs - 1), axis=0
                 ).astype(jnp.float32),
        0.0)
    rows = jax.lax.psum(rows_local, mesh_lib.MODEL_AXIS)
    out = _expand_rows(rows.astype(table.dtype), inv, use_pallas,
                       interpret)
    return out, uniq, inv


def _sharded_bwd_local(uniq, inv, g, vs, dtype):
    """Per-rank backward half: the sparse ``(unique_ids, partial_dW)``
    scatter-add. Row cotangents collide additively onto the unique block
    in f32, non-owned rows route to the dump row, and the partial sums
    over the row-sharding axes are psum'd HERE — before the shard_map
    returns — so the ``P(model, None)`` out_specs claim is exact
    (ZL026: no partial_sum escapes the manual region)."""
    from ..parallel import mesh as mesh_lib

    capacity = uniq.shape[0]
    d = g.shape[-1]
    rank = jax.lax.axis_index(mesh_lib.MODEL_AXIS)
    d_rows = jnp.zeros((capacity, d), jnp.float32).at[inv].add(
        g.astype(jnp.float32))
    loc = uniq - rank * vs
    own = (loc >= 0) & (loc < vs)
    safe = jnp.where(own, jnp.clip(loc, 0, vs - 1), vs)
    dw = jnp.zeros((vs + 1, d), jnp.float32).at[safe].add(d_rows)[:vs]
    dw = jax.lax.psum(dw, (mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS))
    return dw.astype(dtype)


# the custom VJP sits OUTSIDE the shard_map on purpose (the
# fused_cross_entropy._sharded_rows structure): both directions are
# explicit shard_map calls whose bodies own every cross-rank reduction —
# nothing is left to shard_map's transpose machinery
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _sharded_lookup(table, ids, mesh, capacity, vp, use_pallas,
                    interpret):
    out, _ = _sharded_lookup_fwd(table, ids, mesh, capacity, vp,
                                 use_pallas, interpret)
    return out


def _sharded_lookup_fwd(table, ids, mesh, capacity, vp, use_pallas,
                        interpret):
    from jax.sharding import PartitionSpec as P

    row_spec, table_spec = _row_specs(mesh)
    n_model = model_row_shard_count(mesh)

    def run(tt, ii):
        return _sharded_fwd_local(tt, ii, capacity, n_model, use_pallas,
                                  interpret)

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(table_spec, row_spec),
                       out_specs=(P(row_spec[0], None), row_spec, row_spec),
                       check_vma=False)
    out, uniq, inv = fn(table, ids)
    return out, (uniq, inv, jnp.zeros((), table.dtype))


def _sharded_lookup_bwd(mesh, capacity, vp, use_pallas, interpret, res,
                        g):
    from jax.sharding import PartitionSpec as P

    uniq, inv, dtype_token = res
    row_spec, table_spec = _row_specs(mesh)
    n_model = model_row_shard_count(mesh)
    vs = vp // n_model

    def run(uu, ii, gg):
        return _sharded_bwd_local(uu, ii, gg, vs, dtype_token.dtype)

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(row_spec, row_spec, P(row_spec[0], None)),
                       out_specs=table_spec, check_vma=False)
    dw = fn(uniq, inv, g)
    dids = np.zeros(inv.shape, dtype=jax.dtypes.float0)
    return dw, dids


_sharded_lookup.defvjp(_sharded_lookup_fwd, _sharded_lookup_bwd)


def sharded_embedding_lookup(table, ids, mesh=None, *,
                             capacity: Optional[int] = None,
                             dedup: Optional[bool] = None,
                             use_pallas: Optional[bool] = None,
                             interpret: Optional[bool] = None):
    """Row-sharded embedding gather: ``table`` ``(V, D)`` splits row-wise
    over the ``model`` mesh axis, ids over ``data``/``seq``; semantics
    are ``jnp.take(table, ids, axis=0)`` with out-of-range ids clamped.
    ``V`` not divisible by the shard count pads the table internally
    (pad rows are never gathered and their grad slots transpose to the
    sliced-off region); id counts pad to the row-sharding divisor with
    id 0 (inert: outputs sliced off, cotangents zero). On a mesh with
    ``model == 1`` this is the unsharded dedup'd lookup — same sparse
    scatter-add VJP, no collectives.

    ``dedup=False`` keeps the same code path but sizes the unique
    capacity at the full id count (``zoo.embed.dedup`` default on)."""
    from ..parallel import mesh as mesh_lib

    mesh = mesh or mesh_lib.global_mesh()
    n_model = model_row_shard_count(mesh)
    v, d = table.shape
    orig = ids.shape
    flat = jnp.clip(ids.reshape(-1).astype(jnp.int32), 0, v - 1)
    n = flat.shape[0]
    if dedup is None:
        dedup = bool(_conf("zoo.embed.dedup", True))
    if use_pallas is None:
        from .pallas.embedding import pallas_embed_gather_enabled
        use_pallas = pallas_embed_gather_enabled()

    if n_model <= 1:
        cap = capacity or (dedup_capacity(n, v) if dedup
                           else round_up(n, 8))
        out = _dedup_take(table, flat, int(cap), bool(use_pallas),
                          interpret)
        return out.reshape(*orig, d)

    vp = round_up(v, n_model)
    if vp != v:
        table = jnp.pad(table, ((0, vp - v), (0, 0)))
    row_div = int(mesh.shape[mesh_lib.DATA_AXIS]
                  * mesh.shape[mesh_lib.SEQ_AXIS])
    n_pad = (-n) % row_div
    if n_pad:
        flat = jnp.pad(flat, (0, n_pad))
    n_loc = flat.shape[0] // row_div
    cap = capacity or (dedup_capacity(n_loc, vp) if dedup
                       else round_up(n_loc, 8))
    if cap < min(n_loc, vp):
        raise ValueError(
            f"dedup capacity {cap} cannot hold the worst-case "
            f"{min(n_loc, vp)} unique ids per shard — jnp.unique would "
            f"silently truncate; raise capacity or leave it unset")
    out = _sharded_lookup(table, flat, mesh, int(cap), vp,
                          bool(use_pallas), interpret)
    return out[:n].reshape(*orig, d)


# ---------------------------------------------------------------------------
# host-RAM cold tier
# ---------------------------------------------------------------------------

def oocore_gather(hot, cold, remap):
    """The jit-stable two-tier gather: ``remap`` indexes the virtual
    table ``[hot; cold]`` — ``hot`` is the device-resident head,
    ``cold`` the staged ``(capacity, D)`` rows the host plan uploaded.
    Differentiable in both tiers (the standard take transpose);
    :meth:`EmbeddingFetchPlan.scatter_grad` reassembles a dense table
    gradient from the tier cotangents."""
    hr = hot.shape[0]
    cold_part = jnp.take(cold, jnp.clip(remap - hr, 0, cold.shape[0] - 1),
                         axis=0)
    if hr == 0:
        return cold_part
    hot_part = jnp.take(hot, jnp.clip(remap, 0, hr - 1), axis=0)
    return jnp.where((remap < hr)[..., None], hot_part, cold_part)


class EmbeddingFetchPlan:
    """One batch's resolved host plan: the compiled-shape ``cold`` row
    block, the ``remap`` into the virtual ``[hot; cold]`` table, and the
    bookkeeping to reassemble dense gradients."""

    __slots__ = ("ids", "remap", "cold", "cold_ids", "hot_rows",
                 "table_shape")

    def __init__(self, ids, remap, cold, cold_ids, hot_rows, table_shape):
        self.ids = ids
        self.remap = remap
        self.cold = cold
        self.cold_ids = cold_ids
        self.hot_rows = int(hot_rows)
        self.table_shape = tuple(table_shape)

    def scatter_grad(self, d_hot, d_cold) -> np.ndarray:
        """Dense ``(V, D)`` f32 gradient from the tier cotangents of
        :func:`oocore_gather` — the host-side scatter-add the optimizer
        (or a parity test) applies to the master table."""
        v, d = self.table_shape
        dw = np.zeros((v, d), np.float32)
        if self.hot_rows:
            dw[:self.hot_rows] += np.asarray(d_hot, np.float32)
        dc = np.asarray(d_cold, np.float32)
        np.add.at(dw, self.cold_ids, dc[:self.cold_ids.size])
        return dw


class OutOfCoreEmbeddingCache:
    """Two-tier table: a device-resident hot head (sized by the
    ``zoo.embed.hot_rows_budget_mb`` device budget) and a pinned
    host-numpy cold tail. :meth:`plan` resolves one batch's missing rows
    (dedup'd — each distinct cold row is fetched and uploaded once);
    :meth:`stream` overlaps that resolution with device compute on a
    background prefetch thread, degrading to a synchronous fetch when a
    prefetch fails (``embed.prefetch`` fault site) — a step can stall,
    never wedge. Row fetches from host RAM run through the
    ``embed.host_fetch`` fault site; a ledger charges blocked time to
    ``data_wait``."""

    def __init__(self, table, *, hot_rows: Optional[int] = None,
                 prefetch_depth: Optional[int] = None,
                 staged_rows: int = 8192, registry=None, ledger=None):
        from ..observability import default_registry
        self._table = np.ascontiguousarray(np.asarray(table, np.float32))
        v, d = self._table.shape
        if hot_rows is None:
            budget_mb = float(_conf("zoo.embed.hot_rows_budget_mb", 64))
            hot_rows = int((budget_mb * 1024 * 1024) // max(d * 4, 1))
        self.hot_rows = max(0, min(int(hot_rows), v))
        self._hot = jnp.asarray(self._table[:self.hot_rows])
        # the cold tier stays host-resident, contiguous for fast slicing
        self._cold = np.ascontiguousarray(self._table[self.hot_rows:])
        if prefetch_depth is None:
            prefetch_depth = int(_conf("zoo.embed.prefetch_depth", 2))
        self.prefetch_depth = max(1, int(prefetch_depth))
        self._staged_max = max(int(staged_rows), 1)
        self._staged: "collections.OrderedDict[int, np.ndarray]" = \
            collections.OrderedDict()
        # one jitted gather shared by every rows() call — the pow2 cold
        # bucket keeps the compiled shapes stable across batches
        self._gather = jax.jit(oocore_gather)
        self._lock = threading.Lock()
        self._ledger = ledger
        reg = registry if registry is not None else default_registry()
        self._c_hits = reg.counter(
            "zoo_embed_cache_hits_total",
            "unique ids served without a host fetch (device-resident hot "
            "tier or already-staged cold rows)")
        self._c_misses = reg.counter(
            "zoo_embed_cache_misses_total",
            "unique cold-tier ids that required a host-RAM row fetch")
        self._c_prefetch = reg.counter(
            "zoo_embed_prefetch_rows_total",
            "cold rows staged ahead of the consuming step by the "
            "prefetch thread")
        self._c_dedup = reg.counter(
            "zoo_embed_dedup_saved_rows_total",
            "gathered rows saved by per-batch id dedup (ids seen minus "
            "unique ids)")
        self._c_prefetch_err = reg.counter(
            "zoo_embed_prefetch_errors_total",
            "prefetch attempts that failed and degraded to a "
            "synchronous fetch on the consumer thread")
        self._g_ids = reg.counter(
            "zoo_embed_ids_total",
            "ids resolved through the cache (dedup ratio denominator)")
        reg.gauge("zoo_embed_prefetch_depth",
                  "plan buffer depth of the cold-tier prefetch thread"
                  ).set(self.prefetch_depth)
        reg.gauge("zoo_embed_hot_rows",
                  "rows of the embedding table resident on device (the "
                  "hot tier; the rest live in host RAM)"
                  ).set(self.hot_rows)

    # -- table views ---------------------------------------------------------
    @property
    def hot(self):
        """The device-resident hot tier (differentiable operand of
        :func:`oocore_gather`)."""
        return self._hot

    @property
    def table(self) -> np.ndarray:
        """The host master copy (tests reconcile against it)."""
        return self._table

    # -- host planning -------------------------------------------------------
    def plan(self, ids) -> EmbeddingFetchPlan:
        """Resolve one batch: dedup the ids, serve hot/staged rows from
        cache, fetch the missing cold rows from host RAM
        (``embed.host_fetch``), and build the compiled-shape ``(cold,
        remap)`` pair :func:`oocore_gather` consumes."""
        v, d = self._table.shape
        ids_np = np.asarray(ids)
        flat = np.clip(ids_np.reshape(-1).astype(np.int64), 0,
                       max(v - 1, 0))
        uniq, inv = np.unique(flat, return_inverse=True)
        self._g_ids.inc(int(flat.size))
        self._c_dedup.inc(int(flat.size - uniq.size))
        hot_mask = uniq < self.hot_rows
        self._c_hits.inc(int(hot_mask.sum()))
        cold_ids = uniq[~hot_mask]
        rows = self._cold_rows(cold_ids)
        cap = dedup_capacity(max(int(cold_ids.size), 1), max(v, 1))
        cold = np.zeros((cap, d), np.float32)
        cold[:cold_ids.size] = rows
        slot = np.empty(uniq.size, np.int32)
        slot[hot_mask] = uniq[hot_mask].astype(np.int32)
        slot[~hot_mask] = self.hot_rows + np.arange(cold_ids.size,
                                                    dtype=np.int32)
        remap = slot[inv].astype(np.int32).reshape(ids_np.shape)
        return EmbeddingFetchPlan(ids_np, remap, cold, cold_ids,
                                  self.hot_rows, (v, d))

    def _cold_rows(self, cold_ids: np.ndarray) -> np.ndarray:
        """Rows for the (unique) cold ids: staged-LRU hits first, one
        batched host fetch for the misses."""
        d = self._table.shape[1]
        out = np.empty((cold_ids.size, d), np.float32)
        miss_pos, miss_ids = [], []
        with self._lock:
            for j, i in enumerate(cold_ids.tolist()):
                row = self._staged.get(i)
                if row is not None:
                    self._staged.move_to_end(i)
                    out[j] = row
                else:
                    miss_pos.append(j)
                    miss_ids.append(i)
        self._c_hits.inc(cold_ids.size - len(miss_ids))
        if miss_ids:
            self._c_misses.inc(len(miss_ids))
            fetched = self._host_fetch(np.asarray(miss_ids, np.int64))
            out[np.asarray(miss_pos)] = fetched
            with self._lock:
                for i, row in zip(miss_ids, fetched):
                    self._staged[i] = row
                while len(self._staged) > self._staged_max:
                    self._staged.popitem(last=False)
        return out

    def _host_fetch(self, miss_ids: np.ndarray) -> np.ndarray:
        from ..common import faults
        faults.inject("embed.host_fetch")
        return self._cold[miss_ids - self.hot_rows]

    # -- device lookup -------------------------------------------------------
    def rows(self, plan: EmbeddingFetchPlan):
        """Device rows for a planned batch: ``(ids.shape..., D)`` — one
        staged-block + remap upload, then the jitted two-tier gather."""
        return self._gather(self._hot, jnp.asarray(plan.cold),
                            jnp.asarray(plan.remap))

    # -- pipelined streaming -------------------------------------------------
    def stream(self, batches: Iterable, *, ledger=None
               ) -> Iterator[Tuple[np.ndarray, EmbeddingFetchPlan]]:
        """Yield ``(ids, plan)`` with upcoming plans staged by a
        background thread (``feature_set._ThreadedIterator`` — the same
        machinery ``prefetch_to_device`` rides). A prefetch failure
        (``embed.prefetch``) is counted and the plan is rebuilt
        synchronously on the consumer thread; the step never wedges.
        Ledger attribution follows the ``prefetch_to_device`` seam
        discipline: blocked pulls (and degraded synchronous fetches)
        are ``data_wait``, the consumer's compute is ``device_step``."""
        from ..common import faults
        from ..feature.feature_set import _ThreadedIterator
        ledger = ledger if ledger is not None else self._ledger

        def note(category):
            if ledger is not None:
                ledger.note(category)

        def staged():
            for ids in batches:
                try:
                    faults.inject("embed.prefetch")
                    p = self.plan(ids)
                    self._c_prefetch.inc(int(p.cold_ids.size))
                    yield ids, p
                # degrade, never wedge: the consumer refetches in line
                except Exception:  # zoolint: disable=ZL007
                    self._c_prefetch_err.inc()
                    yield ids, None
        src = _ThreadedIterator(staged(),
                                buffer_size=self.prefetch_depth)
        note("idle")
        try:
            for ids, p in src:
                if p is None:
                    p = self.plan(ids)    # synchronous degraded fetch
                note("data_wait")
                yield ids, p
                note("device_step")
        finally:
            note("data_wait")
            src.close()
