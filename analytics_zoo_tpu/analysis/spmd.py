"""zoolint SPMD collective-semantics pass — rules ZL025–ZL028.

The device pass (ZL021–ZL024) checks dtype flow, mesh-axis vocabulary
and Pallas tile geometry but is blind to the COLLECTIVE layer itself:
what a ``psum``/``ppermute``/``all_gather`` does to a value's
distribution across the mesh, and whether a ``shard_map`` body's
``out_specs`` claim matches what the body actually produced. This
fourth stage closes that gap with an abstract interpreter over
``shard_map`` bodies tracking a per-value **distribution state
lattice**:

========================  ==================================================
state                     meaning (per mesh axis)
========================  ==================================================
``replicated``            every rank along the axis holds the same value
``sharded(axis, …)``      ranks hold different blocks (device-varying)
``partial_sum(axis, …)``  ranks hold unreduced partial sums — the true
                          value is the ``psum`` over the axis
``unknown``               nothing provable (the walker's default)
========================  ==================================================

Values are seeded from ``in_specs`` PartitionSpecs, transitioned by
collectives (``psum``/``pmax``/``pmin`` reduce an axis to replicated
and clear partial sums on it; ``psum_scatter`` converts partial to
sharded; ``all_gather`` un-shards; ``axis_index`` is device-varying;
``ppermute``/``all_to_all`` preserve the state) and by arithmetic
(adds/``where`` propagate, a dot whose operands are sharded over the
same axis at DIFFERENT dim positions — the Megatron row-parallel
signature — produces a partial sum over that axis). The walker reuses
``device.py``'s conventions: straight-line statement order, constant
folding through the mesh-module axis constants (ZL022's vocabulary),
one-level local-helper resolution, and *precision over recall* — an
unresolvable spec, axis or call degrades to ``unknown``, which is
never accused.

* **ZL025** — collective axis validity: a collective inside a
  ``shard_map`` body naming an axis the enclosing mesh does not bind
  fails at trace time only on a real multi-chip mesh. The project pass
  (``--contracts``) adds the collective-catalog reconciliation: every
  collective call site in ``parallel/``+``ops/`` ↔ a documented row
  (with its axis semantics) in ``docs/guides/PARALLELISM.md``, both
  directions.
* **ZL026** — unreduced-output hazard: a ``partial_sum(axis)`` value
  reaching ``out_specs`` that claim replication or sharding on that
  axis (``check_vma=False`` ships the wrong numbers silently), plus
  the caller-side form PR 14 hit in production: an in-jit computed
  operand (``jnp.stack``/``jax.tree.map`` at trace time) entering the
  manual region without a committed layout arrives unreduced
  (×axis-size) — pin it with ``with_sharding_constraint`` first.
* **ZL027** — divergent collectives under traced control flow: a
  collective reachable in only one branch of a ``lax.cond`` (or at all
  inside a ``lax.while_loop``, whose traced trip count can differ per
  rank) deadlocks the mesh — some ranks enter the collective, the
  rest never arrive. ``lax.scan`` bodies are exempt: the trip count is
  static, every rank runs the same schedule (the GPipe/ring pattern).
* **ZL028** — PartitionSpec hygiene: an axis used twice in one spec
  (jax rejects it at trace time), and provable arity mismatches at
  ``shard_map`` sites (``in_specs`` count vs the body's parameters,
  ``out_specs`` count vs the returned tuple). Axis-name vocabulary
  membership stays ZL022's job — one rule per fact.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import (Dict, FrozenSet, Iterator, List, Optional, Sequence,
                    Set, Tuple)

from .core import (ERROR, WARNING, Finding, ModuleContext, Rule, dotted,
                   register)
from .device import (_COLLECTIVES, _fold_axis_names, _in_package,
                     extract_axis_decls, package_axis_vocabulary,
                     staged_fns)
from .project import ProjectContext, ProjectRule, register_project

# ---------------------------------------------------------------------------
# the distribution-state lattice
# ---------------------------------------------------------------------------

_EMPTY: FrozenSet[str] = frozenset()


@dataclasses.dataclass(frozen=True)
class DistState:
    """Abstract distribution state of one value inside a manual
    (``shard_map``) region. ``sharded``/``partial`` are sets of mesh
    axis names; ``known=False`` is bottom-less top — nothing provable,
    never accused. ``dims`` optionally remembers which ARRAY dimension
    an axis shards (seed-time fact from the PartitionSpec) so the dot
    transfer can tell row-parallel contraction from batch sharding.
    ``elts`` carries per-element states for tuple values (``psum`` over
    an operand tuple, multi-output bodies)."""

    sharded: FrozenSet[str] = _EMPTY
    partial: FrozenSet[str] = _EMPTY
    known: bool = True
    dims: Tuple[Tuple[str, int], ...] = ()
    elts: Optional[Tuple["DistState", ...]] = None

    # -- constructors -------------------------------------------------
    @staticmethod
    def replicated() -> "DistState":
        return DistState()

    @staticmethod
    def unknown() -> "DistState":
        return DistState(known=False)

    @staticmethod
    def sharded_over(axes, dims: Optional[Dict[str, int]] = None
                     ) -> "DistState":
        return DistState(sharded=frozenset(axes),
                         dims=tuple(sorted((dims or {}).items())))

    @staticmethod
    def partial_over(axes) -> "DistState":
        return DistState(partial=frozenset(axes))

    # -- queries ------------------------------------------------------
    @property
    def is_replicated(self) -> bool:
        return self.known and not self.sharded and not self.partial

    def dim_of(self, axis: str) -> Optional[int]:
        return dict(self.dims).get(axis)

    # -- transitions --------------------------------------------------
    def reduce_over(self, axes) -> "DistState":
        """``psum``/``pmean``/``pmax``/``pmin`` over ``axes``: the
        result is replicated along them — both the sharding and any
        partial sum on those axes are resolved."""
        axes = frozenset(axes)
        return dataclasses.replace(
            self, sharded=self.sharded - axes, partial=self.partial - axes,
            elts=tuple(e.reduce_over(axes) for e in self.elts)
            if self.elts is not None else None)

    def scatter_over(self, axes) -> "DistState":
        """``psum_scatter``: partial sums reduce but the result is
        sharded over the axis."""
        axes = frozenset(axes)
        return dataclasses.replace(
            self, sharded=self.sharded | axes, partial=self.partial - axes,
            dims=(), elts=None)

    def gather_over(self, axes) -> "DistState":
        """``all_gather``: un-shards the axis; a partial sum survives
        gathering (every rank now holds all the unreduced terms)."""
        axes = frozenset(axes)
        return dataclasses.replace(self, sharded=self.sharded - axes,
                                   dims=(), elts=None)

    def drop_dims(self) -> "DistState":
        return dataclasses.replace(self, dims=()) if self.dims else self


def join(a: DistState, b: DistState) -> DistState:
    """Least upper bound used both for control-flow merges and for
    elementwise arithmetic combining (add/sub/``where``): a value that
    is device-varying or partial on EITHER input stays hazardous in the
    result; ``unknown`` absorbs everything."""
    if not a.known or not b.known:
        return DistState.unknown()
    if a.elts is not None and b.elts is not None \
            and len(a.elts) == len(b.elts):
        elts: Optional[Tuple[DistState, ...]] = tuple(
            join(x, y) for x, y in zip(a.elts, b.elts))
    else:
        elts = None
    da, db = dict(a.dims), dict(b.dims)
    if not da:
        dims = b.dims
    elif not db:
        dims = a.dims
    else:
        dims = tuple(sorted((k, v) for k, v in da.items()
                            if db.get(k) == v))
    return DistState(sharded=a.sharded | b.sharded,
                     partial=a.partial | b.partial,
                     dims=dims, elts=elts)


def join_all(states: Sequence[DistState]) -> DistState:
    out = DistState.replicated()
    for s in states:
        out = join(out, s)
    return out


def dot_transfer(a: DistState, b: DistState) -> DistState:
    """Contraction transfer (``dot``/``matmul``/``einsum``/``@``): an
    axis both operands are sharded over at provably DIFFERENT dim
    positions is being contracted across ranks (Megatron row-parallel:
    ``x@P(None, m) · w@P(m, None)``) — the local result is a partial
    sum over it. Same (or unprovable) positions mean batch-style
    sharding (the ring-attention ``bhqd·bhkd`` case) and stay sharded."""
    if not a.known or not b.known:
        return DistState.unknown()
    contracted: Set[str] = set()
    for ax in a.sharded & b.sharded:
        da, db = a.dim_of(ax), b.dim_of(ax)
        if da is not None and db is not None and da != db:
            contracted.add(ax)
    return DistState(
        sharded=(a.sharded | b.sharded) - contracted,
        partial=a.partial | b.partial | frozenset(contracted))


# ---------------------------------------------------------------------------
# PartitionSpec folding
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpecInfo:
    """One folded PartitionSpec: per-dim axis-name tuples (``()`` for a
    ``None``/unsharded dim). ``complete=False`` means some dim did not
    resolve — the spec's KNOWN axes still seed, but nothing is accused
    against its unresolved remainder."""

    dims: Tuple[Tuple[str, ...], ...]
    complete: bool
    line: int

    def axes(self) -> FrozenSet[str]:
        return frozenset(ax for d in self.dims for ax in d)

    def dim_index(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for i, d in enumerate(self.dims):
            for ax in d:
                out.setdefault(ax, i)
        return out


@dataclasses.dataclass
class SpecList:
    """A folded ``in_specs``/``out_specs`` value: a known prefix of
    specs (``None`` entries did not fold) and whether the LENGTH itself
    is proven (conditional ``+ ((mask_spec,) if …)`` tails are not).
    ``single`` marks a lone spec, which shard_map broadcasts over every
    operand/output."""

    specs: List[Optional[SpecInfo]]
    complete: bool
    single: bool = False


def _is_pspec_call(ctx: ModuleContext, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = dotted(node.func)
    if not d:
        return False
    mods, froms = ctx.jax_names
    leaf = d.split(".")[-1]
    if leaf == "PartitionSpec":
        prefix = d.rsplit(".", 1)[0] if "." in d else ""
        return not prefix or prefix in mods or prefix.split(".", 1)[0] in mods
    return "." not in d and froms.get(d) == "PartitionSpec"


def _fold_pspec(ctx: ModuleContext, node: ast.Call,
                consts: Dict[str, str]) -> SpecInfo:
    dims: List[Tuple[str, ...]] = []
    complete = True
    for arg in node.args:
        if isinstance(arg, ast.Starred):
            complete = False
            break
        if isinstance(arg, ast.Constant):
            if arg.value is None:
                dims.append(())
            elif isinstance(arg.value, str):
                dims.append((arg.value,))
            else:
                complete = False
                dims.append(())
            continue
        if isinstance(arg, (ast.Tuple, ast.List)):
            axes: List[str] = []
            for e in arg.elts:
                ax = _resolve_axis_token(e, consts)
                if ax is None:
                    complete = False
                else:
                    axes.append(ax)
            dims.append(tuple(axes))
            continue
        ax = _resolve_axis_token(arg, consts)
        if ax is None:
            complete = False
            dims.append(())
        else:
            dims.append((ax,))
    return SpecInfo(tuple(dims), complete, node.lineno)


def _resolve_axis_token(e: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    """A mesh-axis name out of one expression: a string literal or a
    name resolving through the (in-file + mesh-module) axis constants.
    Anything else — parameters, locals — is unresolvable, by the same
    precision-over-recall stance as ``device.iter_axis_uses``."""
    if isinstance(e, ast.Constant) and isinstance(e.value, str):
        return e.value
    d = dotted(e)
    if d and d.split(".")[-1] in consts:
        return consts[d.split(".")[-1]]
    return None


def _bindings_of(ctx: ModuleContext, scope: ast.AST,
                 name: str) -> List[Tuple[ast.Assign, Optional[int]]]:
    """Assignments binding ``name`` directly in ``scope`` (not in
    nested defs): ``(assign, None)`` for a plain target, ``(assign,
    i)`` for position ``i`` of a tuple-unpack target."""
    out: List[Tuple[ast.Assign, Optional[int]]] = []
    for node in ast.walk(scope):
        if not isinstance(node, ast.Assign):
            continue
        if ctx._enclosing_scope(node) is not scope:
            continue
        for t in node.targets:
            if isinstance(t, ast.Name) and t.id == name:
                out.append((node, None))
            elif isinstance(t, ast.Tuple):
                for i, e in enumerate(t.elts):
                    if isinstance(e, ast.Name) and e.id == name:
                        out.append((node, i))
    return out


def _single_binding(ctx: ModuleContext, at: ast.AST,
                    name: str) -> Optional[Tuple[ast.AST, Optional[int]]]:
    """The unique expression ``name`` is bound to, searched through the
    lexical scope chain of ``at``. Multiple bindings in the deciding
    scope → ambiguous → None (flow-insensitive honesty)."""
    scope = ctx._enclosing_scope(at)
    seen: Set[int] = set()
    while scope is not None and id(scope) not in seen:
        seen.add(id(scope))
        binds = _bindings_of(ctx, scope, name)
        if binds:
            if len(binds) != 1:
                return None
            assign, idx = binds[0]
            return assign.value, idx
        if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = scope.args
            params = {p.arg for p in list(a.posonlyargs) + list(a.args)
                      + list(a.kwonlyargs)}
            if a.vararg:
                params.add(a.vararg.arg)
            if a.kwarg:
                params.add(a.kwarg.arg)
            if name in params:
                return None         # a parameter shadows outer bindings
        if scope is ctx.tree:
            return None
        scope = ctx._enclosing_scope(scope)
    return None


def _helper_returns(ctx: ModuleContext, call: ast.Call) -> List[ast.AST]:
    """The return expressions of a locally-resolvable helper call
    (one level deep), or ``[]``."""
    if not isinstance(call.func, ast.Name):
        return []
    fn = ctx._resolve_local_fn(call, call.func.id)
    if fn is None or not isinstance(fn, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
        return []
    return [n.value for n in ast.walk(fn)
            if isinstance(n, ast.Return) and n.value is not None
            and ctx._enclosing_scope(n) is fn]


def fold_specs(ctx: ModuleContext, node: Optional[ast.AST],
               consts: Dict[str, str], depth: int = 0
               ) -> Optional[SpecList]:
    """Fold an ``in_specs``/``out_specs`` expression into a
    :class:`SpecList`, through the live idioms: literal ``P(...)``
    tuples, ``Name``-bound specs, one-level helper returns (the
    ``_seq_specs``/``_sharded_specs`` pattern), conditional tuple
    concatenation (known prefix, unproven length) and
    ``jax.tree.map(lambda _: P(axis), tree)`` (the gpipe per-leaf
    spec). Returns None when nothing folds."""
    if node is None or depth > 4:
        return None
    if _is_pspec_call(ctx, node):
        spec = _fold_pspec(ctx, node, consts)
        return SpecList([spec], complete=spec.complete, single=True)
    if isinstance(node, (ast.Tuple, ast.List)):
        specs: List[Optional[SpecInfo]] = []
        complete = True
        for e in node.elts:
            if isinstance(e, ast.Starred):
                return SpecList(specs, complete=False)
            sub = fold_specs(ctx, e, consts, depth + 1)
            if sub is not None and sub.single:
                specs.append(sub.specs[0])
                complete = complete and sub.complete
            else:
                specs.append(None)
                complete = False
        return SpecList(specs, complete)
    if isinstance(node, ast.Name):
        bound = _single_binding(ctx, node, node.id)
        if bound is None:
            return None
        expr, idx = bound
        if idx is None:
            return fold_specs(ctx, expr, consts, depth + 1)
        # tuple-unpack binding: `spec, in_specs = _seq_specs(mask)`
        if isinstance(expr, ast.Tuple) and idx < len(expr.elts):
            return fold_specs(ctx, expr.elts[idx], consts, depth + 1)
        if isinstance(expr, ast.Call):
            rets = _helper_returns(ctx, expr)
            if len(rets) == 1 and isinstance(rets[0], ast.Tuple) \
                    and idx < len(rets[0].elts):
                return fold_specs(ctx, rets[0].elts[idx], consts,
                                  depth + 1)
        return None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = fold_specs(ctx, node.left, consts, depth + 1)
        if left is None:
            return None
        right = fold_specs(ctx, node.right, consts, depth + 1)
        if right is not None and right.complete and not right.single:
            return SpecList(left.specs + right.specs,
                            left.complete and right.complete)
        # conditional tail (`+ ((mask_spec,) if ... else ())`): the left
        # prefix is certain, the total length is not
        return SpecList(list(left.specs), complete=False)
    if isinstance(node, ast.IfExp):
        return None                  # two arms, no single truth
    if isinstance(node, ast.Call):
        rets = _helper_returns(ctx, node)
        if len(rets) == 1:
            return fold_specs(ctx, rets[0], consts, depth + 1)
        # `jax.tree.map(lambda _: P(axis), tree)`: the one inner P call
        # IS the per-leaf spec
        d = dotted(node.func) or ""
        parts = d.split(".")
        if parts[-1] in ("map", "tree_map") and (
                "tree" in parts or "tree_util" in parts):
            inner = [n for n in ast.walk(node)
                     if _is_pspec_call(ctx, n)]
            if len(inner) == 1:
                spec = _fold_pspec(ctx, inner[0], consts)
                return SpecList([spec], complete=spec.complete,
                                single=True)
    return None


# ---------------------------------------------------------------------------
# shard_map site discovery
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardMapSite:
    """One ``shard_map`` entry into a manual region: the decorator form
    (``@functools.partial(jax.shard_map, mesh=…, in_specs=…,
    out_specs=…)``) or the call form (``fn = jax.shard_map(local,
    mesh=…, …)``)."""

    line: int
    body: Optional[ast.AST]          # FunctionDef/Lambda, when resolvable
    in_specs: Optional[ast.AST]
    out_specs: Optional[ast.AST]
    mesh_node: Optional[ast.AST]
    names: FrozenSet[str]            # names the wrapped callable binds to


def _is_shard_map_ref(ctx: ModuleContext, node: ast.AST) -> bool:
    d = dotted(node)
    if not d:
        return False
    leaf = d.split(".")[-1]
    if leaf != "shard_map":
        return False
    if "." in d:
        return True
    _, froms = ctx.jax_names
    return froms.get(d) == "shard_map"


def _site_kwargs(call: ast.Call, skip_args: int
                 ) -> Dict[str, Optional[ast.AST]]:
    out: Dict[str, Optional[ast.AST]] = {
        "mesh": None, "in_specs": None, "out_specs": None}
    pos = call.args[skip_args:]
    for name, i in (("mesh", 0), ("in_specs", 1), ("out_specs", 2)):
        if len(pos) > i:
            out[name] = pos[i]
    for k in call.keywords:
        if k.arg in out:
            out[k.arg] = k.value
    return out


def iter_shard_map_sites(ctx: ModuleContext) -> Iterator[ShardMapSite]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                d = dotted(dec.func) or ""
                if d.split(".")[-1] == "partial" and dec.args \
                        and _is_shard_map_ref(ctx, dec.args[0]):
                    kw = _site_kwargs(dec, skip_args=1)
                    yield ShardMapSite(dec.lineno, node, kw["in_specs"],
                                       kw["out_specs"], kw["mesh"],
                                       frozenset({node.name}))
        elif isinstance(node, ast.Call) \
                and _is_shard_map_ref(ctx, node.func):
            body: Optional[ast.AST] = None
            if node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Lambda):
                    body = a0
                elif isinstance(a0, ast.Name):
                    body = ctx._resolve_local_fn(node, a0.id)
            names: Set[str] = set()
            parent = ctx.parent(node)
            if isinstance(parent, ast.Assign):
                for t in parent.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
            kw = _site_kwargs(node, skip_args=1 if node.args else 0)
            yield ShardMapSite(node.lineno, body, kw["in_specs"],
                               kw["out_specs"], kw["mesh"],
                               frozenset(names))


def _merged_axis_env(ctx: ModuleContext
                     ) -> Tuple[Dict[str, int], Dict[str, str], str]:
    """(vocabulary, axis constants, mesh module path) — the in-file
    declarations merged over the package mesh module's, exactly
    ZL022's resolution."""
    vocab, consts = extract_axis_decls(ctx)
    pvocab, pconsts, mesh_path = package_axis_vocabulary(ctx.path)
    if os.path.abspath(ctx.path) == os.path.abspath(mesh_path or ""):
        pvocab, pconsts = {}, {}
    return {**pvocab, **vocab}, {**pconsts, **consts}, mesh_path


def _mesh_vars(ctx: ModuleContext,
               consts: Dict[str, str]) -> Dict[str, FrozenSet[str]]:
    """Variable name → axis set for every in-file ``Mesh(devices,
    (names…))``/``make_mesh(shape, names)`` construction bound to a
    name — the strict per-site binding ZL025 checks against (a
    shard_map over a 2-axis submesh binds only those two names, even
    when the package vocabulary is wider)."""
    out: Dict[str, FrozenSet[str]] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Assign) \
                or not isinstance(node.value, ast.Call):
            continue
        d = dotted(node.value.func) or ""
        if d.split(".")[-1] not in ("Mesh", "make_mesh"):
            continue
        names_arg: Optional[ast.AST] = None
        if len(node.value.args) > 1:
            names_arg = node.value.args[1]
        for k in node.value.keywords:
            if k.arg == "axis_names":
                names_arg = k.value
        if names_arg is None:
            continue
        axes = _fold_axis_names(names_arg, consts, ctx.tree)
        if not axes:
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                out[t.id] = frozenset(axes)
    return out


# ---------------------------------------------------------------------------
# collective call inspection (shared by the interpreter and the rules)
# ---------------------------------------------------------------------------

def _collective_leaf(node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    d = dotted(node.func)
    if not d:
        return None
    parts = d.split(".")
    if parts[-1] in _COLLECTIVES and "lax" in parts:
        return parts[-1]
    return None


def _collective_axis_elts(call: ast.Call, leaf: str) -> List[ast.AST]:
    """The axis-name argument's element expressions (tuple axes yield
    several); ``[]`` when the call has no axis argument."""
    pos = _COLLECTIVES[leaf]
    axis_arg: Optional[ast.AST] = None
    if len(call.args) > pos:
        axis_arg = call.args[pos]
    for k in call.keywords:
        if k.arg == "axis_name":
            axis_arg = k.value
    if axis_arg is None:
        return []
    if isinstance(axis_arg, (ast.Tuple, ast.List)):
        return list(axis_arg.elts)
    return [axis_arg]


def _collective_axes(call: ast.Call, leaf: str,
                     consts: Dict[str, str]
                     ) -> Tuple[List[str], bool]:
    """(resolved axis names, fully_resolved). A parameter-passed axis
    (ring attention's ``axis_name``) resolves nothing and is reported
    unresolved, not guessed."""
    elts = _collective_axis_elts(call, leaf)
    axes: List[str] = []
    ok = True
    for e in elts:
        ax = _resolve_axis_token(e, consts)
        if ax is None:
            # one level through a function-local alias
            # (`axis = mesh_lib.SEQ_AXIS`) is NOT attempted: locals may
            # rebind; consts are module-level truths
            ok = False
        else:
            axes.append(ax)
    if not elts:
        ok = False
    return axes, ok


@dataclasses.dataclass
class CollectiveSite:
    """One collective call site, for the catalog reconciliation."""
    name: str
    axes: Tuple[str, ...]     # resolved axis names; () = unresolvable
    path: str
    line: int


def iter_collective_sites(ctx: ModuleContext) -> Iterator[CollectiveSite]:
    _, consts, _ = _merged_axis_env(ctx)
    for node in ast.walk(ctx.tree):
        leaf = _collective_leaf(node)
        if leaf is None:
            continue
        axes, _ = _collective_axes(node, leaf, consts)
        yield CollectiveSite(leaf, tuple(axes), ctx.path, node.lineno)


# ---------------------------------------------------------------------------
# the abstract interpreter over shard_map bodies
# ---------------------------------------------------------------------------

#: literal constructors — identical content on every rank
_REPLICATED_CTORS = {"zeros", "ones", "full", "empty", "arange", "eye",
                     "array", "asarray", "linspace", "zeros_like",
                     "ones_like", "full_like", "empty_like", "identity"}

#: elementwise / shape ops the walker propagates a joined state through
_ELEMENTWISE = {"where", "select", "add", "subtract", "multiply", "divide",
                "true_divide", "maximum", "minimum", "exp", "log", "log2",
                "sqrt", "square", "abs", "negative", "tanh", "sigmoid",
                "clip", "power", "mod", "remainder", "logical_and",
                "logical_or", "logical_not", "equal", "not_equal",
                "greater", "greater_equal", "less", "less_equal", "isnan",
                "isfinite", "nan_to_num", "astype", "stop_gradient"}

#: array-dim reductions/reshapes — mesh distribution unchanged, but the
#: seed-time axis→dim map no longer applies
_DIM_SCRAMBLERS = {"sum", "mean", "max", "min", "prod", "reshape",
                   "transpose", "swapaxes", "squeeze", "expand_dims",
                   "ravel", "flatten", "moveaxis", "broadcast_to",
                   "concatenate", "stack", "split", "take", "cumsum",
                   "argmax", "argmin", "softmax", "logsumexp"}

_DOT_LIKE = {"dot", "matmul", "tensordot", "dot_general", "einsum"}


class SpmdInterp:
    """Straight-line abstract interpreter over one shard_map body —
    the same shape as ``device.Interp``: statements in order (branch
    arms applied last-writer-wins), one level of local-helper
    resolution, everything unprovable degrading to ``unknown``."""

    def __init__(self, ctx: ModuleContext, consts: Dict[str, str],
                 depth: int = 0):
        self.ctx = ctx
        self.consts = consts
        self.depth = depth
        self.returns: List[Tuple[ast.AST, DistState]] = []

    # -- entry points -------------------------------------------------
    def run_function(self, fn: ast.AST,
                     seeds: Dict[str, DistState]
                     ) -> Tuple[Dict[str, DistState],
                                List[Tuple[ast.AST, DistState]]]:
        env: Dict[str, DistState] = dict(seeds)
        if isinstance(fn, ast.Lambda):
            self.returns.append((fn.body, self.eval(fn.body, env)))
            return env, self.returns
        self.walk_stmts(fn.body, env)
        return env, self.returns

    # -- statements ---------------------------------------------------
    def walk_stmts(self, stmts: Sequence[ast.stmt],
                   env: Dict[str, DistState]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.Assign):
                val = self.eval(stmt.value, env)
                for t in stmt.targets:
                    self._bind_target(t, val, env)
            elif isinstance(stmt, ast.AugAssign) \
                    and isinstance(stmt.target, ast.Name):
                old = env.get(stmt.target.id, DistState.unknown())
                env[stmt.target.id] = join(old,
                                           self.eval(stmt.value, env))
            elif isinstance(stmt, ast.AnnAssign) \
                    and isinstance(stmt.target, ast.Name) \
                    and stmt.value is not None:
                env[stmt.target.id] = self.eval(stmt.value, env)
            elif isinstance(stmt, ast.Return):
                state = self.eval(stmt.value, env) \
                    if stmt.value is not None else DistState.replicated()
                self.returns.append((stmt, state))
            elif isinstance(stmt, ast.If):
                self.walk_stmts(stmt.body, env)
                self.walk_stmts(stmt.orelse, env)
            elif isinstance(stmt, (ast.For, ast.While)):
                if isinstance(stmt, ast.For) \
                        and isinstance(stmt.target, ast.Name):
                    env[stmt.target.id] = DistState.unknown()
                self.walk_stmts(stmt.body, env)
                self.walk_stmts(stmt.orelse, env)
            elif isinstance(stmt, ast.With):
                self.walk_stmts(stmt.body, env)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef, ast.Expr, ast.Pass,
                                   ast.Import, ast.ImportFrom)):
                continue
            elif isinstance(stmt, ast.Try):
                self.walk_stmts(stmt.body, env)
                for h in stmt.handlers:
                    self.walk_stmts(h.body, env)
                self.walk_stmts(stmt.finalbody, env)
            # raise/assert/del/global: no value flow tracked

    def _bind_target(self, target: ast.AST, val: DistState,
                     env: Dict[str, DistState]) -> None:
        if isinstance(target, ast.Name):
            env[target.id] = val
        elif isinstance(target, ast.Tuple):
            elts = val.elts
            for i, e in enumerate(target.elts):
                if not isinstance(e, ast.Name):
                    continue
                if elts is not None and i < len(elts):
                    env[e.id] = elts[i]
                elif val.known and val.is_replicated:
                    env[e.id] = DistState.replicated()
                else:
                    env[e.id] = DistState.unknown()

    # -- expressions --------------------------------------------------
    def eval(self, node: ast.AST, env: Dict[str, DistState]) -> DistState:
        if isinstance(node, ast.Constant):
            return DistState.replicated()
        if isinstance(node, ast.Name):
            return env.get(node.id, DistState.unknown())
        if isinstance(node, (ast.Tuple, ast.List)):
            elts = tuple(self.eval(e, env) for e in node.elts)
            return DistState(
                sharded=frozenset().union(*(e.sharded for e in elts))
                if elts else _EMPTY,
                partial=frozenset().union(*(e.partial for e in elts))
                if elts else _EMPTY,
                known=all(e.known for e in elts), elts=elts)
        if isinstance(node, ast.UnaryOp):
            return self.eval(node.operand, env)
        if isinstance(node, ast.BinOp):
            left = self.eval(node.left, env)
            right = self.eval(node.right, env)
            if isinstance(node.op, ast.MatMult):
                return dot_transfer(left, right)
            return join(left, right)
        if isinstance(node, ast.BoolOp):
            return join_all([self.eval(v, env) for v in node.values])
        if isinstance(node, ast.Compare):
            return join_all([self.eval(node.left, env)]
                            + [self.eval(c, env)
                               for c in node.comparators]).drop_dims()
        if isinstance(node, ast.IfExp):
            return join(self.eval(node.body, env),
                        self.eval(node.orelse, env))
        if isinstance(node, ast.Subscript):
            return self.eval(node.value, env).drop_dims()
        if isinstance(node, ast.Attribute):
            # `x.T`, `x.dtype`, `x.shape` — follow the receiver; shapes
            # are replicated in a manual region (same block everywhere)
            if node.attr in ("shape", "dtype", "ndim", "size"):
                return DistState.replicated()
            base = self.eval(node.value, env)
            return base if base.known else DistState.unknown()
        if isinstance(node, ast.Call):
            return self._eval_call(node, env)
        if isinstance(node, ast.Starred):
            return self.eval(node.value, env)
        return DistState.unknown()

    def _eval_call(self, call: ast.Call,
                   env: Dict[str, DistState]) -> DistState:
        leaf = _collective_leaf(call)
        if leaf is not None:
            return self._collective_transfer(call, leaf, env)
        d = dotted(call.func) or ""
        parts = d.split(".")
        name = parts[-1] if parts else ""
        # method call on a TRACKED value: `x.astype(...)`,
        # `x.reshape(...)` — only when the receiver is a name bound in
        # this environment, so a module-attribute call (`jnp.where`)
        # falls through to the function branches instead of evaluating
        # the module alias itself (always unknown)
        if isinstance(call.func, ast.Attribute) \
                and isinstance(call.func.value, ast.Name) \
                and call.func.value.id in env and name in (
                _ELEMENTWISE | _DIM_SCRAMBLERS):
            recv = env[call.func.value.id]
            args = join_all([recv] + [self.eval(a, env)
                                      for a in call.args])
            return args if name in _ELEMENTWISE else args.drop_dims()
        if name in _REPLICATED_CTORS:
            return DistState.replicated()
        if name in _DOT_LIKE:
            operands = [a for a in call.args
                        if not (isinstance(a, ast.Constant)
                                and isinstance(a.value, str))]
            states = [self.eval(a, env) for a in operands]
            if len(states) >= 2:
                out = states[0]
                for s in states[1:]:
                    out = dot_transfer(out, s)
                return out
            return join_all(states).drop_dims() if states \
                else DistState.unknown()
        if name in _ELEMENTWISE:
            states = [self.eval(a, env) for a in call.args]
            return join_all(states) if states else DistState.unknown()
        if name in _DIM_SCRAMBLERS:
            states = [self.eval(a, env) for a in call.args]
            return (join_all(states) if states
                    else DistState.unknown()).drop_dims()
        # one-level local helper: bind arg states, walk, join returns —
        # the helper-call carry (psum inside a helper still clears)
        if self.depth < 1 and isinstance(call.func, ast.Name):
            fn = self.ctx._resolve_local_fn(call, call.func.id)
            if fn is not None and isinstance(fn, (ast.FunctionDef,
                                                  ast.AsyncFunctionDef)):
                return self._call_helper(fn, call, env)
        return DistState.unknown()

    def _call_helper(self, fn: ast.AST, call: ast.Call,
                     env: Dict[str, DistState]) -> DistState:
        a = fn.args
        params = [p.arg for p in list(a.posonlyargs) + list(a.args)]
        if a.vararg or a.kwarg:
            return DistState.unknown()
        seeds: Dict[str, DistState] = {p: DistState.unknown()
                                       for p in params}
        for p in params[len(params) - len(a.defaults):]:
            seeds[p] = DistState.replicated()   # literal defaults
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                return DistState.unknown()
            if i < len(params):
                seeds[params[i]] = self.eval(arg, env)
        for k in call.keywords:
            if k.arg in seeds:
                seeds[k.arg] = self.eval(k.value, env)
        sub = SpmdInterp(self.ctx, self.consts, depth=self.depth + 1)
        _, rets = sub.run_function(fn, seeds)
        if not rets:
            return DistState.unknown()
        return join_all([s for _, s in rets])

    def _collective_transfer(self, call: ast.Call, leaf: str,
                             env: Dict[str, DistState]) -> DistState:
        axes, resolved = _collective_axes(call, leaf, self.consts)
        if leaf == "axis_size":
            return DistState.replicated()
        if leaf == "axis_index":
            if resolved and axes:
                return DistState.sharded_over(axes)
            return DistState.unknown()
        operand = (self.eval(call.args[0], env) if call.args
                   else DistState.unknown())
        if not resolved:
            return DistState.unknown()
        if leaf in ("psum", "pmean", "pmax", "pmin"):
            return operand.reduce_over(axes)
        if leaf == "psum_scatter":
            return operand.scatter_over(axes)
        if leaf == "all_gather":
            return operand.gather_over(axes)
        if leaf in ("ppermute", "pbroadcast", "pshuffle", "all_to_all"):
            return operand.drop_dims()
        return DistState.unknown()


def interp_source_fn(source: str, fn_name: str,
                     seeds: Dict[str, DistState],
                     path: str = "<spmd>"
                     ) -> Tuple[Dict[str, DistState],
                                List[Tuple[ast.AST, DistState]]]:
    """Test/exploration helper: abstract-interpret one module-level
    function of ``source`` with the given parameter seeds; returns the
    final environment and the (node, state) return list. No fixture
    package or mesh module required — the lattice unit tests drive the
    transfer functions through this."""
    ctx = ModuleContext(path, source)
    consts = _merged_axis_env(ctx)[1]
    fn = None
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == fn_name:
            fn = node
            break
    if fn is None:
        raise ValueError(f"no function {fn_name!r} in source")
    return SpmdInterp(ctx, consts).run_function(fn, seeds)


def _seed_env(body: ast.AST, ins: Optional[SpecList]
              ) -> Dict[str, DistState]:
    """Parameter seeds from a folded ``in_specs``: spec axes become the
    sharded set (with their dim positions); anything past the proven
    prefix — or under an unfoldable spec — is unknown."""
    if isinstance(body, ast.Lambda):
        a = body.args
    else:
        a = body.args
    params = [p.arg for p in list(a.posonlyargs) + list(a.args)]
    env: Dict[str, DistState] = {}
    for i, p in enumerate(params):
        spec: Optional[SpecInfo] = None
        if ins is not None:
            if ins.single:
                spec = ins.specs[0]
            elif i < len(ins.specs):
                spec = ins.specs[i]
            elif ins.complete:
                spec = None
        if spec is None:
            env[p] = DistState.unknown()
        else:
            env[p] = DistState.sharded_over(spec.axes(),
                                            spec.dim_index())
    if a.vararg:
        env[a.vararg.arg] = DistState.unknown()
    for p in a.kwonlyargs:
        env[p.arg] = DistState.unknown()
    return env


# ---------------------------------------------------------------------------
# ZL025 — collective axis validity (+ the catalog project half)
# ---------------------------------------------------------------------------

@register
class CollectiveAxisBinding(Rule):
    """**Collective axis validity.** A collective inside a
    ``shard_map`` body must name an axis the enclosing mesh binds: when
    the site's ``mesh=`` argument resolves to an in-file
    ``Mesh(devices, (names…))`` construction, its axis tuple is the
    binding set; otherwise the merged ZL022 vocabulary stands in. A
    ``psum`` over an unbound axis passes every single-chip CPU test and
    raises ``NameError: unbound axis`` only at trace time on a real
    mesh — and ZL022 cannot catch the submesh case, where the axis IS
    in the package vocabulary but the mesh under this shard_map does
    not carry it. Parameter-passed axis names (ring attention's
    ``axis_name``) are unresolvable and skipped: precision over
    recall. The project pass adds the collective-catalog
    reconciliation against docs/guides/PARALLELISM.md."""

    id = "ZL025"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        vocab, consts, _ = _merged_axis_env(ctx)
        mesh_vars = _mesh_vars(ctx, consts)
        sev = ERROR if _in_package(ctx.path) else WARNING
        for site in iter_shard_map_sites(ctx):
            if site.body is None:
                continue
            bound: Optional[FrozenSet[str]] = None
            if isinstance(site.mesh_node, ast.Name):
                bound = mesh_vars.get(site.mesh_node.id)
            if bound is None:
                bound = frozenset(vocab) or None
            if bound is None:
                continue
            for call in ast.walk(site.body):
                leaf = _collective_leaf(call)
                if leaf is None:
                    continue
                axes, _ = _collective_axes(call, leaf, consts)
                for ax in axes:
                    if ax not in bound:
                        yield self.finding(
                            ctx, call.lineno,
                            f"{leaf} over axis '{ax}' inside a shard_map "
                            f"whose mesh binds only "
                            f"{sorted(bound)} — an unbound collective "
                            f"axis fails at trace time on a real mesh "
                            f"only", sev)


def parse_collective_catalog(path: str
                             ) -> List[Tuple[str, Tuple[str, ...],
                                             str, int]]:
    """PARALLELISM.md "Collective catalog": rows of ``(collective
    name, documented axes, path, line)``; an axis cell without
    backticked axis names (``caller``/``—``) documents a
    caller-supplied axis and matches any axis."""
    from .contracts import _cell_tokens, md_table_column
    with open(path, encoding="utf-8") as f:
        text = f.read()
    out: List[Tuple[str, Tuple[str, ...], str, int]] = []
    for cell, line, rest in md_table_column(text, path, "collective"):
        names = [t for t in _cell_tokens(cell) if t and " " not in t]
        axis_cell = rest.split(" | ")[0] if rest else ""
        axes = tuple(t for t in _cell_tokens(axis_cell)
                     if t and " " not in t and t == t.lower()
                     and "`" + t + "`" in axis_cell)
        for name in names:
            out.append((name, axes, path, line))
    return out


def _is_collective_module(path: str) -> bool:
    parts = os.path.normpath(os.path.abspath(path)).split(os.sep)
    return "parallel" in parts or "ops" in parts


@register_project
class CollectiveCatalogDrift(ProjectRule):
    """**Collective-catalog reconciliation (code↔PARALLELISM.md).**
    Every collective call site in the package's ``parallel/`` and
    ``ops/`` trees must have a documented row (name + axis semantics)
    in the PARALLELISM.md collective catalog, and every cataloged
    (collective, axis) pair must correspond to a live call site — a
    collective someone deletes must take its documentation with it,
    and a new one must state which axis it rides and why. Sites whose
    axis is caller-supplied (ring attention's ``axis_name`` parameter)
    match any row of that collective. Inert when the scanned tree has
    no such call sites (foreign/fixture packages)."""

    id = "ZL025"
    severity = ERROR

    def check(self, project: ProjectContext) -> Iterator[Finding]:
        from .contracts import _missing_catalog
        sites: List[CollectiveSite] = []
        for ctx in project.modules:
            if not _is_collective_module(ctx.path):
                continue
            sites.extend(iter_collective_sites(ctx))
        if not sites:
            return
        path = project.catalog_path("collectives")
        if path is None:
            yield _missing_catalog(self, project, "collectives")
            return
        rows = parse_collective_catalog(path)
        by_name: Dict[str, List[Tuple[Tuple[str, ...], str, int]]] = {}
        for name, axes, rpath, line in rows:
            by_name.setdefault(name, []).append((axes, rpath, line))
        covered: Set[Tuple[str, Optional[str]]] = set()
        for s in sites:
            doc_rows = by_name.get(s.name, [])
            if s.axes:
                for ax in s.axes:
                    hit = any(ax in axes or not axes
                              for axes, _, _ in doc_rows)
                    if hit:
                        covered.add((s.name, ax))
                        covered.add((s.name, None))
                    else:
                        yield Finding(
                            self.id, ERROR, s.path, s.line,
                            f"collective {s.name} over axis '{ax}' has "
                            f"no row in {os.path.basename(path)}'s "
                            f"collective catalog — document the axis "
                            f"semantics (what the collective does to "
                            f"values on that axis)")
            else:
                if doc_rows:
                    # a caller-supplied axis exercises every documented
                    # axis of its collective
                    for axes, _, _ in doc_rows:
                        covered.add((s.name, None))
                        for ax in axes:
                            covered.add((s.name, ax))
                else:
                    yield Finding(
                        self.id, ERROR, s.path, s.line,
                        f"collective {s.name} (caller-supplied axis) "
                        f"has no row in {os.path.basename(path)}'s "
                        f"collective catalog — add one")
        for name, doc_rows in sorted(by_name.items()):
            for axes, rpath, line in doc_rows:
                if not axes:
                    if (name, None) not in covered:
                        yield Finding(
                            self.id, ERROR, rpath, line,
                            f"collective {name} is cataloged here but "
                            f"no parallel/ or ops/ call site uses it — "
                            f"prune the row or restore the code")
                    continue
                for ax in axes:
                    if (name, ax) not in covered:
                        yield Finding(
                            self.id, ERROR, rpath, line,
                            f"collective {name} over axis '{ax}' is "
                            f"cataloged here but no parallel/ or ops/ "
                            f"call site uses it — prune the axis or "
                            f"restore the code")


# ---------------------------------------------------------------------------
# ZL026 — unreduced-output hazard
# ---------------------------------------------------------------------------

_STACKING_LEAVES = {"stack", "concatenate", "vstack", "hstack", "dstack"}


def _contains(ctx: ModuleContext, fn: ast.AST, node: ast.AST) -> bool:
    """Lexical containment: ``node`` sits anywhere under ``fn``."""
    cur: Optional[ast.AST] = node
    while cur is not None:
        if cur is fn:
            return True
        cur = ctx.parent(cur)
    return False


def _is_stacking_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = dotted(node.func) or ""
    parts = d.split(".")
    if parts[-1] in _STACKING_LEAVES:
        return True
    return parts[-1] in ("map", "tree_map") and (
        "tree" in parts or "tree_util" in parts)


def _is_pinned_call(ctx: ModuleContext, node: ast.AST) -> bool:
    """``with_sharding_constraint(...)`` directly, or a local helper
    whose body applies it (the ``_pin_replicated`` idiom)."""
    if not isinstance(node, ast.Call):
        return False
    d = dotted(node.func) or ""
    if d.split(".")[-1] == "with_sharding_constraint":
        return True
    if isinstance(node.func, ast.Name):
        fn = ctx._resolve_local_fn(node, node.func.id)
        if fn is not None:
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call) and (
                        dotted(sub.func) or "").split(".")[-1] \
                        == "with_sharding_constraint":
                    return True
    return False


@register
class UnreducedOutputHazard(Rule):
    """**Unreduced-output hazard.** Two provable forms of the PR-14
    gpipe bug class (values leaving/entering a manual region carrying
    unreduced partial sums, which ``check_vma=False`` ships silently):

    1. *Body side*: the abstract interpreter proves a returned value
       carries ``partial_sum(axis)`` (e.g. a row-parallel dot that was
       never ``psum``-ed) while the matching ``out_specs`` entry claims
       replication or sharding on that axis — the blocks get
       concatenated or rank-0-picked instead of summed.
    2. *Caller side*: a shard_map-wrapped function invoked from
       jit-staged code with an operand computed AT TRACE TIME
       (``jnp.stack``/``jax.tree.map``) and not routed through
       ``with_sharding_constraint`` — GSPMD may commit a layout that
       disagrees with ``in_specs`` and the value enters the region
       unreduced (×data-axis-size per stage, the exact bug
       ``parallel/pipeline.py``'s ``_pin_replicated`` now guards).

    Everything unprovable (unresolvable specs, foreign calls, scan
    carries) degrades to ``unknown`` and is never accused."""

    id = "ZL026"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        _, consts, _ = _merged_axis_env(ctx)
        sev = ERROR if _in_package(ctx.path) else WARNING
        sites = list(iter_shard_map_sites(ctx))
        for site in sites:
            yield from self._check_body(ctx, site, consts, sev)
        yield from self._check_callers(ctx, sites, sev)

    # -- prong 1: partial sums escaping through out_specs -------------
    def _check_body(self, ctx: ModuleContext, site: ShardMapSite,
                    consts: Dict[str, str],
                    sev: str) -> Iterator[Finding]:
        if site.body is None:
            return
        ins = fold_specs(ctx, site.in_specs, consts)
        outs = fold_specs(ctx, site.out_specs, consts)
        if outs is None:
            return
        env = _seed_env(site.body, ins)
        _, returns = SpmdInterp(ctx, consts).run_function(site.body, env)
        for node, state in returns:
            yield from self._match_out(ctx, node, state, outs, sev)

    def _match_out(self, ctx: ModuleContext, node: ast.AST,
                   state: DistState, outs: SpecList,
                   sev: str) -> Iterator[Finding]:
        pairs: List[Tuple[DistState, Optional[SpecInfo]]] = []
        if outs.single:
            spec = outs.specs[0]
            if state.elts is not None:
                pairs = [(e, spec) for e in state.elts]
            else:
                pairs = [(state, spec)]
        elif state.elts is not None and outs.complete \
                and len(state.elts) == len(outs.specs):
            pairs = list(zip(state.elts, outs.specs))
        else:
            return
        line = getattr(node, "lineno", 0) or 0
        for st, spec in pairs:
            if spec is None or not st.known:
                continue
            claimed = spec.axes()
            for ax in sorted(st.partial):
                if ax in claimed:
                    yield self.finding(
                        ctx, line,
                        f"shard_map body returns a value still carrying "
                        f"an unreduced partial sum over axis '{ax}' but "
                        f"out_specs shard that axis — the blocks would "
                        f"be laid out side-by-side, not summed; "
                        f"jax.lax.psum_scatter(..., '{ax}') is the "
                        f"matching reduction", sev)
                elif spec.complete:
                    yield self.finding(
                        ctx, line,
                        f"shard_map body returns a value still carrying "
                        f"an unreduced partial sum over axis '{ax}' but "
                        f"out_specs claim replication on it — insert "
                        f"jax.lax.psum(..., '{ax}') before returning; "
                        f"with check_vma=False this ships wrong numbers "
                        f"silently", sev)
            for ax in sorted(st.sharded - st.partial):
                if spec.complete and ax not in claimed:
                    yield self.finding(
                        ctx, line,
                        f"shard_map body returns a device-varying value "
                        f"(sharded over '{ax}') but out_specs claim "
                        f"replication on that axis — ranks disagree and "
                        f"check_vma=False picks one silently; gather or "
                        f"reduce over '{ax}' first", sev)

    # -- prong 2: unpinned trace-time operands entering the region ----
    def _check_callers(self, ctx: ModuleContext,
                       sites: List[ShardMapSite],
                       sev: str) -> Iterator[Finding]:
        wrapped = frozenset().union(*(s.names for s in sites)) \
            if sites else frozenset()
        if not wrapped:
            return
        staged = staged_fns(ctx)
        for call in ast.walk(ctx.tree):
            if not isinstance(call, ast.Call) \
                    or not isinstance(call.func, ast.Name) \
                    or call.func.id not in wrapped:
                continue
            if not any(_contains(ctx, fn, call) for fn in staged):
                continue        # eager operands carry committed layouts
            for arg in call.args:
                verdict = self._classify_operand(ctx, arg)
                if verdict is None:
                    continue
                yield self.finding(
                    ctx, call.lineno,
                    f"operand computed inside this jit ({verdict}) "
                    f"enters the shard_map manual region without a "
                    f"committed layout — GSPMD may pick one that "
                    f"disagrees with in_specs and the value arrives "
                    f"UNREDUCED (×axis-size; the gpipe stacked-stage-"
                    f"params bug). Pin it replicated with "
                    f"jax.lax.with_sharding_constraint before the "
                    f"call", sev)

    def _classify_operand(self, ctx: ModuleContext, arg: ast.AST,
                          depth: int = 0) -> Optional[str]:
        """A human-readable producer description when ``arg`` is a
        trace-time stacking intermediate with no layout pin; None when
        pinned or not provably hazardous."""
        if depth > 2:
            return None
        if _is_pinned_call(ctx, arg):
            return None
        if _is_stacking_call(arg):
            return f"{dotted(arg.func)} at line {arg.lineno}"
        if isinstance(arg, ast.Name):
            bound = _single_binding(ctx, arg, arg.id)
            if bound is not None and bound[1] is None:
                return self._classify_operand(ctx, bound[0], depth + 1)
        return None


# ---------------------------------------------------------------------------
# ZL027 — divergent collectives under traced control flow
# ---------------------------------------------------------------------------

@register
class DivergentCollective(Rule):
    """**Divergent collectives under traced control flow.** Collectives
    are rendezvous points: EVERY rank along the axis must reach the
    same collective the same number of times. A collective inside only
    one branch of a ``lax.cond`` (or anywhere inside a
    ``lax.while_loop``, whose traced trip count can differ per rank
    when the predicate is device-varying) means some ranks enter the
    rendezvous and the rest never arrive — an SPMD deadlock that no
    single-chip CPU test can reproduce. ``lax.scan``/``fori_loop``
    bodies are exempt: their trip counts are static, every rank runs
    the identical schedule (the ring/GPipe pattern). A branch that
    does not resolve to a local function is skipped — divergence must
    be provable."""

    id = "ZL027"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        _, consts, _ = _merged_axis_env(ctx)
        sev = ERROR if _in_package(ctx.path) else WARNING
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func) or ""
            parts = d.split(".")
            leaf = parts[-1]
            if "lax" not in parts:
                continue
            if leaf == "while_loop":
                for role, fn_arg in (("cond", node.args[0:1]),
                                     ("body", node.args[1:2])):
                    fn = self._resolve_branch(ctx, node,
                                              fn_arg[0]) if fn_arg \
                        else None
                    if fn is None:
                        continue
                    for call, cleaf, axes in self._collectives_in(
                            ctx, fn, consts):
                        yield self.finding(
                            ctx, call.lineno,
                            f"{cleaf} inside a lax.while_loop {role} — "
                            f"the traced trip count can differ per "
                            f"rank, so ranks that exit earlier never "
                            f"reach the collective: SPMD deadlock. "
                            f"Hoist it out of the loop or use a "
                            f"static-trip lax.scan", sev)
            elif leaf == "cond" and len(node.args) >= 3:
                t = self._resolve_branch(ctx, node, node.args[1])
                f = self._resolve_branch(ctx, node, node.args[2])
                if t is None or f is None:
                    continue
                tcoll = list(self._collectives_in(ctx, t, consts))
                fcoll = list(self._collectives_in(ctx, f, consts))
                tkeys = {(c[1], frozenset(c[2])) for c in tcoll}
                fkeys = {(c[1], frozenset(c[2])) for c in fcoll}
                for branch, other_keys, arm in ((tcoll, fkeys, "true"),
                                                (fcoll, tkeys, "false")):
                    for call, cleaf, axes in branch:
                        if (cleaf, frozenset(axes)) in other_keys:
                            continue
                        yield self.finding(
                            ctx, call.lineno,
                            f"{cleaf} reachable only in the {arm} "
                            f"branch of a lax.cond — ranks whose "
                            f"predicate takes the other branch never "
                            f"reach the collective: SPMD deadlock. "
                            f"Run the collective in both branches (or "
                            f"outside the cond)", sev)

    @staticmethod
    def _resolve_branch(ctx: ModuleContext, call: ast.Call,
                        arg: ast.AST) -> Optional[ast.AST]:
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            return ctx._resolve_local_fn(call, arg.id)
        return None

    @staticmethod
    def _collectives_in(ctx: ModuleContext, fn: ast.AST,
                        consts: Dict[str, str]
                        ) -> Iterator[Tuple[ast.Call, str, List[str]]]:
        for sub in ast.walk(fn):
            leaf = _collective_leaf(sub)
            if leaf is None:
                continue
            axes, _ = _collective_axes(sub, leaf, consts)
            yield sub, leaf, axes


# ---------------------------------------------------------------------------
# ZL028 — PartitionSpec hygiene
# ---------------------------------------------------------------------------

@register
class PartitionSpecHygiene(Rule):
    """**PartitionSpec hygiene.** Structural spec facts that are
    provable without a mesh: (a) a mesh axis used twice in one
    ``PartitionSpec`` — jax rejects duplicate axes in a spec at trace
    time, on a multi-chip mesh only; (b) arity at ``shard_map`` sites
    where both sides are proven — an ``in_specs`` tuple whose length
    differs from the body's parameter count, or ``out_specs`` whose
    length differs from the returned tuple's (conditional spec tails
    and ``*args`` bodies are unprovable and skipped). Axis-name
    VOCABULARY membership stays ZL022's job — one rule per fact, one
    suppression per intent."""

    id = "ZL028"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        _, consts, _ = _merged_axis_env(ctx)
        sev = ERROR if _in_package(ctx.path) else WARNING
        for node in ast.walk(ctx.tree):
            if _is_pspec_call(ctx, node):
                spec = _fold_pspec(ctx, node, consts)
                seen: Set[str] = set()
                for d in spec.dims:
                    for ax in d:
                        if ax in seen:
                            yield self.finding(
                                ctx, node.lineno,
                                f"axis '{ax}' appears twice in one "
                                f"PartitionSpec — jax rejects a "
                                f"duplicate mesh axis in a spec at "
                                f"trace time (on a real mesh only)",
                                sev)
                        seen.add(ax)
        for site in iter_shard_map_sites(ctx):
            yield from self._check_arity(ctx, site, consts, sev)

    def _check_arity(self, ctx: ModuleContext, site: ShardMapSite,
                     consts: Dict[str, str],
                     sev: str) -> Iterator[Finding]:
        body = site.body
        if body is None:
            return
        a = body.args
        if a.vararg or a.kwarg or a.kwonlyargs or a.defaults:
            return
        nparams = len(a.posonlyargs) + len(a.args)
        ins = fold_specs(ctx, site.in_specs, consts)
        if ins is not None and ins.complete and not ins.single \
                and len(ins.specs) != nparams:
            yield self.finding(
                ctx, site.line,
                f"shard_map in_specs has {len(ins.specs)} spec(s) but "
                f"the body takes {nparams} parameter(s) — the mismatch "
                f"only fails at trace time", sev)
        outs = fold_specs(ctx, site.out_specs, consts)
        if outs is None or outs.single or not outs.complete:
            return
        ret_lens: Set[int] = set()
        if isinstance(body, ast.Lambda):
            ret_lens.add(len(body.body.elts)
                         if isinstance(body.body, ast.Tuple) else 1)
        else:
            for n in ast.walk(body):
                if isinstance(n, ast.Return) and n.value is not None \
                        and ctx._enclosing_scope(n) is body:
                    ret_lens.add(len(n.value.elts)
                                 if isinstance(n.value, ast.Tuple)
                                 else 1)
        if len(ret_lens) == 1:
            (L,) = ret_lens
            # a 1-element return against N specs is unprovable (the
            # body may return a tuple-valued expression); only a
            # PROVEN tuple literal of the wrong length is accused
            if L > 1 and L != len(outs.specs):
                yield self.finding(
                    ctx, site.line,
                    f"shard_map out_specs has {len(outs.specs)} "
                    f"spec(s) but the body returns a {L}-tuple — the "
                    f"mismatch only fails at trace time", sev)
