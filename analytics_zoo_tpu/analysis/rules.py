"""zoolint per-file rules ZL001–ZL015 — the JAX/TPU hazards that bite
this stack (the whole-project rules ZL016–ZL020 live in ``project.py``/
``contracts.py``; the device-semantics pass ZL021–ZL024 in
``device.py``; the SPMD collective-semantics pass ZL025–ZL028 in
``spmd.py``).

Every rule documents its rationale in the class docstring (surfaced by
``--list-rules`` and docs/guides/STATIC_ANALYSIS.md). Severities:

* ``error``   — gates CI (``tests/test_zoolint.py`` asserts zero). The
  heuristic rules ZL005/ZL008 started warn-only and were promoted once
  the existing findings were triaged (every remaining site carries a
  justified suppression) — see the ROADMAP follow-up.
* ``warning`` — advisory only (ZL007's swallow-pass form outside the
  serving/inference retry paths).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .core import (ERROR, WARNING, Finding, ModuleContext, Rule, dotted,
                   param_names, register)


def _walk_skipping(root: ast.AST, skip_types=(),
                   skip_nodes=frozenset()) -> Iterator[ast.AST]:
    """``ast.walk`` that does not descend into the given node types or
    specific node ids (the root itself is always yielded)."""
    stack = [root]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, skip_types) or id(child) in skip_nodes:
                continue
            stack.append(child)


_CALLBACK_LEAVES = {"callback", "pure_callback", "io_callback"}


def _callback_hosted_fns(ctx: ModuleContext, fn: ast.AST) -> Set[int]:
    """ids of nested functions/lambdas passed to a host-callback API
    (``jax.debug.callback`` / ``jax.pure_callback`` / ``io_callback``) —
    their bodies run on the HOST at execution time, not at trace, so the
    under-jit effect/sync rules must not flag them."""
    out: Set[int] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if not d or d.rsplit(".", 1)[-1] not in _CALLBACK_LEAVES:
            continue
        for arg in node.args:
            if isinstance(arg, ast.Lambda):
                out.add(id(arg))
            elif isinstance(arg, ast.Name):
                target = ctx._resolve_local_fn(node, arg.id)
                if target is not None:
                    out.add(id(target))
    return out


# ---------------------------------------------------------------------------
# ZL001 — PRNG key reuse
# ---------------------------------------------------------------------------

# jax.random callables that do NOT consume their key: ``fold_in`` derives
# without consuming (the idiomatic per-step schedule used across parallel/
# and the keras engine), and the constructors make fresh keys. ``split``
# is deliberately absent — it both consumes and is checked against earlier
# consumption, and _key_call classifies it before this set is consulted.
_NON_CONSUMING = {"fold_in", "key", "PRNGKey", "wrap_key_data",
                  "key_data", "clone", "key_impl"}


@register
class PRNGKeyReuse(Rule):
    """A ``jax.random`` key passed to a second sampler (or re-``split``)
    without an intervening ``split``/reassignment replays the exact same
    random stream — dropout masks repeat, initializers correlate, and the
    bug is invisible at runtime because every draw still *looks* random.
    Loop bodies are scanned twice so a loop-invariant key consumed each
    iteration is caught as well."""

    id = "ZL001"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for scope in [ctx.tree] + list(ctx.functions()):
            findings: List[Finding] = []
            self._walk(ctx, scope.body, {}, findings)
            yield from findings
        # lambda bodies are their own scope (params are fresh bindings, so
        # they start with an empty consumed-set), but a key consumed twice
        # WITHIN one body is still reuse on every call
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Lambda):
                findings = []
                self._scan_expr(ctx, node.body, {}, findings)
                yield from findings

    # -- statement-ordered dataflow walk ------------------------------------
    def _key_call(self, ctx: ModuleContext,
                  call: ast.Call) -> Optional[Tuple[str, str]]:
        """(kind, keyname) for a ``jax.random.X(key, ...)`` call with a
        simple Name key, where kind is 'sampler' | 'split' | 'other'."""
        d = dotted(call.func)
        if not d or "." not in d:
            return None
        prefix, leaf = d.rsplit(".", 1)
        if prefix not in ctx.aliases["jax.random"]:
            return None
        # the key rides as the first positional OR the `key=` keyword —
        # `key` is positional-or-keyword in every jax.random sampler
        key_node: Optional[ast.AST] = None
        if call.args:
            key_node = call.args[0]
        else:
            for kw in call.keywords:
                if kw.arg == "key":
                    key_node = kw.value
                    break
        if not isinstance(key_node, ast.Name):
            return None
        name = key_node.id
        if leaf == "split":
            return "split", name
        if leaf in _NON_CONSUMING:
            return "other", name
        return "sampler", name

    _COMPS = (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)

    def _scan_expr(self, ctx, node, consumed: Dict[str, int],
                   findings: List[Finding],
                   comp_bound: frozenset = frozenset()) -> None:
        stack = [(node, comp_bound)]    # (node, names bound per-iteration)
        while stack:
            sub, comp_bound = stack.pop()
            if isinstance(sub, ast.Lambda):
                continue    # own scope: params shadow (visited separately)
            if isinstance(sub, ast.IfExp):
                # mutually-exclusive arms: at most one consumes at
                # runtime — branch the consumed-set like the
                # statement-level ast.If handling in _walk
                self._scan_expr(ctx, sub.test, consumed, findings,
                                comp_bound)
                branches = []
                for arm in (sub.body, sub.orelse):
                    c = dict(consumed)
                    self._scan_expr(ctx, arm, c, findings, comp_bound)
                    branches.append(c)
                for c in branches:
                    consumed.update(c)
                continue
            if isinstance(sub, ast.BoolOp):
                # short-circuit is sequential-PREFIX, not exclusive arms:
                # operand i evaluates only after operands 0..i-1 already
                # did (and consumed) — accumulate in order so reuse
                # across `and`/`or` operands is caught
                for v in sub.values:
                    self._scan_expr(ctx, v, consumed, findings, comp_bound)
                continue
            if isinstance(sub, self._COMPS):
                # a comprehension body runs once per element: any key it
                # consumes that is NOT the comprehension's own loop
                # variable is loop-invariant reuse
                bound = set(comp_bound)
                for gen in sub.generators:
                    for t in ast.walk(gen.target):
                        if isinstance(t, ast.Name):
                            bound.add(t.id)
                bound_f = frozenset(bound)
                # the FIRST generator's iterable evaluates once in the
                # enclosing scope — `for k in jax.random.split(rng, n)`
                # is the idiomatic fix, not per-element reuse
                for i, gen in enumerate(sub.generators):
                    stack.append((gen.iter,
                                  comp_bound if i == 0 else bound_f))
                    for cond in gen.ifs:
                        stack.append((cond, bound_f))
                if isinstance(sub, ast.DictComp):
                    stack.append((sub.key, bound_f))
                    stack.append((sub.value, bound_f))
                else:
                    stack.append((sub.elt, bound_f))
                continue
            if isinstance(sub, ast.Call):
                kc = self._key_call(ctx, sub)
                if kc is not None and kc[0] != "other":
                    kind, name = kc
                    if comp_bound and name not in comp_bound:
                        findings.append(self.finding(
                            ctx, sub.lineno,
                            f"PRNG key `{name}` is consumed once per "
                            f"comprehension element — every draw is "
                            f"identical; fold_in/split per element "
                            f"instead"))
                    elif name in consumed:
                        verb = ("re-split" if kind == "split"
                                else "passed to a sampler")
                        findings.append(self.finding(
                            ctx, sub.lineno,
                            f"PRNG key `{name}` already consumed on line "
                            f"{consumed[name]} is {verb} again — derive a "
                            f"fresh key with jax.random.split/fold_in"))
                    elif not comp_bound:
                        consumed[name] = sub.lineno
            elif isinstance(sub, ast.NamedExpr) and \
                    isinstance(sub.target, ast.Name):
                consumed.pop(sub.target.id, None)
            # push reversed so the LIFO pop visits children in SOURCE
            # order — the "already consumed on line N" message must cite
            # the earlier call and anchor the later one, not vice versa
            for child in reversed(list(ast.iter_child_nodes(sub))):
                stack.append((child, comp_bound))

    @staticmethod
    def _bound_names(target) -> Iterator[str]:
        """Names in BINDING position only — ``d[k] = v`` / ``obj.k = v``
        assign THROUGH ``k``/``obj`` without rebinding them, so they must
        not clear a key's consumed state."""
        stack = [target]
        while stack:
            t = stack.pop()
            if isinstance(t, ast.Name):
                yield t.id
            elif isinstance(t, (ast.Tuple, ast.List)):
                stack.extend(t.elts)
            elif isinstance(t, ast.Starred):
                stack.append(t.value)

    @classmethod
    def _terminates(cls, stmts) -> bool:
        """Whether a statement list never falls through (its last statement
        unconditionally leaves the block). Such a branch's consumed-set
        must not merge into the fall-through state — the idiomatic
        early-return `if fast: return jax.random.normal(k, ...)` does not
        consume `k` on the path that reaches the next sampler."""
        if not stmts:
            return False
        last = stmts[-1]
        if isinstance(last, (ast.Return, ast.Raise, ast.Break,
                             ast.Continue)):
            return True
        if isinstance(last, ast.If):
            return cls._terminates(last.body) and cls._terminates(last.orelse)
        if isinstance(last, ast.Try):
            return (cls._terminates(last.finalbody)
                    or (cls._terminates(last.orelse if last.orelse
                                        else last.body)
                        and all(cls._terminates(h.body)
                                for h in last.handlers)))
        if isinstance(last, (ast.With, ast.AsyncWith)):
            return cls._terminates(last.body)
        return False

    def _walk(self, ctx, stmts, consumed: Dict[str, int],
              findings: List[Finding]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue    # separate scope, visited on its own
            if isinstance(st, ast.If):
                self._scan_expr(ctx, st.test, consumed, findings)
                c1, c2 = dict(consumed), dict(consumed)
                self._walk(ctx, st.body, c1, findings)
                self._walk(ctx, st.orelse, c2, findings)
                consumed.clear()
                if not self._terminates(st.body):
                    consumed.update(c1)
                if not self._terminates(st.orelse):
                    consumed.update(c2)
            elif isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
                head = st.iter if isinstance(st, (ast.For, ast.AsyncFor)) \
                    else st.test
                self._scan_expr(ctx, head, consumed, findings)
                # two passes over the body: the second catches a key that
                # is consumed each iteration but only rebound outside. A
                # body that never falls through runs at most one iteration
                # (`for ...: return jax.random.normal(k, ...)` is not
                # reuse), so the rescan is skipped
                for _ in range(2):
                    if isinstance(st, (ast.For, ast.AsyncFor)):
                        for n in self._bound_names(st.target):
                            consumed.pop(n, None)
                    self._walk(ctx, st.body, consumed, findings)
                    if self._terminates(st.body):
                        break
                self._walk(ctx, st.orelse, consumed, findings)
            elif isinstance(st, (ast.Try,)):
                # a handler runs only when the body failed — possibly
                # before it consumed anything — so each handler branches
                # from the PRE-body state (like ast.If arms); orelse runs
                # only after the full body, finalbody always
                pre = dict(consumed)
                self._walk(ctx, st.body, consumed, findings)
                branches = []
                for h in st.handlers:
                    c = dict(pre)
                    self._walk(ctx, h.body, c, findings)
                    branches.append(c)
                self._walk(ctx, st.orelse, consumed, findings)
                for h, c in zip(st.handlers, branches):
                    if not self._terminates(h.body):
                        consumed.update(c)
                self._walk(ctx, st.finalbody, consumed, findings)
            elif isinstance(st, ast.Match):
                # case arms are mutually exclusive — branch like ast.If
                self._scan_expr(ctx, st.subject, consumed, findings)
                branches = []
                for case in st.cases:
                    c = dict(consumed)
                    if case.guard is not None:
                        self._scan_expr(ctx, case.guard, c, findings)
                    self._walk(ctx, case.body, c, findings)
                    branches.append((case, c))
                for case, c in branches:
                    if not self._terminates(case.body):
                        consumed.update(c)
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    self._scan_expr(ctx, item.context_expr, consumed,
                                    findings)
                self._walk(ctx, st.body, consumed, findings)
            elif isinstance(st, ast.Assign):
                self._scan_expr(ctx, st.value, consumed, findings)
                for t in st.targets:
                    for n in self._bound_names(t):
                        consumed.pop(n, None)
            elif isinstance(st, ast.AugAssign):
                self._scan_expr(ctx, st.value, consumed, findings)
                for n in self._bound_names(st.target):
                    consumed.pop(n, None)
            elif isinstance(st, ast.AnnAssign):
                if st.value is not None:
                    self._scan_expr(ctx, st.value, consumed, findings)
                for n in self._bound_names(st.target):
                    consumed.pop(n, None)
            elif isinstance(st, ast.Delete):
                for t in st.targets:
                    for n in self._bound_names(t):
                        consumed.pop(n, None)
            else:
                self._scan_expr(ctx, st, consumed, findings)


# ---------------------------------------------------------------------------
# ZL002 — host side effects under jit
# ---------------------------------------------------------------------------

_BARE_EFFECTS = {"print", "input", "breakpoint", "open", "exec", "eval"}
_TIME_EFFECTS = {"time", "perf_counter", "perf_counter_ns", "monotonic",
                 "monotonic_ns", "sleep", "process_time", "time_ns"}
_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical", "log"}
_LOG_OBJECTS = {"log", "logger", "logging"}


@register
class HostEffectInJit(Rule):
    """``print``/``time.time``/logging inside a jitted function executes
    once at TRACE time and never again — the timestamp is the compile
    time, the log line fires on recompiles only, and under donation the
    printed value may alias a freed buffer. Use ``jax.debug.print`` /
    ``jax.debug.callback`` for traced-value output."""

    id = "ZL002"
    severity = ERROR

    def _banned(self, ctx: ModuleContext,
                d: Optional[str]) -> Optional[str]:
        if not d:
            return None
        if d in _BARE_EFFECTS:
            return f"`{d}`"
        if "." in d:
            prefix, leaf = d.rsplit(".", 1)
            if ctx.is_call_to(d, "time", _TIME_EFFECTS):
                return f"`{d}`"
            if leaf in _LOG_METHODS and (
                    prefix.split(".")[0] in _LOG_OBJECTS
                    or prefix in ctx.aliases["logging"]):
                return f"`{d}`"
        else:
            # from-imports: `from time import perf_counter [as pc]`
            if ctx.from_imported("time").get(d) in _TIME_EFFECTS:
                return f"`{d}` (time.*)"
            if ctx.from_imported("logging").get(d) in _LOG_METHODS:
                return f"`{d}` (logging.*)"
        return None

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for info in ctx.jitted.values():
            hosted = _callback_hosted_fns(ctx, info.fn)
            for node in _walk_skipping(info.fn, skip_nodes=hosted):
                if not isinstance(node, ast.Call):
                    continue
                what = self._banned(ctx, dotted(node.func))
                if what:
                    yield self.finding(
                        ctx, node.lineno,
                        f"host side effect {what} inside jitted "
                        f"`{getattr(info.fn, 'name', '<fn>')}` runs at "
                        f"trace time only — use jax.debug.print/callback")


# ---------------------------------------------------------------------------
# ZL003 — hidden host sync in a traced body
# ---------------------------------------------------------------------------

_SYNC_METHODS = {"item", "block_until_ready", "tolist"}


@register
class HostSyncInStep(Rule):
    """``.item()`` / ``np.asarray`` / ``jax.device_get`` /
    ``block_until_ready`` inside a jitted function or a ``lax.scan``-family
    body forces the traced value to a concrete host value — at best a
    ``TracerError``, at worst (on module constants) a silent
    device→host→device round-trip baked into every step."""

    id = "ZL003"
    severity = ERROR

    def _offense(self, ctx: ModuleContext,
                 node: ast.Call) -> Optional[str]:
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_METHODS:
            return f"`.{node.func.attr}()`"
        d = dotted(node.func)
        if not d:
            return None
        # import-resolved like ZL002/ZL006: a local helper that happens to
        # be NAMED device_get must not produce an error-severity finding
        mods, froms = ctx.jax_names
        if "." in d:
            prefix, leaf = d.rsplit(".", 1)
            if leaf == "device_get" and prefix.split(".", 1)[0] in mods:
                return f"`{d}`"
        elif froms.get(d) == "device_get":
            return f"`{d}`"
        leaf = ctx.is_call_to(d, "numpy", ("asarray", "array", "copy"))
        if leaf:
            return f"`{d}`"
        return None

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        bodies = [(info.fn, getattr(info.fn, "name", "<fn>"))
                  for info in ctx.jitted.values()]
        bodies += [(fn, getattr(fn, "name", "<lambda>"))
                   for fn in ctx.scan_bodies]
        seen: Set[int] = set()
        for fn, name in bodies:
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            hosted = _callback_hosted_fns(ctx, fn)
            for node in _walk_skipping(fn, skip_nodes=hosted):
                if isinstance(node, ast.Call):
                    what = self._offense(ctx, node)
                    if what:
                        yield self.finding(
                            ctx, node.lineno,
                            f"{what} in traced `{name}` forces a host "
                            f"sync/concretization — keep the value on "
                            f"device (jnp.*) or move the readback out of "
                            f"the traced body")


# ---------------------------------------------------------------------------
# ZL004 — Python control flow on a traced value
# ---------------------------------------------------------------------------

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                 "weak_type"}
_SAFE_FUNCS = {"len", "isinstance", "getattr", "hasattr", "callable",
               "type", "id"}


def _traced_name_in_expr(ctx: ModuleContext, test: ast.AST,
                         traced: Set[str]) -> Optional[str]:
    """First traced NAME an expression would concretize — the shared
    heuristic behind ZL004 (if/while tests) and ZL013 (assert tests):
    static-metadata attributes (``x.shape``), metadata builtins
    (``len``/``isinstance``/...), identity and ``is None`` comparisons
    don't concretize and are not flagged."""
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and node.id in traced):
            continue
        par = ctx.parent(node)
        if isinstance(par, ast.Attribute) \
                and par.attr in _STATIC_ATTRS:
            continue
        if isinstance(par, ast.Call):
            if node is par.func:
                continue
            if dotted(par.func) in _SAFE_FUNCS:
                continue
        if isinstance(par, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in par.ops):
                continue
            operands = [par.left] + list(par.comparators)
            if any(isinstance(o, ast.Constant) and o.value is None
                   for o in operands):
                continue
        return node.id
    return None


def _traced_params(info) -> Set[str]:
    """Traced (non-static) parameter names of a jitted function — the
    shared set construction behind ZL004 (branch tests) and ZL013
    (assert tests): every positional and kwonly param minus the
    ``static_argnames``, minus ``self``/``cls``. One definition so the
    two rules can never drift on which names count as traced."""
    fn = info.fn
    traced = {n for n in param_names(fn)
              if n not in info.static_names} - {"self", "cls"}
    traced.update(kw.arg for kw in fn.args.kwonlyargs
                  if kw.arg not in info.static_names)
    return traced


@register
class TracedBranch(Rule):
    """A Python ``if``/``while`` on a traced argument concretizes it at
    trace time — ``TracerBoolConversionError`` at best, or (when jit
    falls back to recompiling per value) a silent compile per distinct
    input. Branch on static metadata (``x.shape``, ``x.ndim``), mark the
    argument static, or use ``lax.cond``/``lax.select``/``jnp.where``."""

    id = "ZL004"
    severity = ERROR

    def _test_traced_name(self, ctx: ModuleContext, test: ast.AST,
                          traced: Set[str]) -> Optional[str]:
        return _traced_name_in_expr(ctx, test, traced)

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for info in ctx.jitted.values():
            fn = info.fn
            traced = _traced_params(info)
            if not traced:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                if ctx.in_nested_scope(node, fn):   # own scope: shadows
                    continue
                name = self._test_traced_name(ctx, node.test, traced)
                if name:
                    kind = "while" if isinstance(node, ast.While) else "if"
                    yield self.finding(
                        ctx, node.lineno,
                        f"Python `{kind}` on traced argument `{name}` of "
                        f"jitted `{getattr(fn, 'name', '<fn>')}` — use "
                        f"lax.cond/jnp.where, branch on static metadata, "
                        f"or mark the argument static")


# ---------------------------------------------------------------------------
# ZL005 — per-element device work built in a Python loop (warn)
# ---------------------------------------------------------------------------

_BUILD_SINKS = {"stack", "concatenate", "array", "asarray", "vstack",
                "hstack"}


@register
class LoopBuiltArray(Rule):
    """A Python loop appending per-element ``jnp`` results that are later
    ``jnp.stack``-ed dispatches one device op (and potentially one
    compile) per element; ``vmap`` or a batched op does it in one fused
    kernel. Heuristic — loops over layers/pytrees of distinct shapes are
    legitimate and carry a justified suppression (cf. ``layers/gpipe.py``);
    error severity since the package-wide triage (ROADMAP follow-up)."""

    id = "ZL005"
    severity = ERROR

    def _jnp_call_inside(self, ctx: ModuleContext, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                d = dotted(sub.func)
                if d and "." in d:
                    prefix = d.rsplit(".", 1)[0]
                    if prefix in ctx.aliases["jax.numpy"]:
                        return True
        return False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        # one lexical scope at a time: appended-list names and stack-sink
        # names must come from the SAME function (or the module top level)
        # — a bare-name match across unrelated scopes is meaningless, and
        # the module pass must not re-walk every function body
        nested = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        for fn in list(ctx.functions()) + [ctx.tree]:
            scope = [n for st in fn.body if not isinstance(st, nested)
                     for n in _walk_skipping(st, skip_types=nested)]
            loops: List[Tuple[ast.For, Set[str]]] = []
            for node in scope:
                if not isinstance(node, ast.For):
                    continue
                appended: Set[str] = set()
                for sub in _walk_skipping(node, skip_types=nested):
                    if (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and sub.func.attr == "append"
                            and isinstance(sub.func.value, ast.Name)
                            and sub.args
                            and self._jnp_call_inside(ctx, sub.args[0])):
                        appended.add(sub.func.value.id)
                if appended:
                    loops.append((node, appended))
            if not loops:
                continue
            sinks: Set[str] = set()
            for node in scope:
                if isinstance(node, ast.Call):
                    d = dotted(node.func)
                    if d and "." in d and \
                            d.rsplit(".", 1)[-1] in _BUILD_SINKS and \
                            d.rsplit(".", 1)[0] in ctx.aliases["jax.numpy"]:
                        for arg in node.args:
                            for sub in ast.walk(arg):
                                if isinstance(sub, ast.Name):
                                    sinks.add(sub.id)
            for loop, appended in loops:
                hit = appended & sinks
                if hit:
                    yield self.finding(
                        ctx, loop.lineno,
                        f"list `{sorted(hit)[0]}` built from jnp results "
                        f"in a Python loop then stacked — consider "
                        f"jax.vmap or a batched op (one dispatch instead "
                        f"of one per element)")


# ---------------------------------------------------------------------------
# ZL006 — import-time device/mesh init & mutable defaults
# ---------------------------------------------------------------------------

_DEVICE_LEAVES = {"devices", "local_devices", "device_count",
                  "local_device_count", "process_count", "process_index"}
_MESH_LEAVES = {"Mesh", "create_mesh", "make_mesh", "create_device_mesh"}
_MUTABLE_CTORS = {"list", "dict", "set", "bytearray",
                  "collections.defaultdict", "collections.OrderedDict"}


@register
class ImportTimeHazard(Rule):
    """Module-level ``jax.devices()``/``Mesh`` construction runs at import
    — before ``jax.distributed.initialize`` on multi-host, it pins a
    single-process backend and every later mesh is wrong (see
    ``parallel/mesh.py``'s lazy ``global_mesh()``). Mutable default
    arguments are the classic shared-state bug: one instance mutates,
    every later call sees it."""

    id = "ZL006"
    severity = ERROR

    def _device_call(self, node: ast.Call,
                     ctx: ModuleContext) -> Optional[str]:
        """The dotted name iff this call resolves to jax device/mesh API.
        Import-resolved (like the jit detection in core.py): a bare name
        must be from-imported off a jax module, a dotted one must hang
        off a local jax-module alias — so ``trimesh.Mesh(...)`` or a
        local ``make_mesh()`` never produces an error-severity finding,
        and ``import jax as j; j.devices()`` does."""
        d = dotted(node.func)
        if not d:
            return None
        mods, froms = ctx.jax_names
        if "." in d:
            prefix, leaf = d.rsplit(".", 1)
            if prefix.split(".", 1)[0] not in mods:
                return None
        else:
            leaf = froms.get(d)
            if leaf is None:
                return None
        if leaf in _DEVICE_LEAVES or leaf in _MESH_LEAVES:
            return d
        return None

    @staticmethod
    def _not_import_time_guard(test: ast.AST) -> bool:
        """``if __name__ == "__main__":`` bodies run as a script entry
        point, not when the module is imported; ``if TYPE_CHECKING:``
        bodies never run at all — neither is an import-time hazard."""
        if dotted(test) in ("TYPE_CHECKING", "typing.TYPE_CHECKING"):
            return True
        if isinstance(test, ast.Compare) and len(test.ops) == 1 and \
                isinstance(test.ops[0], ast.Eq):
            sides = [test.left] + list(test.comparators)
            return ("__name__" in {dotted(s) for s in sides}
                    and any(isinstance(s, ast.Constant)
                            and s.value == "__main__" for s in sides))
        return False

    def _walk_import_time(self, stmts) -> Iterator[ast.AST]:
        """Expressions evaluated at import: module/class bodies (through
        if/try/with/loops — including their head expressions: the ``if``
        test, the ``for`` iterable, the ``with`` context managers) plus
        def-statement default args and decorators, and class decorators/
        bases/keywords — but not function bodies, main-guard bodies, or
        ``TYPE_CHECKING`` blocks."""
        for st in stmts:
            if isinstance(st, ast.If) and \
                    self._not_import_time_guard(st.test):
                # the else-branch of a guard still runs at import
                yield from self._walk_import_time(st.orelse)
                continue
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from st.decorator_list
                for default in (list(st.args.defaults)
                                + [d for d in st.args.kw_defaults if d]):
                    yield default
                continue
            if isinstance(st, ast.ClassDef):
                yield from st.decorator_list
                yield from st.bases
                for kw in st.keywords:
                    yield kw.value
                yield from self._walk_import_time(st.body)
                continue
            if isinstance(st, (ast.If, ast.While)):
                yield st.test
            elif isinstance(st, (ast.For, ast.AsyncFor)):
                yield st.iter
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    yield item.context_expr
            for attr in ("body", "orelse", "finalbody"):
                if hasattr(st, attr):
                    yield from self._walk_import_time(getattr(st, attr))
            if hasattr(st, "handlers"):
                for h in st.handlers:
                    yield from self._walk_import_time(h.body)
            if not hasattr(st, "body"):
                yield st

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for fn in ctx.functions():
            for default in (list(fn.args.defaults)
                            + [d for d in fn.args.kw_defaults if d]):
                bad = isinstance(default, (ast.List, ast.Dict, ast.Set,
                                           ast.ListComp, ast.DictComp,
                                           ast.SetComp))
                if not bad and isinstance(default, ast.Call):
                    bad = dotted(default.func) in _MUTABLE_CTORS
                if bad:
                    yield self.finding(
                        ctx, fn.lineno,
                        f"mutable default argument in "
                        f"`{fn.name}` — use None and create inside")
        for expr in self._walk_import_time(ctx.tree.body):
            # lambda bodies never run at import — the lazy-accessor
            # pattern (`get_devices = lambda: jax.devices()`) is the fix,
            # not a violation (_walk_skipping always descends from its
            # root, so a default arg that IS a lambda must be skipped here)
            if isinstance(expr, ast.Lambda):
                continue
            for node in _walk_skipping(expr, skip_types=(ast.Lambda,)):
                if isinstance(node, ast.Call):
                    d = self._device_call(node, ctx)
                    if d:
                        yield self.finding(
                            ctx, node.lineno,
                            f"`{d}(...)` at import time pins the backend "
                            f"before multi-host init — build devices/"
                            f"meshes lazily (cf. parallel/mesh.py "
                            f"global_mesh())")


# ---------------------------------------------------------------------------
# ZL007 — swallowed exceptions in retry paths
# ---------------------------------------------------------------------------

def _in_serving_hot_path(path: str) -> bool:
    """Whether a file lives in the serving / inference retry paths (the
    rules that escalate there: ZL007's swallow-pass, ZL010's unbounded
    spins). Absolutized so severity tracks the file's real location, not
    how the scan path was spelled (a cwd-relative `server.py` must gate
    exactly like CI's absolute-path scan of the same file)."""
    if os.path.exists(path):
        path = os.path.abspath(path)
    p = path.replace("\\", "/")
    return ("/serving/" in p or p.startswith("serving/")
            or "/pipeline/inference/" in p
            or p.startswith("pipeline/inference/"))


@register
class SwallowedException(Rule):
    """A bare ``except:`` (which also catches ``KeyboardInterrupt`` /
    ``SystemExit``) or an ``except Exception: pass`` turns a dead model
    replica or a poisoned request into silence — the serving loop keeps
    accepting work it can never answer. Bare excepts are errors
    everywhere; swallow-``pass`` is an error in the ``serving/`` and
    ``pipeline/inference/`` retry paths and a warning elsewhere."""

    id = "ZL007"
    severity = ERROR

    def _in_hot_path(self, path: str) -> bool:
        return _in_serving_hot_path(path)

    @staticmethod
    def _swallows(handler: ast.ExceptHandler) -> bool:
        for st in handler.body:
            if isinstance(st, ast.Pass) or isinstance(st, ast.Continue):
                continue
            if isinstance(st, ast.Expr) and \
                    isinstance(st.value, ast.Constant):
                continue    # docstring / Ellipsis
            return False
        return True

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                # a re-raise only counts in the handler's own scope — a
                # `raise` inside a nested def/lambda does not run here
                nested = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                if any(isinstance(st, ast.Raise) for sub in node.body
                       if not isinstance(sub, nested)
                       for st in _walk_skipping(sub, skip_types=nested)):
                    continue    # bare except that re-raises: tolerated
                yield self.finding(
                    ctx, node.lineno,
                    "bare `except:` swallows KeyboardInterrupt/SystemExit "
                    "— catch Exception (and log) at most")
                continue
            if isinstance(node.type, ast.Tuple):
                # `except (Exception,):` / `except (Exception, ...)`
                names = [dotted(e) for e in node.type.elts]
                d = next((n for n in names
                          if n in ("Exception", "BaseException")), None)
            else:
                d = dotted(node.type)
            if d in ("Exception", "BaseException") and self._swallows(node):
                sev = ERROR if self._in_hot_path(ctx.path) else WARNING
                yield self.finding(
                    ctx, node.lineno,
                    f"`except {d}: pass` swallows errors"
                    + (" in a serving/inference retry path — log and "
                       "surface them" if sev == ERROR
                       else " — log them at least"),
                    severity=sev)


# ---------------------------------------------------------------------------
# ZL008 — missing donate_argnums on a rebinding step (warn)
# ---------------------------------------------------------------------------

@register
class MissingDonation(Rule):
    """A jitted step that re-binds its first argument (``params = ...``)
    produces a new buffer while the old one stays live — double the
    parameter HBM footprint per step. ``donate_argnums=(0,)`` lets XLA
    reuse the input buffer in place (cf. training.py's steps). Donation
    is wrong when the caller keeps using the input — such sites carry a
    justified suppression (cf. ``pipeline/inference/inference_model.py``);
    error severity since the package-wide triage (ROADMAP follow-up)."""

    id = "ZL008"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for info in ctx.jitted.values():
            if info.donates:
                continue
            fn = info.fn
            names = [n for n in param_names(fn) if n not in ("self", "cls")]
            if not names:
                continue
            first = names[0]
            rebinds = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                else:
                    continue
                if ctx.in_nested_scope(node, fn):
                    continue
                if any(isinstance(sub, ast.Name) and sub.id == first
                       for t in targets for sub in ast.walk(t)):
                    rebinds = True
                    break
            if rebinds:
                yield self.finding(
                    ctx, info.anchor_line,
                    f"jitted `{getattr(fn, 'name', '<fn>')}` re-binds its "
                    f"first argument `{first}` but declares no "
                    f"donate_argnums — the old buffer stays live (2x param "
                    f"HBM); add donate_argnums=(0,) if the caller discards "
                    f"its input")


# ---------------------------------------------------------------------------
# ZL009 — unbatched host→device transfer in a loop
# ---------------------------------------------------------------------------

_NESTED_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


@register
class UnbatchedTransferInLoop(Rule):
    """``jax.device_put`` (or the implicit upload in ``jnp.asarray`` /
    ``jnp.array``) on a per-iteration value inside a Python ``for``/
    ``while`` body issues one small host→device transfer per element,
    each paying the full dispatch round-trip where one stacked transfer — or ``FeatureSet``'s
    ``prefetch_to_device`` pipeline — pays it once. Flags transfers whose
    argument derives from the loop variable (``for``) or from a name
    rebound each iteration (``while``); intentionally-chunked bulk
    uploads carry a justified suppression (cf.
    ``pipeline/inference/inference_model.py``)."""

    id = "ZL009"
    severity = ERROR

    def _transfer_call(self, ctx: ModuleContext,
                       node: ast.Call) -> Optional[str]:
        """The dotted name iff this call uploads its first argument to
        device — import-resolved (like ZL003's device_get) so a local
        helper named ``device_put`` or a non-jax ``asarray`` is never
        flagged."""
        d = dotted(node.func)
        if not d or not node.args:
            return None
        mods, froms = ctx.jax_names
        if "." in d:
            prefix, leaf = d.rsplit(".", 1)
            if leaf == "device_put" and prefix.split(".", 1)[0] in mods:
                return d
            if leaf in ("asarray", "array") \
                    and prefix in ctx.aliases["jax.numpy"]:
                return d
        else:
            if froms.get(d) == "device_put":
                return d
            if ctx.from_imported("jax.numpy").get(d) in ("asarray", "array"):
                return d
        return None

    @staticmethod
    def _binding_names(target) -> Iterator[str]:
        """Names in BINDING position (``x``, ``x, y = ...``, ``*rest``) —
        ``obj.attr = v`` / ``d[k] = v`` assign THROUGH the name without
        rebinding it, so they do not make it per-iteration state."""
        stack = [target]
        while stack:
            t = stack.pop()
            if isinstance(t, ast.Name):
                yield t.id
            elif isinstance(t, (ast.Tuple, ast.List)):
                stack.extend(t.elts)
            elif isinstance(t, ast.Starred):
                stack.append(t.value)

    @staticmethod
    def _references(node: ast.AST, names: Set[str]) -> bool:
        return any(isinstance(sub, ast.Name) and sub.id in names
                   for sub in ast.walk(node))

    def _check_loop(self, ctx: ModuleContext, loop) -> Iterator[Finding]:
        body = [n for st in loop.body
                for n in _walk_skipping(st, skip_types=_NESTED_SCOPES)]
        seeds: Set[str] = set()
        if isinstance(loop, (ast.For, ast.AsyncFor)):
            seeds.update(self._binding_names(loop.target))
        else:
            # while: anything rebound in the body is per-iteration state —
            # and so is a walrus target in the CONDITION, the idiomatic
            # `while (item := q.get()) is not None:` streaming form
            for n in ast.walk(loop.test):
                if isinstance(n, ast.NamedExpr):
                    seeds.update(self._binding_names(n.target))
            for n in body:
                if isinstance(n, ast.Assign):
                    for t in n.targets:
                        seeds.update(self._binding_names(t))
                elif isinstance(n, (ast.AugAssign, ast.AnnAssign,
                                    ast.NamedExpr)):
                    seeds.update(self._binding_names(n.target))
                elif isinstance(n, (ast.For, ast.AsyncFor)):
                    seeds.update(self._binding_names(n.target))
        # propagate derivation: `chunk = f(i)` makes `chunk` per-iteration,
        # and a comprehension over a seed binds per-iteration targets; two
        # passes close realistic chains without a full fixpoint
        for _ in range(2):
            for n in body:
                if isinstance(n, ast.Assign) \
                        and self._references(n.value, seeds):
                    for t in n.targets:
                        seeds.update(self._binding_names(t))
                elif isinstance(n, ast.NamedExpr) \
                        and self._references(n.value, seeds):
                    seeds.update(self._binding_names(n.target))
                elif isinstance(n, (ast.ListComp, ast.SetComp,
                                    ast.GeneratorExp, ast.DictComp)):
                    for gen in n.generators:
                        if self._references(gen.iter, seeds):
                            seeds.update(self._binding_names(gen.target))
        if not seeds:
            return
        for n in body:
            if not isinstance(n, ast.Call):
                continue
            d = self._transfer_call(ctx, n)
            if d is None or not self._references(n.args[0], seeds):
                continue
            # `device_put(jnp.asarray(x), ...)` is ONE transfer: flag the
            # outer call only
            par = ctx.parent(n)
            if isinstance(par, ast.Call) \
                    and self._transfer_call(ctx, par) is not None:
                continue
            yield self.finding(
                ctx, n.lineno,
                f"`{d}(...)` on a per-iteration value inside a loop — one "
                f"small host→device transfer (and dispatch round-trip) per "
                f"element; stack on the host and transfer once, or stream "
                f"through feature.prefetch_to_device")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        # loops inside jit-traced code unroll at TRACE time — jnp.asarray
        # on a traced value is free there and device_put of a constant is
        # baked into the program, so no per-iteration runtime transfer
        # exists to flag
        traced = {id(info.fn) for info in ctx.jitted.values()}
        traced.update(id(fn) for fn in ctx.scan_bodies)
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            cur = loop
            while cur is not None and id(cur) not in traced:
                cur = ctx.parent(cur)
            if cur is not None:
                continue
            yield from self._check_loop(ctx, loop)


# ---------------------------------------------------------------------------
# ZL010 — unbounded time.sleep retry spin
# ---------------------------------------------------------------------------

_CLOCK_LEAVES = {"monotonic", "monotonic_ns", "time", "time_ns",
                 "perf_counter", "perf_counter_ns"}


@register
class UnboundedRetrySpin(Rule):
    """A ``while`` loop that ``time.sleep``-polls with no deadline — no
    clock read anywhere in the loop's test or body — waits forever when
    the condition never comes true: a dead backend turns the caller into
    a silently hung thread (the pre-reliability ``InputQueue.enqueue``
    full-stream spin). Route the wait through
    ``common.reliability.RetryPolicy`` (``delays()`` / ``wait_for`` with
    a deadline, a bounded ``for`` — never flagged) or check a
    ``time.monotonic()`` deadline in the loop. Error severity in the
    ``serving/`` and ``pipeline/inference/`` paths, warning elsewhere
    (an intentional forever-guard like ``ray/raycontext.py``'s
    parent-watch carries the warning knowingly)."""

    id = "ZL010"
    severity = ERROR

    def _is_sleep(self, ctx: ModuleContext, node: ast.Call) -> bool:
        d = dotted(node.func)
        if not d:
            return False
        if ctx.is_call_to(d, "time", ("sleep",)):
            return True
        return "." not in d and ctx.from_imported("time").get(d) == "sleep"

    def _is_clock_read(self, ctx: ModuleContext, node: ast.Call) -> bool:
        d = dotted(node.func)
        if not d:
            return False
        if ctx.is_call_to(d, "time", _CLOCK_LEAVES):
            return True
        return "." not in d and \
            ctx.from_imported("time").get(d) in _CLOCK_LEAVES

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        nested = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, ast.While):
                continue
            scope = list(ast.walk(loop.test)) \
                + [n for st in loop.body if not isinstance(st, nested)
                   for n in _walk_skipping(st, skip_types=nested)]
            sleeps = [n for n in scope if isinstance(n, ast.Call)
                      and self._is_sleep(ctx, n)]
            if not sleeps:
                continue
            if any(isinstance(n, ast.Call) and self._is_clock_read(ctx, n)
                   for n in scope):
                continue        # a clock read implies a deadline check
            sev = ERROR if _in_serving_hot_path(ctx.path) else WARNING
            yield self.finding(
                ctx, sleeps[0].lineno,
                "time.sleep retry spin with no deadline in a `while` loop"
                + (" in a serving/inference path" if sev == ERROR else "")
                + " — bound it through common.reliability.RetryPolicy "
                  "(delays()/wait_for) or check a time.monotonic() "
                  "deadline",
                severity=sev)


# ---------------------------------------------------------------------------
# ZL011 — unbounded queue.Queue / blocking put with no timeout
# ---------------------------------------------------------------------------

_QUEUE_CLASSES = ("Queue", "LifoQueue", "PriorityQueue", "SimpleQueue")


@register
class UnboundedQueueUse(Rule):
    """An unbounded ``queue.Queue()`` between pipeline stages removes the
    backpressure the serving path depends on — a stalled consumer lets
    the producer buffer without limit until the host OOMs (the failure
    mode the bounded publisher queue exists to prevent). And a blocking
    ``.put()`` with no ``timeout`` on a BOUNDED queue is the same hang
    ZL010 flags for sleep spins: when the consumer wedges, the producer
    thread parks forever instead of surfacing the stall. Bound the queue
    (``maxsize=``) and the put (``timeout=`` + handle ``queue.Full``, or
    ``put_nowait``/``block=False`` where dropping is correct). Error
    severity in the ``serving/`` and ``pipeline/inference/`` paths,
    warning elsewhere (a deliberately unbounded hand-off carries the
    warning knowingly, with a justified suppression)."""

    id = "ZL011"
    severity = ERROR

    def _is_queue_ctor(self, ctx: ModuleContext, node: ast.Call) -> bool:
        d = dotted(node.func)
        if not d:
            return False
        if ctx.is_call_to(d, "queue", _QUEUE_CLASSES):
            return True
        return "." not in d and \
            ctx.from_imported("queue").get(d) in _QUEUE_CLASSES

    @staticmethod
    def _maxsize(node: ast.Call) -> Optional[ast.AST]:
        for kw in node.keywords:
            if kw.arg == "maxsize":
                return kw.value
        return node.args[0] if node.args else None

    @staticmethod
    def _target_leaf(t: ast.AST) -> Optional[str]:
        if isinstance(t, ast.Name):
            return t.id
        if isinstance(t, ast.Attribute):
            return t.attr
        return None

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        sev = ERROR if _in_serving_hot_path(ctx.path) else WARNING
        # names bound to a queue constructor anywhere in the module
        # (`q = queue.Queue(...)`, `self._pub_queue = queue.Queue(...)`,
        # annotated forms included): the receivers whose `.put` calls
        # this rule attributes to a stdlib queue rather than to some
        # unrelated object's put method
        qnames = set()
        for node in ast.walk(ctx.tree):
            value = getattr(node, "value", None)
            if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                    and isinstance(value, ast.Call) \
                    and self._is_queue_ctor(ctx, value):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    leaf = self._target_leaf(t)
                    if leaf:
                        qnames.add(leaf)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if self._is_queue_ctor(ctx, node):
                d = dotted(node.func) or "queue.Queue"
                if d.rsplit(".", 1)[-1] == "SimpleQueue":
                    # SimpleQueue cannot be bounded at all
                    yield self.finding(
                        ctx, node.lineno,
                        "queue.SimpleQueue() is always unbounded — a "
                        "stalled consumer buffers without limit; use "
                        "queue.Queue(maxsize=...) so the producer "
                        "backpressures",
                        severity=sev)
                    continue
                size = self._maxsize(node)
                if size is None or (isinstance(size, ast.Constant)
                                    and isinstance(size.value, (int, float))
                                    and not isinstance(size.value, bool)
                                    and size.value <= 0):
                    yield self.finding(
                        ctx, node.lineno,
                        f"{d}() with no positive maxsize is unbounded"
                        + (" in a serving/inference path"
                           if sev == ERROR else "")
                        + " — a stalled consumer buffers without limit; "
                          "pass maxsize= so the producer backpressures",
                        severity=sev)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "put" \
                    and self._target_leaf(node.func.value) in qnames:
                # Queue.put(item, block=True, timeout=None): both block
                # and timeout may be passed positionally
                if any(kw.arg == "timeout" for kw in node.keywords) \
                        or len(node.args) >= 3:
                    continue
                block_arg = None
                for kw in node.keywords:
                    if kw.arg == "block":
                        block_arg = kw.value
                if block_arg is None and len(node.args) >= 2:
                    block_arg = node.args[1]
                if isinstance(block_arg, ast.Constant) \
                        and block_arg.value is False:
                    continue    # non-blocking put raises Full immediately
                yield self.finding(
                    ctx, node.lineno,
                    "blocking .put() on a queue with no timeout"
                    + (" in a serving/inference path" if sev == ERROR
                       else "")
                    + " — a wedged consumer parks this thread forever; "
                      "pass timeout= and handle queue.Full (or "
                      "put_nowait/block=False where dropping is correct)",
                    severity=sev)


# ---------------------------------------------------------------------------
# ZL012 — full-vocab cross-entropy materialization in a training path
# ---------------------------------------------------------------------------

def _in_training_hot_path(path: str) -> bool:
    """Whether a file lives in the keras training engine — the paths where
    a full-logits cross-entropy lands on the LM-head training hot loop
    (objectives, the step builders, the estimator driver). Absolutized
    like ``_in_serving_hot_path`` so severity tracks the file's real
    location."""
    if os.path.exists(path):
        path = os.path.abspath(path)
    p = path.replace("\\", "/")
    return ("/pipeline/api/keras/" in p or p.startswith("pipeline/api/keras/")
            or "/pipeline/estimator/" in p
            or p.startswith("pipeline/estimator/"))


@register
class FullVocabCrossEntropy(Rule):
    """``log_softmax`` over full logits followed by a label pick
    (``take_along_axis`` / ``one_hot``) is the sparse-cross-entropy shape
    that materializes the ``(N, V)`` log-probability tensor — three times
    over, counting the softmax backward and the pick's scatter. At LM-head
    vocab widths that is gigabytes of fp32 HBM traffic per step (the 32k
    long-context bench budgeted 2 GB for it at 4k seq). Training-path
    sparse CE should stream through ``ops.fused_cross_entropy`` (chunked
    online logsumexp + label logit, O(chunk·V) memory, custom VJP) — the
    keras loss resolution does this automatically for big-vocab Dense
    heads (``zoo.train.fused_ce``). Error severity in the keras training
    engine (``pipeline/api/keras/``, ``pipeline/estimator/``); warning
    elsewhere — a small-class head where full logits are harmless, or the
    equivalence oracle itself, carries a justified suppression."""

    id = "ZL012"
    severity = ERROR

    def _is_log_softmax(self, ctx: ModuleContext, node: ast.Call) -> bool:
        d = dotted(node.func)
        if not d:
            return False
        mods, froms = ctx.jax_names
        if "." in d:
            prefix, leaf = d.rsplit(".", 1)
            return leaf == "log_softmax" and prefix.split(".", 1)[0] in mods
        return froms.get(d) == "log_softmax"

    def _is_label_pick(self, ctx: ModuleContext, node: ast.Call) -> bool:
        d = dotted(node.func)
        if not d:
            return False
        mods, froms = ctx.jax_names
        if "." in d:
            prefix, leaf = d.rsplit(".", 1)
            if leaf == "take_along_axis" \
                    and prefix in ctx.aliases["jax.numpy"]:
                return True
            return leaf == "one_hot" and prefix.split(".", 1)[0] in mods
        if ctx.from_imported("jax.numpy").get(d) == "take_along_axis":
            return True
        return froms.get(d) == "one_hot"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        sev = ERROR if _in_training_hot_path(ctx.path) else WARNING
        nested = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        scopes = [ctx.tree] + list(ctx.functions()) + [
            n for n in ast.walk(ctx.tree) if isinstance(n, ast.Lambda)]
        for scope in scopes:
            body = scope.body if isinstance(scope.body, list) \
                else [scope.body]
            # nested functions/lambdas are their own scope — a module-level
            # walk must not merge two different functions' calls into one
            # fake cross-entropy
            calls = [n for st in body if not isinstance(st, nested)
                     for n in _walk_skipping(st, skip_types=nested)
                     if isinstance(n, ast.Call)]
            softmaxes = [n for n in calls if self._is_log_softmax(ctx, n)]
            if not softmaxes:
                continue
            if not any(self._is_label_pick(ctx, n) for n in calls):
                continue
            yield self.finding(
                ctx, softmaxes[0].lineno,
                "full-vocab log_softmax + label pick materializes the "
                "(N, V) log-probability tensor"
                + (" in a training path" if sev == ERROR else "")
                + " — stream it through ops.fused_cross_entropy "
                  "(fused_sparse_cross_entropy: chunked logsumexp + label "
                  "logit, O(chunk*V) memory; the keras loss resolution "
                  "picks it up via zoo.train.fused_ce)",
                severity=sev)


# ---------------------------------------------------------------------------
# ZL013 — bare Python assert on traced values inside jit-staged bodies
# ---------------------------------------------------------------------------

def _in_package(path: str) -> bool:
    """Whether a file is package code (``analytics_zoo_tpu/``) — where a
    compiled-away assertion is a shipped latent bug, so ZL013 runs at
    error severity; elsewhere (tests, examples, user scripts) it warns."""
    if os.path.exists(path):
        path = os.path.abspath(path)
    p = path.replace("\\", "/")
    return "/analytics_zoo_tpu/" in p or p.startswith("analytics_zoo_tpu/")


@register
class TracedAssert(Rule):
    """A bare Python ``assert`` on a traced value inside a jit-staged
    body is a guard that cannot guard: at trace time the tracer either
    raises ``TracerBoolConversionError`` (boolean contexts) or — the
    insidious form — the assert evaluates ONCE on the abstract value,
    is baked out of the compiled program, and never runs again on real
    data (and under ``python -O`` asserts vanish entirely). A numeric
    invariant the author meant to enforce per step silently enforces
    nothing. Use ``checkify.check`` / ``jax.debug`` for a real runtime
    check, branch on static metadata (``x.shape`` asserts are fine and
    not flagged), or return a packed sentinel flag the host inspects
    (the ``common/anomaly.py`` pattern). Error severity in package
    code; warning elsewhere."""

    id = "ZL013"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        sev = ERROR if _in_package(ctx.path) else WARNING
        bodies: List[Tuple[ast.AST, Set[str], str]] = []
        for info in ctx.jitted.values():
            fn = info.fn
            if not hasattr(fn, "args"):
                continue
            bodies.append((fn, _traced_params(info),
                           getattr(fn, "name", "<fn>")))
        for fn in ctx.scan_bodies:
            if hasattr(fn, "args"):   # every param of a scan body traces
                traced = set(param_names(fn)) - {"self", "cls"}
                bodies.append((fn, traced,
                               getattr(fn, "name", "<lambda>")))
        seen: Set[int] = set()
        for fn, traced, name in bodies:
            if id(fn) in seen or not traced:
                continue
            seen.add(id(fn))
            # derivation-aware (the ZL009 discipline): a local assigned
            # from a traced value is itself traced (`y = jnp.dot(x, w);
            # assert y.sum() > 0`). Taint propagates through the static-
            # metadata filter, so `n = x.shape[0]` stays untainted.
            derived = set(traced)
            # one AST walk collects the candidate assignments; the
            # fixpoint then iterates only over that list (a long
            # derivation chain must not re-walk the whole body per
            # newly-tainted name)
            assigns = [node for node in ast.walk(fn)
                       if isinstance(node, (ast.Assign, ast.AugAssign))
                       and not ctx.in_nested_scope(node, fn)]
            changed = True
            while changed:
                changed = False
                for node in assigns:
                    if not _traced_name_in_expr(ctx, node.value, derived):
                        continue
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        for tn in ast.walk(t):
                            if isinstance(tn, ast.Name) \
                                    and tn.id not in derived:
                                derived.add(tn.id)
                                changed = True
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assert):
                    continue
                if ctx.in_nested_scope(node, fn):   # own scope: shadows
                    continue
                offender = _traced_name_in_expr(ctx, node.test, derived)
                if offender:
                    yield self.finding(
                        ctx, node.lineno,
                        f"bare `assert` on traced value `{offender}` in "
                        f"jit-staged `{name}` — evaluated once at trace "
                        f"time (or TracerBoolConversionError), never on "
                        f"real data; use checkify.check/jax.debug, "
                        f"assert static metadata, or return a sentinel "
                        f"flag the host checks", severity=sev)


# ---------------------------------------------------------------------------
# ZL014 — thread-shared state without lock discipline
# ---------------------------------------------------------------------------

def _threading_ctor_names(ctx: ModuleContext,
                          leaves: Tuple[str, ...]) -> Tuple[Set[str],
                                                            Set[str]]:
    """``(prefixes, bare)`` local spellings of ``threading.<leaf>`` for
    the given leaves — module aliases (``import threading as th``) and
    from-imports (``from threading import Thread as T``)."""
    prefixes = set(ctx.aliases.get("threading", {"threading"}))
    bare = {local for local, orig in ctx.from_imported("threading").items()
            if orig in leaves}
    return prefixes, bare


def _is_threading_call(ctx: ModuleContext, node: ast.AST,
                       leaves: Tuple[str, ...]) -> bool:
    d = dotted(node)
    if d is None:
        return False
    prefixes, bare = _threading_ctor_names(ctx, leaves)
    if "." in d:
        prefix, leaf = d.rsplit(".", 1)
        return leaf in leaves and prefix in prefixes
    return d in bare


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


@register
class ThreadSharedWriteDiscipline(Rule):
    """**Thread-shared instance state without lock discipline.** A class
    that runs several of its methods on different threads (the serving
    server: serve loop + publisher + heartbeat/reclaim) and writes the
    same instance attribute from more than one of those thread entry
    points is relying on the GIL making each *individual* bytecode
    atomic — read-modify-write sequences interleave, and the bug
    surfaces only under production concurrency. Interprocedural within
    the class: thread roots are the methods handed to
    ``threading.Thread(target=..., args=(...))``, writes are attributed
    through the intra-class call graph, and a write counts as guarded
    only when every path to it holds the same ``threading.Lock``
    attribute (``with self._lock:`` at the write or around every call
    site leading to it). Error in the ``serving/`` and
    ``pipeline/inference/`` paths, warning elsewhere."""

    id = "ZL014"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        sev = ERROR if _in_serving_hot_path(ctx.path) else WARNING
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(ctx, node, sev)

    # -- per-class facts ----------------------------------------------------
    def _methods(self, cls: ast.ClassDef) -> Dict[str, ast.AST]:
        return {n.name: n for n in cls.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def _lock_attrs(self, ctx: ModuleContext, cls: ast.ClassDef,
                    methods: Dict[str, ast.AST]) -> Set[str]:
        """Attributes assigned ``threading.Lock()``/``RLock()``/
        ``Condition()`` anywhere in the class."""
        out: Set[str] = set()
        for fn in methods.values():
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call) and \
                        _is_threading_call(ctx, node.value.func,
                                           ("Lock", "RLock", "Condition")):
                    for t in node.targets:
                        attr = _self_attr(t)
                        if attr:
                            out.add(attr)
        return out

    def _thread_contexts(self, ctx: ModuleContext, cls: ast.ClassDef,
                         methods: Dict[str, ast.AST]) -> List[Set[str]]:
        """One entry per thread the class can spawn: the set of
        own-method names a ``threading.Thread(...)`` creation may run
        (the target plus any method reference passed through ``args=``
        / ``kwargs=`` — the ``Thread(target=self._supervised,
        args=("serve", self._loop))`` trampoline idiom). A creation
        site lexically inside a loop (or comprehension) spawns the same
        roots CONCURRENTLY with themselves — the worker-pool pattern —
        so it contributes two contexts."""
        out: List[Set[str]] = []
        for fn in methods.values():
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and _is_threading_call(ctx, node.func, ("Thread",))):
                    continue
                roots: Set[str] = set()
                for sub in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    for ref in ast.walk(sub):
                        attr = _self_attr(ref)
                        if attr and attr in methods:
                            roots.add(attr)
                if not roots:
                    continue
                out.append(roots)
                cur = ctx.parent(node)
                while cur is not None and cur is not fn:
                    if isinstance(cur, (ast.For, ast.AsyncFor, ast.While,
                                        ast.ListComp, ast.SetComp,
                                        ast.GeneratorExp)):
                        out.append(set(roots))   # N spawns race each other
                        break
                    cur = ctx.parent(cur)
        return out

    def _call_edges(self, methods: Dict[str, ast.AST],
                    lock_attrs: Set[str]):
        """``(caller, callee, locks_held_at_site)`` for every own-method
        reference inside a method body — direct ``self.m()`` calls and
        method references passed around as callbacks (conservative:
        a referenced method may run)."""
        edges = []
        for name, fn in methods.items():
            for node in ast.walk(fn):
                attr = _self_attr(node)
                if attr and attr in methods and attr != name and \
                        isinstance(node.ctx, ast.Load):
                    edges.append((name, attr,
                                  self._locks_at(node, fn, lock_attrs)))
        return edges

    @staticmethod
    def _locks_at(node: ast.AST, fn: ast.AST,
                  lock_attrs: Set[str]) -> Set[str]:
        """Lock attributes held at ``node`` — enclosing ``with
        self.<lock>:`` blocks up to the method root."""
        held: Set[str] = set()
        cur = getattr(node, "_zl_parent", None)
        while cur is not None and cur is not fn:
            if isinstance(cur, (ast.With, ast.AsyncWith)):
                for item in cur.items:
                    attr = _self_attr(item.context_expr)
                    if attr and attr in lock_attrs:
                        held.add(attr)
            cur = getattr(cur, "_zl_parent", None)
        return held

    def _check_class(self, ctx: ModuleContext, cls: ast.ClassDef,
                     sev: str) -> Iterator[Finding]:
        methods = self._methods(cls)
        contexts = self._thread_contexts(ctx, cls, methods)
        if len(contexts) < 2:
            return      # fewer than two thread entry points: no sharing
        lock_attrs = self._lock_attrs(ctx, cls, methods)
        edges = self._call_edges(methods, lock_attrs)

        # reachability per thread context over the call graph
        reach: List[Set[str]] = []
        for roots in contexts:
            seen = set(roots)
            frontier = list(roots)
            while frontier:
                cur = frontier.pop()
                for caller, callee, _ in edges:
                    if caller == cur and callee not in seen:
                        seen.add(callee)
                        frontier.append(callee)
            reach.append(seen)
        threaded: Set[str] = set().union(*reach)

        # minimal locks guaranteed held on ENTRY to each method: the
        # intersection over every known call site's (locks at site +
        # caller's own guaranteed locks). Thread roots hold none; other
        # methods start UNKNOWN and only take a value once a known
        # caller reaches them — starting them at "no locks" instead
        # would poison the meet (X & anything = X) and un-guard every
        # callee of an always-locked helper
        roots_all: Set[str] = set().union(*contexts)
        inherited: Dict[str, Set[str]] = {m: set() for m in roots_all}
        for _ in range(len(methods) + 1):
            changed = False
            for m in threaded - roots_all:
                sites = [locks | inherited[caller]
                         for caller, callee, locks in edges
                         if callee == m and caller in inherited]
                if not sites:
                    continue            # no known caller yet
                new = set.intersection(*sites)
                if inherited.get(m) != new:
                    inherited[m] = new
                    changed = True
            if not changed:
                break

        # writes: self.<attr> = / += / self.<attr>[k] = inside threaded
        # methods, with the locks held at the write site
        writes: Dict[str, List[Tuple[str, int, Set[str]]]] = {}
        for name in threaded:
            fn = methods.get(name)
            if fn is None:
                continue
            for node in ast.walk(fn):
                targets: List[ast.AST] = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    attr = _self_attr(t)
                    if attr is None and isinstance(t, ast.Subscript):
                        attr = _self_attr(t.value)
                    if attr is None or attr in lock_attrs:
                        continue
                    held = self._locks_at(node, fn, lock_attrs) \
                        | inherited.get(name, set())
                    writes.setdefault(attr, []).append(
                        (name, node.lineno, held))

        for attr in sorted(writes):
            ws = writes[attr]
            hit = [i for i, r in enumerate(reach)
                   if any(w[0] in r for w in ws)]
            if len(hit) < 2:
                continue
            common = set.intersection(*(w[2] for w in ws))
            if common:
                continue
            first = min(ws, key=lambda w: w[1])
            methods_writing = sorted({w[0] for w in ws})
            yield self.finding(
                ctx, first[1],
                f"attribute `self.{attr}` is written from "
                f"{len(hit)} thread entry points "
                f"({', '.join(methods_writing)}) without one shared "
                f"threading.Lock guarding every write path — "
                f"read-modify-write interleavings corrupt it under "
                f"load; wrap the writes in `with self.<lock>:`",
                severity=sev)


# ---------------------------------------------------------------------------
# ZL015 — metric naming / labeling convention drift
# ---------------------------------------------------------------------------

#: non-base-unit duration suffixes (OBSERVABILITY.md: durations are
#: `_seconds`, quantile summaries `_quantiles_seconds`)
_BAD_UNIT_SUFFIXES = ("_ms", "_msec", "_millis", "_milliseconds", "_us",
                      "_micros", "_microseconds", "_ns", "_nanos",
                      "_nanoseconds", "_mins", "_minutes", "_hours",
                      "_days", "_sec", "_secs")


@register
class MetricNamingDrift(Rule):
    """**Metric naming/labeling drift.** The OBSERVABILITY.md convention
    (``zoo_<layer>_<what>[_unit]``; counters end ``_total``, durations
    ``_seconds``, summaries ``_quantiles_seconds``) is what dashboards
    and the catalog reconciliation key on — a misnamed family is
    invisible to both. Worse is cardinality: a label whose value comes
    from request data (a uri, a trace id) mints one series per distinct
    value and grows the registry without bound — label values must be
    constants, literal-loop enumerations, or a justified bounded set
    (suppress with the rationale). Error in package code, warning
    elsewhere."""

    id = "ZL015"
    severity = ERROR

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        from .contracts import iter_metric_sites
        sev = ERROR if _in_package(ctx.path) else WARNING
        for s in iter_metric_sites(ctx):
            if s.name is None:
                yield self.finding(
                    ctx, s.line,
                    "metric name is not statically resolvable — use a "
                    "string constant (or constant f-string) so the "
                    "catalog reconciliation can see the family",
                    severity=sev)
            else:
                yield from self._name_findings(ctx, s, sev)
            if s.opaque_labels:
                yield self.finding(
                    ctx, s.line,
                    "labels= is not a dict literal — the label keys "
                    "cannot be checked against the catalog; inline the "
                    "dict", severity=sev)
            for key in s.dynamic_label_keys:
                yield self.finding(
                    ctx, s.line,
                    f"label '{key}' takes a runtime value here — "
                    f"unbounded series cardinality if it derives from "
                    f"request data; use constants or a literal "
                    f"enumeration (or suppress with the bounded-set "
                    f"rationale)", severity=sev)

    def _name_findings(self, ctx: ModuleContext, s,
                       sev: str) -> Iterator[Finding]:
        name = s.name
        plain = name.replace("*", "")
        if not re.match(r"[a-z*][a-z0-9_*]*\Z", name):
            yield self.finding(
                ctx, s.line,
                f"metric name '{name}' is not a valid Prometheus "
                f"family name ([a-z][a-z0-9_]*)", severity=sev)
            return
        if not name.startswith("zoo_") and not name.startswith("*"):
            yield self.finding(
                ctx, s.line,
                f"metric name '{name}' is not `zoo_`-prefixed — the "
                f"package namespace every dashboard and the catalog "
                f"key on", severity=sev)
        wildcard_tail = name.endswith("*")
        if s.kind == "counter" and not wildcard_tail \
                and not name.endswith("_total"):
            yield self.finding(
                ctx, s.line,
                f"counter '{name}' must end in `_total` (Prometheus "
                f"rate() semantics key on the suffix)", severity=sev)
        if s.kind in ("gauge", "histogram") and name.endswith("_total"):
            yield self.finding(
                ctx, s.line,
                f"{s.kind} '{name}' ends in `_total` — that suffix "
                f"promises a monotonic counter", severity=sev)
        if s.kind == "summary" and not wildcard_tail \
                and not name.endswith("_quantiles_seconds"):
            yield self.finding(
                ctx, s.line,
                f"summary '{name}' must end in `_quantiles_seconds` "
                f"(the histogram sibling keeps the bare `_seconds` "
                f"name)", severity=sev)
        for suf in _BAD_UNIT_SUFFIXES:
            # `_per_<unit>` names are RATES (records_per_sec), not
            # durations — the unit there is a denominator, not a quantity
            if plain.endswith(suf) and "_per" + suf not in plain:
                yield self.finding(
                    ctx, s.line,
                    f"metric name '{name}' uses a non-base unit "
                    f"(`{suf}`) — durations are `_seconds` in the "
                    f"catalog convention", severity=sev)
                break
