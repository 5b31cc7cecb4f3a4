"""A ledger for the device's step: every operation of the compiled train
step under one ``zoo_*`` scope and one pass.

``GoodputLedger`` puts every HOST second of a ``fit`` in one category; this
module does the same for the instructions of the compiled ``train.step``.
The program opens ``jax.named_scope``s from the closed set ``SCOPES`` where
the work happens (metadata only: a scope changes no instruction), JAX writes
them, and the transformation an operation came from, into the operation's
``op_name`` (``jit(plain)/transpose(jvp(zoo_norm))/checkpoint/
rematted_computation/zoo_attn.proj/dot_general``), and ``classify`` reads
one ``(scope, pass)`` out of that path:

* scope: the innermost (last) component that is a name of ``SCOPES`` or the
  containers' fallback ``zoo_layer.<ClassName>`` (``engine.dispatch_layer``;
  a layer with scopes of its own gets none, so an inner ``zoo_*`` scope
  always wins over it); else ``unscoped``;
* pass: ``recompute`` where the path holds ``rematted_computation`` (the
  second forward of a ``jax.checkpoint``), else ``backward`` where it holds
  ``transpose(``, else ``update`` for a ``zoo_opt.*`` scope, else
  ``forward``.

``census`` walks a compiled module's text and counts, by class, the
instructions that run as events of their own on the device: exclusive and
exhaustive. The control-flow wrappers (``while``, ``conditional``, ``call``)
are whole events AROUND the instructions of their bodies, which are counted;
the wrappers are listed apart and never counted. ``fit`` hands the census
out as ``model.last_fit_report["step_census"]`` where ``zoo.metrics.flops``
already compiles the step for its cost analysis; ``benchmark/lib/
step_ledger.py`` joins the same classes with the device trace's seconds.

No import of JAX: the module reads text.
"""

from __future__ import annotations

import re
from typing import Any, Dict, FrozenSet, Iterator, List, Tuple

__all__ = ["SCOPES", "LAYER_SCOPE", "PASSES", "UNSCOPED", "WRAPPERS",
           "classify", "census", "instructions"]

#: the scopes the program opens, each with what runs under it. A scope is
#: opened INSIDE ``cond`` branches, loop bodies and checkpointed functions,
#: never round the ``cond`` / ``fori_loop`` / ``jax.checkpoint`` call.
SCOPES: Dict[str, str] = {
    "zoo_embed": "token, position and token-type lookups, the sum, embedding "
                 "dropout and norm (a stack's rotary tables stay under "
                 "zoo_attn.rope / zoo_mla.rope)",
    "zoo_attn.proj": "the q, k, v products of an attention layer, fused or "
                     "not",
    "zoo_attn.rope": "rotary tables and the rotation of q and k",
    "zoo_attn.qk_norm": "the RMSNorm over each head's columns of q and of k",
    "zoo_attn.attend": "the flash kernels, or the XLA softmax attention with "
                       "its mask and dropout; the ring over a seq mesh",
    "zoo_attn.out": "the output product of an attention layer and its "
                    "dropout",
    "zoo_mla.q_latent": "latent attention: x Wqa and the latent's RMSNorm",
    "zoo_mla.kv_latent": "latent attention: x Wkva, the norm, the k_pe slice",
    "zoo_mla.expand": "latent attention: the up-projections Wqb / Wkvb and "
                      "what places k_pe in every key head",
    "zoo_mla.rope": "latent attention: tables and rotation of the rotary "
                    "slices",
    "zoo_mla.attend": "latent attention: the flash kernels or the XLA op",
    "zoo_mla.out": "latent attention: the output product Wo",
    "zoo_conv.in_proj": "short-convolution mixer: a Win",
    "zoo_conv.gate": "short-convolution mixer: the gate-conv-gate chain",
    "zoo_conv.out_proj": "short-convolution mixer: Wout",
    "zoo_ffn.dense": "a post-LN block's two products, their activation and "
                     "dropout",
    "zoo_ffn.gated": "a dense SiLU-gated feed-forward layer",
    "zoo_moe.route": "routed experts: router product, scores, top-k, sorts",
    "zoo_moe.dispatch": "routed experts: the row gather",
    "zoo_moe.experts": "routed experts: the grouped products and the gate",
    "zoo_moe.combine": "routed experts: the weighted gather-sum to tokens",
    "zoo_moe.shared": "routed experts: the shared expert",
    "zoo_norm": "a block's glue: LayerNorm / RMSNorm and the residual add "
                "beside it; a stack's final norm",
    "zoo_loss": "the objective or the fused cross-entropy kernels and what "
                "stands round them, a tied head's or a pooler's product, the "
                "auxiliary-loss sum",
    "zoo_opt.update": "opt.update, apply_updates and the two sharding pins",
    "zoo_opt.guard": "global gradient norm, sentinel check, clip, fault "
                     "injection",
}

#: the containers' fallback: ``zoo_layer.<ClassName>`` round every layer a
#: container dispatches, but for those that say ``layer_scope = False``
LAYER_SCOPE = "zoo_layer."
UNSCOPED = "unscoped"
PASSES = ("forward", "backward", "recompute", "update")
#: whole events around the instructions of their bodies
WRAPPERS = ("while", "conditional", "call")
#: never events of their own
_NO_EVENT = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")

_COMPONENT = re.compile(r"[/()]")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLEES = re.compile(
    r"\b(?:body|condition|true_computation|false_computation|to_apply)="
    r"%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")


def classify(op_name: str) -> Tuple[str, str]:
    """``(scope, pass)`` of one operation, from its ``op_name`` path. A
    component counts as a scope where it is a name of ``SCOPES`` or a
    container's ``zoo_layer.<ClassName>``: a Pallas kernel's own name
    (``.../zoo_attn.attend/zoo_flash_fwd/pallas_call``) is a component
    too, and no scope."""
    scope = UNSCOPED
    for part in _COMPONENT.split(op_name):
        if part in SCOPES or part.startswith(LAYER_SCOPE):
            scope = part
    if "rematted_computation" in op_name:
        return scope, "recompute"
    if "transpose(" in op_name:
        return scope, "backward"
    return scope, "update" if scope.startswith("zoo_opt.") else "forward"


def _opcode(body: str) -> str:
    """The opcode of an instruction line (``%name = <shape> opcode(...)``);
    the shape may be a tuple and carry layouts."""
    rest = body.partition(" = ")[2]
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0].strip()
    return ""


def instructions(hlo_text: str
                 ) -> Iterator[Tuple[str, str, str, str, FrozenSet[str]]]:
    """``(line, opcode, scope, pass, inside)`` of every instruction of a
    compiled module's text that the device runs as an event of its own, the
    wrappers among them: the instructions of the entry computation and of
    what it reaches through ``while`` / ``conditional`` / ``call``, not
    those of fused computations, reducers or comparators, and not
    ``parameter`` / ``constant`` / ``tuple`` / ``get-tuple-element`` /
    ``bitcast``. A fusion the compiler left without an ``op_name`` of its
    own is classed by its fused computation's: the root's, or the nearest
    one before it. ``inside`` holds the scopes of a fusion's fused
    instructions: XLA fuses a LayerNorm's backward or a weight's Adam
    update into the product beside it, one event under the product's name,
    and ``inside`` still says what else the event holds."""
    bodies: Dict[str, List[str]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _HEADER.match(line)
            current = m.group(2) if m else None
            if m:
                bodies[current] = []
                if m.group(1):
                    entry = current
        elif current is not None and " = " in line:
            body = line.strip()
            bodies[current].append(body[5:] if body.startswith("ROOT ")
                                   else body)
    todo, seen = [entry] if entry else [], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in bodies:
            continue
        seen.add(name)
        for body in bodies[name]:
            opcode = _opcode(body)
            if opcode in _NO_EVENT:
                continue
            if opcode in WRAPPERS:
                todo.extend(_CALLEES.findall(body))
                for group in _BRANCHES.findall(body):
                    todo.extend(n.strip().lstrip("%")
                                for n in group.split(","))
            fused = _FUSED.search(body) if opcode == "fusion" else None
            inner = [n for line in bodies.get(fused.group(1), ())
                     for n in _OP_NAME.findall(line)] if fused else []
            named = _OP_NAME.findall(body) or inner
            yield (body, opcode) + classify(named[-1] if named else "") + (
                frozenset(classify(n)[0] for n in inner) - {UNSCOPED},)


def census(hlo_text: str) -> Dict[str, Any]:
    """The instruction count of a compiled step by class: ``by_scope``,
    ``by_pass``, ``by_scope_pass`` (``{scope: {pass: n}}``), ``unscoped``
    and ``instructions`` (their sum: each counted instruction is in exactly
    one class), ``unnamed`` (those of the unscoped that carry no
    ``op_name`` at all: what the compiler inserted, prefetch copies and
    layout changes, which no scope of the program can reach), and
    ``wrappers`` (``{opcode: {scope: n}}``): the ``while`` / ``conditional``
    / ``call`` instructions, counted nowhere else."""
    by_class: Dict[str, Dict[str, int]] = {}
    wrappers: Dict[str, Dict[str, int]] = {}
    unnamed = 0
    for line, opcode, scope, pass_, _inside in instructions(hlo_text):
        wrapper = opcode in WRAPPERS
        held = (wrappers.setdefault(opcode, {}) if wrapper
                else by_class.setdefault(scope, {}))
        name = scope if wrapper else pass_
        held[name] = held.get(name, 0) + 1
        unnamed += (not wrapper and scope == UNSCOPED
                    and "op_name=" not in line)
    by_scope = {scope: sum(row.values()) for scope, row in by_class.items()}
    by_pass = {p: sum(row.get(p, 0) for row in by_class.values())
               for p in PASSES}
    return {"by_scope": by_scope,
            "by_pass": {p: n for p, n in by_pass.items() if n},
            "by_scope_pass": by_class,
            "unscoped": by_scope.get(UNSCOPED, 0), "unnamed": unnamed,
            "instructions": sum(by_scope.values()), "wrappers": wrappers}
