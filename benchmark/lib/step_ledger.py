"""The device's step by scope and pass: the join of the reduced device
trace (``trace.py``: seconds by ``<instruction> <result shape>``) with the
classes the program itself gives the instructions of its compiled train
step (``analytics_zoo_tpu/observability/step_ledger.py``: one ``zoo_*``
scope and one pass, forward / backward / recompute / update, for every
instruction that runs as an event of its own). ``classify`` is imported
from the program, so what the operator's ``step_census`` counts and what
these metrics time cannot drift apart; a checkout from before PR 38 has no
such module and every reader here reads nothing.

Each trace key's seconds are split over the classes of the step's
instructions that share the key, by their count (``scopes.py``'s rule, and
its limit: per-instruction times would end it). The control-flow wrappers
(``while`` / ``conditional`` / ``call``) are whole events AROUND events that
are counted, and are left out; keys the step's text does not hold belong to
other programs of the window (the eager ``fold_in`` that derives a step's
key, the loss reduction). So

    placed_s + unscoped_s + other_programs_s + wrapper_s == sum(op_seconds)

and the first three together are the device's busy time, each operation
counted once.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Optional, Sequence

from . import scopes
from . import trace as trace_lib

try:
    from analytics_zoo_tpu.observability import step_ledger as program
except ImportError:     # the program of a checkout before PR 38
    program = None

#: every compiled train step of the program holds this scope
MARKER = "zoo_opt.update"


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def step_text(view: Dict[str, Any]) -> str:
    """``scopes.step_text``, compiled again where the text it found carries
    another checkout's scopes. JAX's persistent compile cache leaves an
    operation's metadata out of its key, so a step that differs from an
    older checkout's in scopes alone loads THAT executable, ``op_name``s
    and all: same instructions, same events, stale names. Compiling with
    the metadata in the key misses that entry and writes one of its own."""
    text = scopes.step_text(view)
    if MARKER in text:
        return text
    import jax
    _log(f"the compiled step's text holds no {MARKER}: it was loaded from "
         f"a compile-cache entry another checkout wrote; compiling it again "
         f"with the metadata in the cache's key")
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        # the process keeps the loaded executable by its lowered module:
        # only a step traced and lowered anew is compiled anew
        jax.clear_caches()
        del view["_step_text"]
        text = scopes.step_text(view)
    finally:
        jax.config.update(flag, before)
    return text


def ledger(view: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"busy_s", "placed_s", "unscoped_s", "other_programs_s",
    "wrapper_s", "by_scope", "by_pass", "by_scope_pass", "holds", "keys"}``
    of the traced window, kept on ``view``; None without a trace or without
    the program's classifier. ``by_scope`` / ``by_pass`` / ``by_scope_pass``
    (``{scope: {pass: s}}``) hold the step's instructions, ``unscoped``
    among the scopes; ``placed_s`` is what lies under a ``zoo_*`` scope;
    ``holds`` is, by scope, the seconds of the events that carry its name
    OR hold an instruction of it inside their fused computation (an upper
    bound beside ``by_scope``'s lower one: XLA fuses a norm's backward or a
    weight's update into the product beside it, one event, one name);
    ``keys`` is ``{trace key: {(scope, pass): s}}`` for readers that also
    go by a kernel's name."""
    if "_step_ledger" in view:
        return view["_step_ledger"]
    tr = view["trace"]
    if tr is None or program is None or tr["busy_s"] <= 0:
        return None
    counts: Dict[str, Dict[Any, int]] = {}
    touched: Dict[str, Dict[str, int]] = {}
    for line, opcode, scope, pass_, inside in program.instructions(
            step_text(view)):
        key = trace_lib.op_key(line)
        held = counts.setdefault(key, {})
        cls = None if opcode in program.WRAPPERS else (scope, pass_)
        held[cls] = held.get(cls, 0) + 1
        if cls is not None:
            for name in inside | {scope}:
                row = touched.setdefault(key, {})
                row[name] = row.get(name, 0) + 1
    keys: Dict[str, Dict[Any, float]] = {}
    by_class: Dict[str, Dict[str, float]] = {}
    holds: Dict[str, float] = {}
    other = wrapper = 0.0
    for key, secs in tr["op_seconds"].items():
        held = counts.get(key)
        if held is None:
            other += secs
            continue
        n = sum(held.values())
        for name, c in touched.get(key, {}).items():
            holds[name] = holds.get(name, 0.0) + secs * c / n
        for cls, c in held.items():
            if cls is None:
                wrapper += secs * c / n
                continue
            keys.setdefault(key, {})[cls] = secs * c / n
            row = by_class.setdefault(cls[0], {})
            row[cls[1]] = row.get(cls[1], 0.0) + secs * c / n
    by_scope = {s: sum(row.values()) for s, row in by_class.items()}
    by_pass = {p: sum(row[p] for row in by_class.values() if p in row)
               for p in program.PASSES}
    unscoped = by_scope.get(program.UNSCOPED, 0.0)
    led = {"busy_s": tr["busy_s"],
           "placed_s": sum(by_scope.values()) - unscoped,
           "unscoped_s": unscoped, "other_programs_s": other,
           "wrapper_s": wrapper, "by_scope": by_scope,
           "by_pass": {p: s for p, s in by_pass.items() if s},
           "by_scope_pass": by_class, "holds": holds, "keys": keys}
    view["_step_ledger"] = led
    _report(led)
    return led


def seconds(led: Dict[str, Any], prefixes: Sequence[str],
            kernels: Sequence[str] = ()) -> float:
    """Seconds under the scopes that start with one of ``prefixes``, and of
    the events whose own name holds one of ``kernels``, each event once."""
    total = 0.0
    for key, held in led["keys"].items():
        if any(k in key.split(" ", 1)[0] for k in kernels):
            total += sum(held.values())
        else:
            total += sum(s for (scope, _), s in held.items()
                         if scope.startswith(tuple(prefixes)))
    return total


def share(view: Dict[str, Any], what) -> Optional[float]:
    """100 x ``what(ledger)`` over the window's busy time; None where there
    is no ledger."""
    led = ledger(view)
    return None if led is None else 100.0 * what(led) / led["busy_s"]


def _dump_dir() -> Optional[str]:
    """``--dump`` of the run's command line (``view`` does not carry it)."""
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--dump" and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith("--dump="):
            return arg.split("=", 1)[1]
    return None


def _report(led: Dict[str, Any]) -> None:
    """The whole ``(scope, pass)`` table, once a traced run: to stderr, and
    into ``--dump`` with the largest unscoped keys."""
    busy = led["busy_s"]
    _log("step ledger (s of busy {:.3f}): placed {:.3f} unscoped {:.3f} "
         "other_programs {:.3f} wrappers(left out) {:.3f}; by pass {}".format(
             busy, led["placed_s"], led["unscoped_s"],
             led["other_programs_s"], led["wrapper_s"],
             json.dumps({p: round(s, 4)
                         for p, s in sorted(led["by_pass"].items())})))
    for scope, row in sorted(led["by_scope_pass"].items(),
                             key=lambda kv: -sum(kv[1].values())):
        _log("step ledger  {:<20s} {:8.4f} s {:6.2f} %  (holds {:6.2f} %)  "
             "{}".format(
                 scope, sum(row.values()), 100.0 * sum(row.values()) / busy,
                 100.0 * led["holds"].get(scope, 0.0) / busy,
                 " ".join(f"{p}={s:.4f}" for p, s in sorted(row.items()))))
    loose = sorted(((sum(s for (scope, _), s in held.items()
                         if scope == program.UNSCOPED), key)
                    for key, held in led["keys"].items()), reverse=True)
    for secs, key in loose[:8]:
        if secs > 0:
            _log(f"step ledger  unscoped: {secs:.4f} s {key}")
    dump = _dump_dir()
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, "step_ledger.json"), "w") as f:
            json.dump({**{k: v for k, v in led.items() if k != "keys"},
                       "unscoped_keys": [[k, s] for s, k in loose if s > 0],
                       "keys": {k: {f"{scope}|{p}": s
                                    for (scope, p), s in held.items()}
                                for k, held in led["keys"].items()}}, f)
