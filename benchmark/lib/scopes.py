"""Device time by ``jax.named_scope``, for the per-layer metrics that read a
scope and not a kernel's name.

The reduced trace (``trace.py``) keys device time by ``<instruction>
<result shape>``; a scope is not part of that key. The compiled train step
is: every instruction of its HLO text carries ``metadata={op_name="...
/zoo_moe.experts/..."}``. ``scope_seconds`` keys the text's instructions as
the trace keys its events and sums the traced time of the keys a scope's
instructions have. Where instructions inside and outside the scope share a
key (same operation, same shape), the key's time is split by their count.
Fused computations' inner instructions never run as events of their own and
are left out.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

from . import trace as trace_lib

_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def step_text(view: Dict[str, Any]) -> Optional[str]:
    """The compiled train step's HLO text (from the persistent cache, where
    the run's own compilation left it); kept on ``view`` for the next
    reader."""
    if "_step_text" not in view:
        import jax

        from analytics_zoo_tpu.parallel import mesh as mesh_lib
        model = view["model"]
        loop = model._loop
        x, y = view["batch"]
        bsh = mesh_lib.batch_sharding(loop.mesh)
        view["_step_text"] = loop._train_step.trace(
            model.params, model.opt_state, model.net_state,
            jax.random.key(0), jax.device_put(x, bsh),
            jax.device_put(y, bsh)).lower().compile().as_text()
    return view["_step_text"]


def scope_shares(text: str, marker: str) -> Dict[str, float]:
    """``{trace key: share of its instructions whose op_name holds
    marker}`` over the instructions that run as events of their own."""
    inside: Dict[str, int] = {}
    total: Dict[str, int] = {}
    fused = False
    for line in text.splitlines():
        if line and not line[0].isspace():
            # a computation's header or its closing brace
            fused = line.startswith(("%fused_computation", "fused_computation",
                                     "%bitcast_fusion", "bitcast_fusion"))
            continue
        body = line.strip()
        if fused or " = " not in body:
            continue
        if body.startswith("ROOT "):
            body = body[5:]
        key = trace_lib.op_key(body)
        total[key] = total.get(key, 0) + 1
        m = _OP_NAME.search(body)
        if m and marker in m.group(1):
            inside[key] = inside.get(key, 0) + 1
    return {k: n / total[k] for k, n in inside.items()}


def scope_seconds(trace: Dict[str, Any], text: str, marker: str,
                  kernels=()) -> float:
    """Traced device seconds of the operations under the scope ``marker``,
    and of the calls whose own name holds one of ``kernels`` (a custom call
    that XLA expands an operation into keeps the operation's name and
    loses the scope: ``ragged-dot``), each key counted once."""
    shares = scope_shares(text, marker)
    total = 0.0
    for key, secs in trace["op_seconds"].items():
        if any(k in key.split(" ", 1)[0] for k in kernels):
            total += secs
        elif key in shares:
            total += secs * shares[key]
    return total
