"""Device-mesh management — the TPU-native replacement for the reference's
Spark executor topology.

In the reference (robert-sbd/analytics-zoo), physical parallelism is organised by
``Engine.init`` counting Spark executors and cores
(``common/NNContext.scala:133-149``) and data parallelism is the only axis
(``docs/docs/wp-bigdl.md:113``).  Here the physical layer is a
``jax.sharding.Mesh`` over TPU chips with up to four logical axes:

* ``data``  — data parallelism (the reference's per-partition model replicas,
  ``Topology.scala:1150-1158``),
* ``model`` — tensor/model parallelism (absent in the reference; greenfield),
* ``seq``   — sequence/context parallelism (absent in the reference),
* ``expert`` — expert parallelism for MoE layers (absent in the reference),
* ``pipe``  — pipeline parallelism (GPipe microbatch schedule; absent in the
  reference).

Collectives ride ICI within a mesh; XLA inserts psum/all-gather from sharding
annotations, replacing BigDL's Spark-BlockManager ``AllReduceParameter``
(``wp-bigdl.md:140-160``).
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"

ALL_AXES = (DATA_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS, PIPE_AXIS)

_global_mesh: Optional[Mesh] = None


def create_mesh(
    data: int = -1,
    model: int = 1,
    seq: int = 1,
    expert: int = 1,
    pipe: int = 1,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create a logical mesh over the available devices.

    ``data=-1`` means "absorb all remaining devices", mirroring how the
    reference sizes data parallelism to the cluster (one model replica per
    Spark partition, ``Topology.scala:1102-1110``).

    The axis order is (data, pipe, seq, expert, model), placing the model
    axis innermost so tensor-parallel collectives ride the fastest ICI links
    and the pipe axis outermost-but-one so stage hops cross the slowest
    links only once per microbatch.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    fixed = model * seq * expert * pipe
    if data == -1:
        if n % fixed != 0:
            raise ValueError(
                f"device count {n} not divisible by "
                f"model*seq*expert*pipe={fixed}"
            )
        data = n // fixed
    total = data * fixed
    if total != n:
        raise ValueError(
            f"mesh {data}x{pipe}x{seq}x{expert}x{model}={total} "
            f"!= device count {n}"
        )
    dev_array = np.asarray(devices).reshape(data, pipe, seq, expert, model)
    return Mesh(dev_array,
                (DATA_AXIS, PIPE_AXIS, SEQ_AXIS, EXPERT_AXIS, MODEL_AXIS))


def set_global_mesh(mesh: Mesh) -> None:
    global _global_mesh
    _global_mesh = mesh


def global_mesh() -> Mesh:
    """Return the process-wide mesh, creating a pure-DP mesh on first use."""
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = create_mesh()
    return _global_mesh


def reset_global_mesh() -> None:
    global _global_mesh
    _global_mesh = None


def in_manual_region() -> bool:
    """True while tracing the body of a ``shard_map``: arrays there are
    per-shard blocks already, so a caller that would otherwise open a
    ``shard_map`` of its own (the Pallas wrappers in ``ops/``) must not."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def data_parallel_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or global_mesh()
    return mesh.shape[DATA_AXIS]


def mesh_metadata(mesh: Optional[Mesh] = None) -> dict:
    """JSON-serializable topology descriptor — stored in checkpoint
    manifests (``utils/checkpoint.py``) so a restore under a DIFFERENT
    device count/mesh shape is detected and re-placed instead of
    silently mis-sharded. Host-side snapshot leaves are topology-free;
    this records only what the snapshot was cut under."""
    mesh = mesh or global_mesh()
    return {"axes": {str(k): int(v) for k, v in mesh.shape.items()},
            "devices": int(mesh.devices.size)}


def format_mesh(meta: Optional[dict]) -> str:
    """Compact human form of :func:`mesh_metadata` output for log lines:
    ``{data:8}`` (singleton axes elided; ``{}`` when all are 1)."""
    axes = (meta or {}).get("axes", {}) or {}
    kept = {k: v for k, v in axes.items() if int(v) != 1}
    inner = ", ".join(f"{k}:{v}" for k, v in kept.items())
    return "{" + inner + "}"


def batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Sharding for a batch: leading dim split over the data axis."""
    mesh = mesh or global_mesh()
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Fully-replicated sharding (the reference replicates parameters whole
    per worker, ``Topology.scala:1118-1120``)."""
    mesh = mesh or global_mesh()
    return NamedSharding(mesh, P())


def param_shardings(model, params, mesh: Optional[Mesh] = None):
    """Per-leaf NamedSharding tree for a model's params: layers declare
    PartitionSpecs over the ``model`` axis via ``Layer.param_sharding``
    (Dense/Embedding shard; everything else replicates). On a mesh without
    tensor parallelism everything replicates — the pure-DP fast path."""
    import jax

    mesh = mesh or global_mesh()
    repl = replicated_sharding(mesh)
    # fast path only when NO param-bearing axis exists: expert-stacked MoE
    # weights shard over ``expert``, GPipe stage stacks over ``pipe``, even
    # without tensor parallelism
    if (mesh.shape[MODEL_AXIS] * mesh.shape[EXPERT_AXIS]
            * mesh.shape[PIPE_AXIS] == 1
            or not hasattr(model, "param_sharding")):
        return jax.tree.map(lambda _: repl, params)
    spec_tree = model.param_sharding(params)
    fallbacks: list = []

    def to_sharding(path, spec, leaf):
        if spec is None:
            return repl
        # a dim that doesn't divide by its axis size can't shard — fall back
        # to replicated for that leaf (e.g. a 3-class head under model=2)
        shape = np.shape(leaf)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            if i >= len(shape) or shape[i] % mesh.shape[ax] != 0:
                fallbacks.append((jax.tree_util.keystr(path), shape, spec))
                return repl
        return NamedSharding(mesh, spec)

    out = jax.tree_util.tree_map_with_path(
        to_sharding, spec_tree, params,
        is_leaf=lambda s: s is None or isinstance(s, P))
    if fallbacks:
        # ONE summary line — count + first offender. Per-leaf spam (a W
        # and b line per undividable head, re-listed on every run) buried
        # the signal in multichip logs; anyone chasing the rest can log
        # analytics_zoo_tpu.mesh at DEBUG.
        import logging
        logger = logging.getLogger("analytics_zoo_tpu.mesh")
        first_p, first_s, first_sp = fallbacks[0]
        logger.warning(
            "%d param leaf/leaves replicated instead of model-sharded "
            "(dim not divisible by axis size); first offender: %s shape=%s "
            "spec=%s", len(fallbacks), first_p, first_s, first_sp)
        if len(fallbacks) > 1:
            logger.debug("all replicated-fallback leaves: %s",
                         "; ".join(f"{p} shape={s} spec={sp}"
                                   for p, s, sp in fallbacks))
    return out


def zero_sharding_for(base: NamedSharding, shape,
                      mesh: Optional[Mesh] = None) -> NamedSharding:
    """ZeRO-1 placement for one param-shaped optimizer-state leaf (SURVEY
    §2.4: the TPU-native replacement for the reference's sliced
    ``AllReduceParameter``, ``wp-bigdl.md:140-160``, which shards optimizer
    state across workers): take the leaf's existing param sharding (model/
    expert axes intact) and partition the first still-unsharded dim whose
    size divides the ``data`` axis. Leaves with no such dim stay on their
    base sharding — correct, just not memory-sharded.

    Under jit this annotation is all GSPMD needs: the gradient reduction
    feeding the moment update lowers to reduce-scatter and the updated
    params all-gather back, instead of a full all-reduce with replicated
    moments."""
    mesh = mesh or global_mesh()
    dp = mesh.shape[DATA_AXIS]
    if dp <= 1:
        return base
    spec = list(base.spec) + [None] * (len(shape) - len(base.spec))
    for i, (ax, dim) in enumerate(zip(spec, shape)):
        if ax is None and dim % dp == 0:
            spec[i] = DATA_AXIS
            return NamedSharding(mesh, P(*spec))
    return base
