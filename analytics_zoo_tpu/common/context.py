"""Runtime bring-up — the TPU-native equivalent of the reference's
``NNContext.initNNContext`` (``common/NNContext.scala:133-149``) and pyzoo's
``init_nncontext`` (``pyzoo/zoo/common/nncontext.py:104``).

Where the reference creates a tuned SparkContext (conf merge at
``NNContext.scala:188-200``, KMP/OMP env pinning at ``NNContext.scala:209-237``)
and calls BigDL ``Engine.init``, this module:

* discovers JAX devices and process topology (multi-host over DCN),
* builds the global device ``Mesh`` (data/seq/expert/model axes),
* loads layered configuration (defaults < yaml file < env < kwargs),
* seeds the global PRNG and sets matmul precision policy.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Dict, Mapping, Optional

import jax

from ..parallel import mesh as mesh_lib

log = logging.getLogger("analytics_zoo_tpu")

#: Bundled defaults — the analogue of ``spark-analytics-zoo.conf``
#: (``zoo/src/main/resources/spark-analytics-zoo.conf``, loaded at
#: ``NNContext.scala:188-200``).
DEFAULT_CONF: Dict[str, Any] = {
    "zoo.mesh.data": -1,        # -1 = all remaining devices
    "zoo.mesh.model": 1,
    "zoo.mesh.seq": 1,
    "zoo.mesh.expert": 1,
    "zoo.mesh.pipe": 1,
    "zoo.seed": 0,
    # multi-host (DCN) bring-up — the reference's Spark executor topology
    # becomes the JAX multi-process runtime; empty coordinator = single host
    "zoo.distributed.coordinator": "",   # "host:port" of process 0
    "zoo.distributed.num_processes": 1,
    "zoo.distributed.process_id": 0,
    "zoo.matmul.precision": "default",   # default | high | highest
    "zoo.pallas.attention": "auto",      # auto (TPU only) | true | false
    "zoo.pallas.cross_entropy": "auto",  # fused-CE forward kernel: auto (TPU) | true | false
    "zoo.pallas.block_sweep": False,     # one-shot on-device block sweep per kernel signature
    "zoo.pallas.vmem_budget_mb": 0,      # 0 = the per-core default (16 MiB) for block selection (flash at a head wider than 128: two of them)
    "zoo.pallas.embed_gather": "auto",   # one-hot MXU expand-gather: auto (TPU) | true | false
    "zoo.rng.impl": "auto",              # auto (rbg on TPU) | default | rbg
    "zoo.seq.mode": "ring",              # seq-parallel routing: ring | ulysses | auto
    "zoo.seq.strict": False,             # fail (not warn) when attention can't ride the seq mesh
    "zoo.compute.dtype": "float32",      # float32 | bfloat16
    "zoo.train.zero_sharding": False,    # ZeRO-1: optimizer state sharded over data axis
    "zoo.train.fused_ce": "auto",        # fused blockwise LM-head CE: auto (V>=1024) | true | false
    "zoo.train.fused_ce_chunk": 512,     # rows per streamed logits tile (O(chunk*V) memory)
    "zoo.train.remat": False,            # step remat: false | true/dots | full
    "zoo.train.seq_attention": "off",    # force seq-parallel attention in the
    #   training step: off | ring | ulysses (needs a seq mesh axis; fallback
    #   to full attention becomes an error instead of a warning)
    "zoo.train.pipe_stages": 0,          # >0: cut the model's homogeneous block
    #   run into this many GPipe stages over the pipe mesh axis (0 = off)
    "zoo.train.pipe_microbatch": 0,      # GPipe microbatches per step (0 = the
    #   pipe-axis size; raise it to amortize the (n_micro + P - 1) bubble)
    # -- anomaly sentinels / self-healing training (docs/guides/TRAINING.md)
    "zoo.train.sentinel": "off",         # off | warn | recover: on-device
    #   nan-loss / nan-grad / grad-norm-spike checks folded into the step
    "zoo.train.spike_factor": 10.0,      # grad-norm spike = factor x its EWMA
    "zoo.train.grad_clip": 0.0,          # >0: global-norm gradient clipping in
    #   the train step (zoo_train_grad_clip_engaged_total)
    "zoo.train.max_skips_per_epoch": 8,  # recover mode: skips past this in one
    #   epoch escalate to rollback-to-last-good-checkpoint
    "zoo.train.max_rollbacks": 3,        # rollbacks per fit before the loop
    #   fails loudly with TrainingDiverged (RetryBudget-backed)
    # -- out-of-core sharded embeddings (docs/guides/TRAINING.md)
    "zoo.embed.sharded": "auto",         # row-partitioned dedup'd lookup for plain
    #   Embedding layers: auto (model>1 and rows divide) | true | false
    "zoo.embed.dedup": True,             # per-step unique-id dedup in the lookup
    "zoo.embed.hot_rows_budget_mb": 64,  # device budget for the oocore hot tier
    "zoo.embed.prefetch_depth": 2,       # staged plans ahead of the consuming step
    "zoo.metrics.flops": False,          # fit(): cost-analysis pass feeding the MFU gauge
    "zoo.failure.retry_times": 5,        # ≅ bigdl.failure.retryTimes (Topology.scala:1172)
    "zoo.failure.retry_window_sec": 3600,
    "zoo.faults.enabled": False,         # gate for common.faults.activate (chaos tests)
    "zoo.checkpoint.keep": 3,
    "zoo.checkpoint.on_sigterm": False,  # SIGTERM during fit → final sync snapshot + clean exit
    "zoo.checkpoint.sigterm_grace_s": 0.0,  # >0: cut a MID-EPOCH snapshot from the
    #   SIGTERM handler when the estimated time to the next step boundary
    #   exceeds this budget (preemption deadline shorter than a dispatch)
    # -- serving overload / degradation (docs/guides/RELIABILITY.md) --------
    "zoo.serving.shed_watermark": 0,     # stream-depth watermark; >0 sheds the
    #   newest records in each admission window once the backlog exceeds it
    "zoo.serving.adaptive_batch": False,  # AIMD batch-size control from the
    #   live backlog/queue-wait signals (zoo_serving_batch_size_target)
    "zoo.serving.queue_wait_target_ms": 500,  # queue-wait breach target the
    #   AIMD controller backs off against
    # -- serving device path: bucketing + multiplexing (SERVING.md) ---------
    "zoo.serving.shape_buckets": "",     # compiled-shape dispatch buckets, a
    #   comma-joined list of batch row counts ("" = powers of two up to
    #   batch_size); ragged reads pad up to a bucket instead of retracing jit
    "zoo.serving.dtype": "float32",      # serving precision path for models
    #   the server wraps (KerasNet lane specs): float32 | bfloat16 | int8
    #   (int8 = weight-only quantized inference, fp32 results on the wire)
    "zoo.serving.lane_max_inflight": "",  # per-lane dispatch-window ceilings,
    #   "lane:n,lane:n" — a big model's lane caps its in-flight batches so it
    #   cannot starve the other lanes ("" = the server-wide max_inflight)
    "zoo.serving.lane_batch_size": "",   # per-lane batch-size ceilings,
    #   "lane:n,lane:n" — caps the lane's dispatch size, bucket ladder, AIMD
    #   ceiling and arena rows ("" = the server-wide batch_size)
    "zoo.serving.dlq_dir": "",           # non-empty: spill dead-lettered records
    #   to this append-only on-disk DLQ (scripts/zoo-dlq replays them)
    "zoo.serving.dlq_max_bytes": 64 << 20,  # DLQ disk bound; oldest sealed
    #   segment evicted first once exceeded
    # -- fleet serving: consumer groups + coordinated backpressure ----------
    "zoo.serving.consumer_group": "serving",  # stream consumer group each
    #   replica joins ("" = legacy single-consumer consume-on-read)
    "zoo.serving.claim_idle_ms": 30000,  # pending entries idle past this are
    #   reclaimable by a surviving replica (crash-safe entry reclaim)
    "zoo.serving.max_deliveries": 5,     # deliveries (read + reclaims) past
    #   this dead-letter the entry instead of reclaiming it forever
    "zoo.serving.fleet_backpressure": False,  # InputQueue.enqueue consults
    #   the fleet registry and refuses/slows producers when EVERY live
    #   replica reports itself saturated (FleetSaturatedError)
    # -- telemetry plane: ring-buffer TSDB + fleet collector ----------------
    "zoo.telemetry.sample_interval_s": 1.0,  # cadence of the local registry
    #   sampler, the device-memory sampler and the fleet collector's
    #   scrape loop
    "zoo.telemetry.retention_s": 900.0,  # per-series history window; ring
    #   capacity = retention / sample interval (bounded, oldest evicted)
    "zoo.telemetry.device_memory": True,  # poll jax.Device.memory_stats()
    #   into zoo_device_hbm_bytes and the /statusz device block
    #   (graceful no-op off-TPU)
    # -- performance attribution: goodput ledger + profiler trigger ---------
    "zoo.goodput.enabled": True,  # per-fit / per-replica GoodputLedger:
    #   attribute every wall-clock second to an exclusive category
    #   (zoo_goodput_ratio, zoo_badput_seconds_total{category=})
    "zoo.profiler.dir": "",       # ProfilerTrigger trace-dir root
    #   ("" = ./zoo-profiles); captures land in capture-NNNN-<trigger>/
    "zoo.profiler.keep": 3,       # newest capture dirs retained; older
    #   ones evicted after each arm (never the in-flight capture)
    "zoo.profiler.duration_s": 10.0,  # time bound per capture (daemon
    #   timer stops the trace); used when zoo.profiler.steps == 0
    "zoo.profiler.steps": 0,      # >0 bounds a capture by step()
    #   notifications from the hosting loop instead of wall time
    "zoo.log.level": "INFO",
}

_ENV_PREFIX = "ZOO_TPU_"

#: where compiled programs are kept when ``JAX_COMPILATION_CACHE_DIR`` does
#: not say: ONE fixed path inside the checkout. The directory's path is part
#: of what a later process has to reproduce to find an entry again, so it is
#: never derived from a pid, a time or a temporary name.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: normalized ("zoo_failure_retry_times") → canonical ("zoo.failure.retry_times")
#: so env/kwargs spellings of multi-word leaf keys land on the right conf entry
_CANONICAL = {k.lower().replace(".", "_"): k for k in DEFAULT_CONF}


#: keys this package once read and no longer does, by normalized spelling.
#: An unknown key is accepted in silence, so a job that still sets one of
#: these would lose the path it asked for without a word: they raise.
_RETIRED = {k.replace(".", "_"): k for k in (
    "zoo.train.scan_steps", "zoo.train.device_cache",
    "zoo.train.fuse_epochs")}


def _reject_retired(merged: Mapping[str, Any]) -> None:
    """Raise for a retired key, whichever channel (yaml, env, conf dict,
    kwarg) brought it in."""
    for key in merged:
        retired = _RETIRED.get(str(key).lower().replace(".", "_"))
        if retired is not None:
            raise ValueError(
                f"{retired} is retired: fit dispatches one optimizer step "
                f"at a time, and no key selects another path; remove it "
                f"from the configuration")


def _canonical_key(raw: str) -> str:
    """Map an underscore-separated key (env var / kwarg) to its canonical
    dotted form. Known keys resolve via DEFAULT_CONF regardless of whether an
    underscore is a namespace separator or part of a leaf name
    (``failure_retry_times`` → ``zoo.failure.retry_times``); unknown keys fall
    back to dots-for-underscores."""
    norm = raw.lower().replace(".", "_")
    if not norm.startswith("zoo_"):
        norm = "zoo_" + norm
    if norm in _CANONICAL:
        return _CANONICAL[norm]
    return norm.replace("_", ".")


def _env_overrides() -> Dict[str, Any]:
    """``ZOO_TPU_MESH_MODEL=2`` → ``{"zoo.mesh.model": 2}`` — the analogue of
    the reference's env-var config channel (``NNContext.scala:216-229``)."""
    out: Dict[str, Any] = {}
    for k, v in os.environ.items():
        if k.startswith(_ENV_PREFIX):
            out[_canonical_key(k[len(_ENV_PREFIX):])] = _parse_scalar(v)
    return out


def _parse_scalar(v: str) -> Any:
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v.lower() in ("true", "false"):
        return v.lower() == "true"
    return v


def _load_yaml(path: str) -> Dict[str, Any]:
    """Flat yaml config loader (``config.yaml`` channel of the reference,
    ``serving/utils/ClusterServingHelper.scala``). Minimal parser: only
    ``key: value`` and one level of nesting, so we don't depend on pyyaml."""
    try:
        import yaml  # type: ignore

        with open(path) as f:
            data = yaml.safe_load(f) or {}
        return _flatten(data)
    except ImportError:
        out: Dict[str, Any] = {}
        prefix = ""
        with open(path) as f:
            for raw in f:
                line = raw.rstrip()
                if not line or line.lstrip().startswith("#"):
                    continue
                indented = line.startswith((" ", "\t"))
                key, _, val = line.strip().partition(":")
                val = val.strip()
                if not val:
                    prefix = key + "."
                    continue
                out[(prefix if indented else "") + key] = _parse_scalar(val)
                if not indented:
                    prefix = ""
        return out


def _flatten(d: Mapping[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in d.items():
        if isinstance(v, Mapping):
            out.update(_flatten(v, prefix + k + "."))
        else:
            out[prefix + k] = v
    return out


@dataclasses.dataclass
class ZooContext:
    """Process-wide runtime handle — what ``NNContext``/BigDL ``Engine`` is in
    the reference. Holds the mesh, config, and root PRNG key."""

    conf: Dict[str, Any]
    mesh: Any  # jax.sharding.Mesh

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def data_parallel_size(self) -> int:
        return self.mesh.shape[mesh_lib.DATA_AXIS]

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def seed(self) -> int:
        return int(self.conf["zoo.seed"])

    def rng(self) -> jax.Array:
        return jax.random.key(self.seed)

    def get(self, key: str, default: Any = None) -> Any:
        return self.conf.get(key, default)


_context: Optional[ZooContext] = None
#: jax_default_prng_impl before init_zoo_context first overrode it (None =
#: never overridden); reset_zoo_context restores it
_prng_impl_before_init: Optional[str] = None
_distributed_initialized = False


def _place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a home before the first
    compilation. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and this sets nothing; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`. Every entry point (``bench.py``,
    ``chip_smoke.py``, the serving launchers, the examples) reaches this
    through ``init_zoo_context`` — it is the only place that sets it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def _maybe_init_distributed(conf: Mapping[str, Any]) -> None:
    """Multi-host bring-up over DCN: ``jax.distributed.initialize`` when a
    coordinator is configured (``zoo.distributed.*`` conf /
    ``ZOO_TPU_DISTRIBUTED_COORDINATOR`` env). Single-process runs skip this
    entirely — the analogue of the reference running Spark ``local[N]``
    without a cluster manager (``DistriEstimatorSpec.scala:118``)."""
    global _distributed_initialized
    coordinator = str(conf.get("zoo.distributed.coordinator") or "").strip()
    if not coordinator or _distributed_initialized:
        return
    num_processes = int(conf.get("zoo.distributed.num_processes", 1))
    process_id = int(conf.get("zoo.distributed.process_id", 0))
    log.info("initializing JAX multi-host runtime: coordinator=%s "
             "process %d/%d", coordinator, process_id, num_processes)
    # init_zoo_context(...) with a coordinator must run before any
    # jax.devices()/computation in this process: jax.distributed.initialize
    # raises RuntimeError itself once a backend exists
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _distributed_initialized = True


def init_zoo_context(
    conf: Optional[Mapping[str, Any]] = None,
    conf_path: Optional[str] = None,
    **kwargs: Any,
) -> ZooContext:
    """Initialise (or fetch) the global context.

    Precedence (lowest → highest): bundled defaults, yaml ``conf_path``, env
    vars ``ZOO_TPU_*``, explicit ``conf`` dict, ``kwargs`` — mirroring the
    reference's conf-file < spark-conf < user-conf merge
    (``NNContext.scala:239-246``).

    Idempotent like ``SparkContext.getOrCreate``: a second call returns the
    existing context unless new settings are passed.
    """
    global _context
    if _context is not None and conf is None and conf_path is None and not kwargs:
        return _context

    merged: Dict[str, Any] = dict(DEFAULT_CONF)
    explicit: set = set()
    if conf_path:
        loaded = _load_yaml(conf_path)
        merged.update(loaded)
        explicit.update(loaded)
    env = _env_overrides()
    merged.update(env)
    explicit.update(env)
    if conf:
        merged.update(conf)
        explicit.update(conf)
    for k, v in kwargs.items():
        ck = _canonical_key(k)
        merged[ck] = v
        explicit.add(ck)

    # validate BEFORE any global side-effect (jax config, distributed
    # bring-up): a rejected call must not leave half-applied state.
    _reject_retired(merged)
    # jnp.dtype normalization accepts both "bfloat16" and jnp.bfloat16.
    import jax.numpy as jnp
    try:
        dtype = jnp.dtype(merged.get("zoo.compute.dtype", "float32")).name
    except TypeError:
        dtype = str(merged.get("zoo.compute.dtype"))
    if dtype not in ("float32", "bfloat16"):
        raise ValueError(f"zoo.compute.dtype must be float32|bfloat16, "
                         f"got {merged.get('zoo.compute.dtype')!r}")
    merged["zoo.compute.dtype"] = dtype

    logging.basicConfig(level=merged.get("zoo.log.level", "INFO"))

    _place_compile_cache()
    _maybe_init_distributed(merged)

    precision = merged.get("zoo.matmul.precision", "default")
    if precision != "default":
        jax.config.update("jax_default_matmul_precision", precision)

    # PRNG implementation. "auto" picks the hardware RBG generator on TPU —
    # dropout-heavy training otherwise spends real step time producing
    # threefry bits on the VPU (measured ~25% of a BERT-base fine-tune
    # step); rbg trades threefry's sharding-invariant streams for
    # hardware-rate bits, the right default on TPU where dropout RNG rides
    # the critical path. CPU/test runs keep threefry determinism.
    impl = str(merged.get("zoo.rng.impl", "auto")).lower()
    if impl == "auto":
        impl = "rbg" if jax.default_backend() == "tpu" else ""
    elif impl in ("default", "threefry", "threefry2x32"):
        impl = "threefry2x32"
    elif impl not in ("rbg", "unsafe_rbg", ""):
        raise ValueError(f"zoo.rng.impl must be auto|default|rbg, got "
                         f"{merged.get('zoo.rng.impl')!r}")
    if impl:
        global _prng_impl_before_init
        if _prng_impl_before_init is None:
            _prng_impl_before_init = jax.config.jax_default_prng_impl
        jax.config.update("jax_default_prng_impl", impl)

    mesh = mesh_lib.create_mesh(
        data=int(merged["zoo.mesh.data"]),
        model=int(merged["zoo.mesh.model"]),
        seq=int(merged["zoo.mesh.seq"]),
        expert=int(merged["zoo.mesh.expert"]),
        pipe=int(merged["zoo.mesh.pipe"]),
    )
    mesh_lib.set_global_mesh(mesh)

    # mixed-precision policy: params stay float32, layer compute runs at
    # zoo.compute.dtype (bfloat16 = MXU native). Applied only AFTER the
    # mesh commits (a failed re-init must not leave a half-applied
    # context). Ownership semantics (the flag lives in engine, the module
    # that owns the policy): an explicit zoo.compute.dtype makes the
    # CONTEXT own the policy; a later re-init without one resets a
    # context-owned policy back to the conf default (re-inits restart from
    # defaults like every other key); a policy set directly via
    # ``engine.set_policy(...)`` is never touched by inits that don't
    # name a dtype.
    from ..pipeline.api.keras import engine as _engine
    if "zoo.compute.dtype" in explicit or _engine.policy_owner() == "context":
        _engine._set_policy_from_context(dtype)

    _context = ZooContext(conf=merged, mesh=mesh)
    log.info(
        "ZooContext: %d device(s), mesh %s, %d process(es)",
        _context.num_devices,
        dict(mesh.shape),
        jax.process_count(),
    )
    return _context


def get_zoo_context() -> ZooContext:
    """Fetch the context, initialising with defaults if needed."""
    return init_zoo_context()


#: accepted spellings for boolean context flags — every tri-state
#: (auto|true|false) parser shares these so the flags can never drift
TRUE_FLAG_SPELLINGS = ("1", "true", "yes", "on")
FALSE_FLAG_SPELLINGS = ("0", "false", "no", "off", "")


def tri_state_conf(key: str, default: str = "auto"):
    """Parse an ``auto|true|false`` context flag to ``"auto"``, ``True``,
    or ``False`` — the call site decides what ``auto`` resolves to.
    ``default`` answers only for a key the conf does not hold; a context
    that cannot be built (a mesh that does not fit the devices) raises
    here like everywhere else. Raises ``ValueError`` on an unrecognized
    spelling."""
    flag = get_zoo_context().get(key, default)
    if isinstance(flag, str):
        low = flag.strip().lower()
        if low == "auto":
            return "auto"
        if low in TRUE_FLAG_SPELLINGS:
            return True
        if low in FALSE_FLAG_SPELLINGS:
            return False
        raise ValueError(f"{key} must be auto|true|false, got {flag!r}")
    return bool(flag)


def reset_zoo_context() -> None:
    """Tear down the global context (mainly for tests)."""
    global _context, _prng_impl_before_init
    _context = None
    mesh_lib.reset_global_mesh()
    if _prng_impl_before_init is not None:
        # restore the PRE-init value: a user's own jax.config choice made
        # outside the zoo context is not ours to clobber
        jax.config.update("jax_default_prng_impl", _prng_impl_before_init)
        _prng_impl_before_init = None
    from ..pipeline.api.keras import engine as _engine
    _engine._reset_policy()
