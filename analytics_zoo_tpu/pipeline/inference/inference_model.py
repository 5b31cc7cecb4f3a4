"""InferenceModel — the TPU-native inference runtime, parity with the
reference's multi-backend ``InferenceModel``
(``pipeline/inference/InferenceModel.scala:30-67,622-656``):

* ``concurrent_num``-deep **replica queue**: the reference clones the model
  ``concurrentNum`` times into a ``LinkedBlockingQueue`` so concurrent callers
  each hold one replica (``InferenceModel.scala:67``). Here params are
  immutable jax arrays and the compiled predict fn is pure, so replicas share
  weights; the queue holds permits that bound in-flight predictions and make
  ``predict`` safely callable from many threads (serving threads, ``L9``).
* **multi-format load**: the reference loads BigDL/Caffe/TF/Torch/OpenVINO
  (``InferenceModel.scala:80-450``); the TPU-native formats are the ZooModel
  one-file ``.npz`` (``load(path)``), a training checkpoint directory
  (``load_checkpoint``), or an in-memory ``KerasNet`` (``from_keras``).
* **precision paths**: fp32, bf16 (MXU native), and **int8 weight-only
  quantization** with per-channel scales — the AQT-style replacement for the
  reference's OpenVINO int8 calibration path
  (``InferenceModel.scala:350-450``, ``OpenVinoInferenceSupportive.scala``);
  int8 weights stay int8 in HBM (4x smaller, bandwidth-bound layers speed
  up) and are dequantized inside the fused XLA program.
* **batch bucketing**: inputs are padded to the next power-of-two batch so
  arbitrary request sizes reuse a small set of compiled programs instead of
  recompiling per shape (XLA static-shape discipline).
"""

from __future__ import annotations

import queue
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...common.reliability import RetryPolicy
from ...models.common.zoo_model import load_model
from ...observability import default_registry, instrument_jit
from ...parallel import mesh as mesh_lib
from ..api.keras.engine import KerasNet, intercept_layer_calls
from ..api.keras.training import _copy_leaves
from ...utils.checkpoint import CheckpointManager

__all__ = ["InferenceModel"]


def _as_list(x):
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _own_arrays(tree, theirs):
    """``tree`` with every leaf that is one of the device arrays of
    ``theirs`` replaced by a copy (all of them in one dispatch)."""
    taken = {id(a) for a in jax.tree_util.tree_leaves(theirs)
             if isinstance(a, jax.Array)}
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shared = [i for i, a in enumerate(leaves) if id(a) in taken]
    if shared:
        for i, a in zip(shared, _copy_leaves([leaves[i] for i in shared])):
            leaves[i] = a
    return jax.tree_util.tree_unflatten(treedef, leaves)


#: chunked predicts keep at most this many chunk OUTPUTS resident in HBM:
#: chunk i-1 is read back while chunk i runs / i+1 dispatches (ADVICE r5 —
#: dispatching every chunk before any readback held the whole output set
#: on device until collect()). 2 preserves the dispatch/readback overlap;
#: the common serving case (one chunk) is untouched.
_MAX_INFLIGHT_CHUNKS = 2


# ---------------------------------------------------------------------------
# int8 weight-only quantization (AQT-style)
# ---------------------------------------------------------------------------

_QUANT_MIN_SIZE = 512  # leaves smaller than this stay float (biases, scalars)


def quantize_int8(params) -> Tuple[Any, Any]:
    """Split a float param tree into (int8-or-float tree, scale-or-None tree).

    Per-channel symmetric quantization over the last axis: for a Dense kernel
    ``(in, out)`` each output column gets its own scale — the same granularity
    OpenVINO's calibration uses for FC layers. Small leaves (biases, norms)
    are kept in float; their footprint is negligible and quantizing them
    costs accuracy for nothing."""

    def q(leaf):
        a = np.asarray(jax.device_get(leaf))
        if a.dtype.kind != "f" or a.size < _QUANT_MIN_SIZE or a.ndim < 1:
            return a, None
        axes = tuple(range(a.ndim - 1)) if a.ndim > 1 else (0,)
        amax = np.max(np.abs(a), axis=axes, keepdims=True)
        scale = (amax / 127.0).astype(np.float32)
        scale = np.where(scale == 0, 1.0, scale)
        qa = np.clip(np.round(a / scale), -127, 127).astype(np.int8)
        return qa, np.squeeze(scale, axis=axes) if a.ndim > 1 else scale

    flat, treedef = jax.tree_util.tree_flatten(params)
    qs, scales = zip(*(q(l) for l in flat)) if flat else ((), ())
    return (jax.tree_util.tree_unflatten(treedef, list(qs)),
            jax.tree_util.tree_unflatten(treedef, list(scales)))


def _quantize_layer_entry(sub, act_scale: float):
    """Per-layer static-int8 params: int8 weight + per-out-channel scale +
    the calibrated activation scale (what ``quantized_call`` consumes)."""
    W = np.asarray(jax.device_get(sub["W"]))
    axes = tuple(range(W.ndim - 1))
    amax = np.max(np.abs(W), axis=axes)
    w_scale = np.where(amax == 0, 1.0, amax / 127.0).astype(np.float32)
    entry = {"W": np.clip(np.round(W / w_scale), -127, 127).astype(np.int8),
             "w_scale": w_scale, "x_scale": np.float32(act_scale)}
    for k, v in sub.items():
        if k != "W":
            entry[k] = np.asarray(jax.device_get(v))
    return entry


def dequantize_int8(q_tree, scale_tree, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8`, run INSIDE the jitted predict so the
    int8 leaves are what lives in HBM."""

    def dq(q, s):
        if s is None:
            return q.astype(dtype) if q.dtype.kind == "f" else q
        return q.astype(dtype) * jnp.asarray(s, dtype)

    return jax.tree.map(dq, q_tree, scale_tree,
                        is_leaf=lambda x: x is None or not isinstance(
                            x, (dict, list, tuple)))


# ---------------------------------------------------------------------------
# InferenceModel
# ---------------------------------------------------------------------------

class InferenceModel:
    """Replica-queue batched inference runtime.

    >>> im = InferenceModel(concurrent_num=4)
    >>> im.load("/path/model.npz", dtype="bfloat16")
    >>> probs = im.predict(x)                       # thread-safe
    """

    def __init__(self, concurrent_num: int = 1, *,
                 max_batch_size: int = 4096, registry=None,
                 readback_retry: Optional[RetryPolicy] = None):
        if concurrent_num < 1:
            raise ValueError("concurrent_num must be >= 1")
        self.concurrent_num = int(concurrent_num)
        self.max_batch_size = int(max_batch_size)
        #: chunk readbacks cross the device link — transient transport
        #: errors retry under this policy instead of failing the whole
        #: predict; non-transport errors (shape bugs, OOM) propagate
        #: immediately
        self._readback_retry = readback_retry if readback_retry \
            is not None else RetryPolicy(
                max_attempts=3, base_delay=0.05, max_delay=0.5,
                retryable=(ConnectionError, OSError))
        self.metrics = registry if registry is not None else default_registry()
        self._m_permit_wait = self.metrics.histogram(
            "zoo_inference_permit_wait_seconds",
            "wait for a replica permit per predict dispatch")
        self._m_batch_time = self.metrics.histogram(
            "zoo_inference_batch_seconds",
            "predict dispatch to readback completion per batch "
            "(device time + transfer; overlapped callers hide it)")
        self._m_batches = self.metrics.counter(
            "zoo_inference_batches_total", "predict batches collected")
        self._m_records = self.metrics.counter(
            "zoo_inference_records_total", "records predicted")
        self.mesh = mesh_lib.global_mesh()
        # replica-permit pool: exactly concurrent_num tokens ever exist,
        # so the explicit bound documents the invariant and every return
        # is put_nowait — a put into this pool can never block (ZL011)
        self._permits: "queue.Queue[int]" = queue.Queue(
            maxsize=self.concurrent_num)
        for i in range(self.concurrent_num):
            self._permits.put_nowait(i)
        self._model: Optional[KerasNet] = None
        self._params = None
        self._net_state = None
        self._scales = None          # int8 path only
        self._dtype = jnp.float32
        self._predict = None         # shape-polymorphic jitted fn

    # ---- loaders (InferenceModel.scala:80-450 family) ---------------------
    def load(self, path: str, *, dtype: str = "float32",
             quantize: Optional[str] = None,
             calibrate=None) -> "InferenceModel":
        """Load a ZooModel one-file ``.npz`` (``doLoadBigDL`` role)."""
        return self.from_keras(load_model(path), dtype=dtype,
                               quantize=quantize, calibrate=calibrate)

    def load_checkpoint(self, model: KerasNet, ckpt_dir: str, *,
                        dtype: str = "float32",
                        quantize: Optional[str] = None,
                        calibrate=None) -> "InferenceModel":
        """Load the newest training snapshot from ``ckpt_dir`` into
        ``model``'s architecture (``doLoadTF(checkpoint)`` role)."""
        if model.params is None:
            model.init_weights()
        mgr = CheckpointManager(ckpt_dir)
        # verified restore with fallback (docs/guides/TRAINING.md): a
        # torn newest snapshot is skipped and the next valid one loads —
        # serving never boots on bad weights. READ-ONLY (quarantine=False):
        # this process does not own the directory, and what looks
        # uncommitted may be a live training run's save in flight
        out = mgr.restore_latest({"params": model.params,
                                  "net_state": model.net_state},
                                 quarantine=False)
        if out is None:
            raise FileNotFoundError(f"no valid snapshot in {ckpt_dir}")
        _step, trees, _meta = out
        model.params = trees["params"]
        model.net_state = trees["net_state"]
        return self.from_keras(model, dtype=dtype, quantize=quantize,
                               calibrate=calibrate)

    def from_keras(self, model: KerasNet, *, dtype: str = "float32",
                   quantize: Optional[str] = None,
                   calibrate=None) -> "InferenceModel":
        """Wrap an in-memory KerasNet/ZooModel (weights already present).

        ``quantize="int8"`` alone is weight-only (int8 in HBM, float
        compute). Adding ``calibrate=representative_batch`` runs one eager
        calibration pass recording each Dense/Conv2D input range, then
        executes those layers as int8 x int8 -> int32 MXU ops with a fused
        rescale — the native equivalent of the reference's OpenVINO
        calibrate-then-int8 pipeline (``InferenceModel.scala:80-450``,
        ``OpenVinoInferenceSupportive.scala:61-68``)."""
        if model.params is None:
            model.init_weights()
        self._model = model
        self._dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                       "bf16": jnp.bfloat16}[dtype]
        params, net_state = model.params, model.net_state
        self._act_scales = None
        if calibrate is not None and quantize != "int8":
            raise ValueError(
                "calibrate= requires quantize='int8' (a calibration batch "
                "without a quantized mode would be silently ignored)")
        if quantize is None:
            cast = (lambda a: a.astype(self._dtype)
                    if hasattr(a, "dtype") and a.dtype == jnp.float32
                    and self._dtype != jnp.float32 else a)
            self._params = jax.tree.map(cast, params)
            self._scales = None
        elif quantize == "int8":
            repl = mesh_lib.replicated_sharding(self.mesh)
            if calibrate is not None:
                self._act_scales = self._calibrate(model, params, net_state,
                                                   calibrate)
                q = self._rewrite_quantized(params, self._act_scales)
                self._params = jax.device_put(q, repl)
                self._scales = None
            else:
                q, s = quantize_int8(params)
                # quantize_int8 produces HOST numpy arrays; pin them on
                # device once — otherwise every predict re-uploads the whole
                # int8 weight set. Replicated over the mesh, matching the
                # batch-sharded inputs.
                self._params = jax.device_put(q, repl)
                self._scales = jax.device_put(s, repl)
        else:
            raise ValueError(f"unknown quantize mode {quantize!r}; "
                             "use None or 'int8'")
        # arrays of its own: a later ``model.fit`` hands the model's arrays
        # to its loop, whose first step consumes them, and what is served
        # here must survive every call. A cast or a quantization made new
        # ones already; a leaf it passed through is still the model's
        self._params, self._net_state = _own_arrays(
            (self._params, net_state), (params, net_state))
        model, dtype, scales = self._model, self._dtype, self._scales
        act_scales = self._act_scales

        def qhook(layer, p, s, x, training, rng):
            if (act_scales is not None and layer.name in act_scales
                    and isinstance(p, dict) and "x_scale" in p
                    and not isinstance(x, (list, tuple))):
                return layer.quantized_call(p, x), (s or {})
            return None

        def run(params, net_state, x):
            if scales is not None:
                params = dequantize_int8(params, scales, dtype)
            if dtype != jnp.float32:
                x = jax.tree.map(
                    lambda a: a.astype(dtype) if a.dtype.kind == "f" else a, x)
            with intercept_layer_calls(qhook if act_scales else None):
                yp, _ = model.apply(params, net_state, x, training=False,
                                    rng=None)
            return jax.tree.map(lambda a: a.astype(jnp.float32)
                                if a.dtype == jnp.bfloat16 else a, yp)

        # one shape-polymorphic jitted fn; jax.jit caches one executable per
        # padded batch size (bounded by the power-of-two bucketing below) and
        # is itself thread-safe. `params` is rebound only to its dequantized
        # view — self._params must survive every call, so donation is wrong.
        # instrument_jit: each new padded batch size is an expected compile
        # (bucketing bounds them); a retrace storm here means a caller is
        # bypassing the bucketing
        self._predict = instrument_jit(  # zoolint: disable=ZL008
            run, name="inference.predict", registry=self.metrics)
        return self

    @staticmethod
    def _quantizable(layer) -> bool:
        """True when the class that provides the layer's EFFECTIVE ``call``
        also provides a matching ``quantized_call`` — a subclass that
        overrides ``call`` (ShareConvolution2D's explicit padding,
        Deconvolution2D's transpose) must not inherit a quantized path with
        different semantics."""
        for cls in type(layer).__mro__:
            if "call" in cls.__dict__:
                return "quantized_call" in cls.__dict__
        return False

    @staticmethod
    def _calibrate(model, params, net_state, calibrate
                   ) -> Dict[str, Tuple[float, tuple]]:
        """One eager forward over the calibration batch, recording per
        quantizable layer the activation scale AND the kernel shape —
        ``{name: (x_scale, W_shape)}`` — so the rewrite can refuse
        name-colliding layers in other containers. The max of colliding
        ranges is taken (conservative)."""
        records: Dict[str, float] = {}

        shapes: Dict[str, tuple] = {}

        def rec(layer, p, s, x, training, rng):
            if (InferenceModel._quantizable(layer) and isinstance(p, dict)
                    and "W" in p and not isinstance(x, (list, tuple))):
                amax = float(jnp.abs(x).max())
                records[layer.name] = max(records.get(layer.name, 0.0), amax)
                shapes[layer.name] = tuple(p["W"].shape)
            return None

        xs = [jnp.asarray(a) for a in _as_list(calibrate)]
        with intercept_layer_calls(rec):
            model.apply(params, net_state, xs if len(xs) > 1 else xs[0],
                        training=False, rng=None)
        if not records:
            raise ValueError("calibration found no quantizable layer "
                             "(Dense/Convolution2D) in the model")
        return {name: (max(amax, 1e-8) / 127.0, shapes[name])
                for name, amax in records.items()}

    @staticmethod
    def _rewrite_quantized(params, act_scales):
        """Replace each calibrated layer's param subtree with its static-int8
        entry, recursing through nested containers. A subtree is rewritten
        only when BOTH the layer name and the kernel shape recorded at
        calibration match — a non-quantizable layer in another container
        that merely shares a calibrated layer's name keeps its float params
        (the collision _calibrate's docstring warns about)."""
        def rewrite(tree):
            if not isinstance(tree, dict):
                return tree
            out = {}
            for k, v in tree.items():
                entry = act_scales.get(k)
                if (entry is not None and isinstance(v, dict) and "W" in v
                        and tuple(v["W"].shape) == entry[1]):
                    out[k] = _quantize_layer_entry(v, entry[0])
                else:
                    out[k] = rewrite(v)
            return out
        return rewrite(params)

    # ---- predict (InferenceModel.scala:622-656) ---------------------------
    def predict(self, x, batch_size: Optional[int] = None):
        """Batched predict. Blocks while all ``concurrent_num`` replicas are
        busy (the reference blocks on the replica queue,
        ``InferenceModel.scala:622-656``). Thread-safe."""
        return self.predict_async(x, batch_size)()

    def predict_async(self, x, batch_size: Optional[int] = None,
                      block: bool = True):
        """Dispatch a predict WITHOUT blocking on readback. Returns a
        zero-arg ``collect`` callable: the device work is enqueued here
        (XLA dispatch is asynchronous), ``collect()`` blocks on the
        transfer and returns the numpy result. The replica permit is held
        until ``collect`` runs — call it exactly once. Inputs larger than
        ``max_batch_size`` dispatch in chunks with at most
        ``_MAX_INFLIGHT_CHUNKS`` chunk outputs resident in HBM (older
        chunks are read back while newer ones dispatch).

        With ``block=False`` the call returns None instead of waiting when
        every replica permit is in flight. A single-threaded pipeline MUST
        use this mode for its second in-flight dispatch: with
        ``concurrent_num=1`` a blocking dispatch-before-collect would
        deadlock on the permit its own later collect() releases. The serve
        loop (``serving/server.py``) overlaps batches this way."""
        if self._model is None:
            raise RuntimeError("no model loaded; call load()/from_keras() first")
        xs = [np.asarray(a) for a in _as_list(x)]
        n = xs[0].shape[0]
        if n == 0:
            raise ValueError("predict called with an empty batch")
        dp = mesh_lib.data_parallel_size(self.mesh)
        # the chunk cap is a power of two <= max_batch_size so padded chunks
        # never exceed the user's HBM bound
        cap = max(_next_pow2(self.max_batch_size + 1) // 2, dp)
        cap = min(cap, max(_next_pow2(n), dp))
        if block:
            t_wait = time.perf_counter()
            permit = self._permits.get()
            self._m_permit_wait.observe(time.perf_counter() - t_wait)
        else:
            try:
                permit = self._permits.get_nowait()
            except queue.Empty:
                return None
            self._m_permit_wait.observe(0.0)
        t_dispatch = time.perf_counter()
        deferred = []
        outs = []       # host results, in chunk order

        def readback_oldest():
            # device_get rides the device transport: retried under the
            # readback policy so one dropped link round-trip does not
            # fail a predict whose compute already succeeded
            yp, m = deferred.pop(0)
            host = self._readback_retry.call(
                lambda: jax.tree.map(lambda a: np.asarray(
                    jax.device_get(a)), yp),
                op="inference.readback", registry=self.metrics)
            outs.append(jax.tree.map(lambda a, mm=m: a[:mm], host))

        try:
            for i in range(0, n, cap):
                if len(deferred) >= _MAX_INFLIGHT_CHUNKS:
                    # bound the in-flight chunk outputs: read back the
                    # oldest before dispatching another, so a many-chunk
                    # predict never holds every chunk output in HBM
                    readback_oldest()
                chunk = [a[i:i + cap] for a in xs]
                m = chunk[0].shape[0]
                padded = max(_next_pow2(m), dp)
                if m != padded:
                    chunk = [np.concatenate(
                        [a, np.repeat(a[-1:], padded - m, axis=0)], axis=0)
                        for a in chunk]
                sharding = mesh_lib.batch_sharding(self.mesh)
                # each chunk IS the batched transfer (bounded by
                # max_batch_size so padded chunks fit the HBM budget)
                chunk_d = [jax.device_put(jnp.asarray(a), sharding)  # zoolint: disable=ZL009
                           for a in chunk]
                yp = self._predict(self._params, self._net_state,
                                   chunk_d if len(chunk_d) > 1 else chunk_d[0])
                deferred.append((yp, m))
        except BaseException:
            self._permits.put_nowait(permit)
            raise

        done = [False]

        def collect():
            if done[0]:
                raise RuntimeError("predict_async result already collected")
            done[0] = True
            try:
                while deferred:
                    readback_oldest()
                self._m_batch_time.observe(time.perf_counter() - t_dispatch)
                self._m_batches.inc()
                self._m_records.inc(n)
                return jax.tree.map(
                    lambda *ys: np.concatenate(ys, axis=0), *outs)
            finally:
                self._permits.put_nowait(permit)

        return collect

    def predict_classes(self, x, zero_based: bool = True):
        from ...utils.prediction import probs_to_classes
        return probs_to_classes(self.predict(x), zero_based=zero_based)

    # ---- introspection ----------------------------------------------------
    def memory_bytes(self) -> int:
        """Weight footprint in HBM — shows the int8 4x reduction. Reads only
        dtype/shape metadata (no device transfer)."""
        return sum(int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(self._params))
