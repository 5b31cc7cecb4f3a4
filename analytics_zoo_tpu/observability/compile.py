"""Compilation observability — the answer to "why did this step suddenly
take 40x longer?" (a silent retrace).

:func:`instrument_jit` is a drop-in ``jax.jit`` replacement used by every
hot-path entry point (the training step, the eval/predict
steps, ``InferenceModel``'s serving predict, ``Seq2seq.infer``'s
encode/decode closures). On every call it derives the ABSTRACT signature
of the arguments (pytree structure + per-leaf shape/dtype — the same
identity ``jax.jit`` keys its executable cache on, minus shardings) and,
when the signature is new:

* counts the compilation in ``zoo_jit_compile_total`` (process-wide) and
  times it into ``zoo_jit_compile_seconds{fn=...}`` — the wall time of
  the first dispatch, which trace+compile dominate,
* emits a ``jit.compile`` event, and — when the function had already
  compiled under a DIFFERENT signature — a ``jit.retrace`` event plus a
  ``zoo_jit_retrace_total{fn=...}`` increment. A retrace under load is
  almost always a shape-discipline bug (unpadded dynamic batch, a new
  sequence length); the event names the function so the operator can go
  straight to the offending caller.

Steady-state cost is two executable-cache size reads per call (~tens of
nanoseconds; the signature is only derived on the rare call that actually
compiled). On jax builds without ``_cache_size`` it degrades to one
pytree flatten per call — the same order of work ``jax.jit``'s own cache
lookup does. Retrace classification is deliberately sharding-blind: a
recompile triggered purely by a resharded input counts as a compile but
never as a retrace, so the retrace signal stays a pure shape-discipline
alarm.

``instrument_jit`` times a first dispatch from outside and sees only its
own entry points. What JAX itself spent, on any program, comes from
``jax.monitoring``'s events (:func:`install_compile_listeners`, once a
process): Python tracing, lowering to MLIR, the XLA backend, or a load
from the persistent cache, booked into
``zoo_xla_compile_seconds_total{fn=,phase=trace|lower|backend|cache_load}``
and ``zoo_xla_compile_total{fn=,cache=hit|miss}``. ``fn`` is the
``instrument_jit`` name whose call is on this thread's stack, and
``"uninstrumented"`` for everything else (an eager ``jnp`` reduction whose
shape holds the step count, a reader's own ``.trace().lower()``).

``jax`` is imported lazily so the observability package stays importable
(and the scrape/status CLI stays fast) in jax-free processes.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .metrics import MetricsRegistry, default_registry

__all__ = ["instrument_jit", "InstrumentedJit", "install_compile_listeners",
           "xla_compile_totals", "COMPILE_PHASES", "UNINSTRUMENTED"]

_HASHABLE = (int, float, bool, str, bytes, type(None))

#: ``phase`` values of ``zoo_xla_compile_seconds_total``
COMPILE_PHASES = ("trace", "lower", "backend", "cache_load")
#: ``fn`` of a compilation with no ``instrument_jit`` call on the stack
UNINSTRUMENTED = "uninstrumented"

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_install_lock = threading.Lock()
_installed = False


class _ThreadState(threading.local):
    """What the listeners know of the thread an event fires on."""

    def __init__(self):
        #: ``(name, {phase: seconds})`` of the innermost instrumented call
        self.call: Optional[Tuple[str, Dict[str, float]]] = None
        #: ``(start, end)`` of the events that ended and may lie inside one
        #: that has not: JAX reports a nested ``jit``'s trace (and an eager
        #: op compiled while an outer function is traced) on its own AND
        #: inside the enclosing event's duration
        self.ended: List[Tuple[float, float]] = []
        #: the persistent cache answered the compilation in progress
        self.cache_hit = False


_thread = _ThreadState()
_PHASE_OF = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
             _BACKEND_EVENT: "backend"}


def _on_duration(event: str, seconds: float, **_kw) -> None:
    phase = _PHASE_OF.get(event)
    if phase is None:
        return
    # self time: events end innermost first, so whatever ended after this
    # one began lies inside it and has been booked already
    end = time.perf_counter()
    start = end - seconds
    ended = _thread.ended
    while ended and ended[-1][0] >= start:
        inner = ended.pop()
        seconds -= inner[1] - inner[0]
    ended.append((start, end))
    call = _thread.call
    fn = call[0] if call is not None else UNINSTRUMENTED
    reg = default_registry()
    if phase == "backend":
        # the event spans the cache lookup too: on a hit all of it is the
        # load (key hashing, read, deserialization), none of it XLA
        hit, _thread.cache_hit = _thread.cache_hit, False
        if hit:
            phase = "cache_load"
        # fn = an instrument_jit(name=...) constant or "uninstrumented"
        reg.counter(  # zoolint: disable=ZL015 bounded label set
            "zoo_xla_compile_total",
            "programs brought up, by the instrumented entry point on the "
            "stack and by whether the persistent cache held them",
            labels={"fn": fn, "cache": "hit" if hit else "miss"}).inc()
    seconds = max(seconds, 0.0)
    if call is not None:
        call[1][phase] = call[1].get(phase, 0.0) + seconds
    # fn: as above
    reg.counter(  # zoolint: disable=ZL015 bounded label set
        "zoo_xla_compile_seconds_total",
        "seconds JAX spent bringing programs up, by the instrumented entry "
        "point on the stack and by phase (Python tracing, lowering to MLIR, "
        "XLA backend compilation, load from the persistent cache); each "
        "second is booked once, to the innermost event that covers it",
        labels={"fn": fn, "phase": phase}).inc(seconds)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _thread.cache_hit = True


def install_compile_listeners() -> None:
    """Register the ``jax.monitoring`` listeners, once a process (JAX keeps
    listeners for the life of the process; the counters they feed are
    looked up in :func:`default_registry` at each event, so a test's
    ``reset_default_registry()`` is honored)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def xla_compile_totals(registry: Optional[MetricsRegistry] = None
                       ) -> Dict[str, Dict[str, float]]:
    """``{fn: {"trace": s, "lower": s, "backend": s, "cache_load": s,
    "hit": n, "miss": n}}`` as the registry holds them now (keys that were
    never booked are absent). Two of these, subtracted, are what one
    ``fit`` compiled (``model.last_fit_report["compile"]``)."""
    reg = registry if registry is not None else default_registry()
    out: Dict[str, Dict[str, float]] = {}
    key_of = {"zoo_xla_compile_seconds_total": "phase",
              "zoo_xla_compile_total": "cache"}
    for m in reg.metrics():
        if m.name in key_of:
            labels = dict(m.labels)
            out.setdefault(labels["fn"], {})[labels[key_of[m.name]]] = m.value
    return out


class InstrumentedJit:
    """A jitted callable with compile/retrace accounting. Behaves like the
    underlying ``jax.jit`` result — extra attributes (``lower``,
    ``clear_cache``, ...) forward to it, so AOT cost-analysis callers
    (``TrainingLoop._maybe_compute_flops``) work unchanged."""

    def __init__(self, fn, *, name: str,
                 registry: Optional[MetricsRegistry] = None, **jit_kwargs):
        import jax
        install_compile_listeners()
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._name = name
        # None = resolve default_registry() per compile event, so a test's
        # reset_default_registry() is honored (compiles are rare; the
        # lookup never lands on the steady-state path)
        self._registry = registry
        self._seen: set = set()
        self._lock = threading.Lock()

    @staticmethod
    def _signature(args, kwargs) -> Tuple[Any, ...]:
        import jax
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        sig: list = [treedef]
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                # metadata only — safe on buffers the call just donated
                sig.append((tuple(shape), str(dtype)))
            elif isinstance(leaf, (int, float, bool)):
                # jax traces Python numbers by dtype, not value — keying
                # by value would report a phantom retrace per distinct
                # value (and grow the seen-set without bound)
                sig.append((type(leaf).__name__,))
            elif isinstance(leaf, _HASHABLE):
                # str/bytes/None only pass jit as static args, where the
                # VALUE does key the executable cache
                sig.append((type(leaf).__name__, leaf))
            else:
                sig.append((type(leaf).__name__,))
        return tuple(sig)

    def _registry_now(self) -> MetricsRegistry:
        return (self._registry if self._registry is not None
                else default_registry())

    def __call__(self, *args, **kwargs):
        # the compile listeners book what JAX does inside this call
        # under this entry point's name
        outer, _thread.call = _thread.call, (self._name, {})
        try:
            return self._call(args, kwargs)
        finally:
            _thread.call = outer

    def _call(self, args, kwargs):
        cache_size = getattr(self._jitted, "_cache_size", None)
        if cache_size is not None:
            # fast path: one executable-cache size read (~tens of ns)
            # before and after — the signature is only derived on the
            # rare call that actually compiled, so the steady state pays
            # no pytree flatten at all
            before = cache_size()
            t0 = time.perf_counter()
            out = self._jitted(*args, **kwargs)
            if cache_size() == before:
                return out
            dur = time.perf_counter() - t0
            self._record_compile(self._signature(args, kwargs), dur)
            return out
        # fallback (no _cache_size on this jax): signature-per-call —
        # a sharding-only recompile is invisible here, matching the
        # documented sharding-blind contract
        sig = self._signature(args, kwargs)
        with self._lock:
            known = sig in self._seen
        if known:
            return self._jitted(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        self._record_compile(sig, time.perf_counter() - t0)
        return out

    def _record_compile(self, sig, dur: float) -> None:
        with self._lock:
            fresh = sig not in self._seen
            if fresh:
                self._seen.add(sig)
            n_sigs = len(self._seen)
        reg = self._registry_now()
        reg.counter(
            "zoo_jit_compile_total",
            "XLA compilations across all instrumented entry points").inc()
        # fn = the instrument_jit(name=...) entry-point constant
        reg.histogram(  # zoolint: disable=ZL015 bounded label set
            "zoo_jit_compile_seconds",
            "first-dispatch wall time per compilation "
            "(trace+compile dominated)",
            labels={"fn": self._name}).observe(dur)
        phases = _thread.call[1] if _thread.call is not None else {}
        reg.emit("jit.compile", fn=self._name, dur_s=dur, n_signatures=n_sigs,
                 **{p + "_s": phases.get(p, 0.0) for p in COMPILE_PHASES})
        # retrace = a compile under a NEW abstract signature after the
        # first; a compile with a KNOWN signature (resharded inputs, a
        # concurrent first call racing this one) counts above but is not
        # a retrace — never report a phantom shape-discipline bug
        if fresh and n_sigs > 1:
            # fn = the instrument_jit(name=...) entry-point constant
            reg.counter(  # zoolint: disable=ZL015 bounded label set
                "zoo_jit_retrace_total",
                "recompilations of an already-compiled function under a "
                "new abstract signature",
                labels={"fn": self._name}).inc()
            reg.emit("jit.retrace", fn=self._name, dur_s=dur,
                     n_signatures=n_sigs)

    def __getattr__(self, attr):
        if attr == "_jitted":
            # only reachable when __init__ hasn't populated the instance
            # dict (e.g. unpickling); forwarding would infinitely recurse
            raise AttributeError(attr)
        return getattr(self._jitted, attr)

    def __repr__(self):
        return f"InstrumentedJit({self._name!r}, {self._jitted!r})"


def instrument_jit(fn, *, name: str,
                   registry: Optional[MetricsRegistry] = None,
                   **jit_kwargs) -> InstrumentedJit:
    """``jax.jit(fn, **jit_kwargs)`` with compile observability. ``name``
    labels the ``zoo_jit_compile_seconds``/``zoo_jit_retrace_total``
    series and the ``jit.compile``/``jit.retrace`` events; keep it a
    stable dotted identifier (``train.step``, ``inference.predict``)."""
    return InstrumentedJit(fn, name=name, registry=registry, **jit_kwargs)
