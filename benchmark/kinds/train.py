"""Traffic kind ``train``: one configuration trained through ``model.fit``.

Set-up builds one model, installs weights made from ``--seed``, drives it
through its first three optimizer steps (one ``fit`` call a step, each on
rows of its own; these are the steps the reference follows), and hands the
same object to the window: one further ``fit`` call that this file's
``WindowTrigger`` ends after the number of steps that fill ``--seconds`` at
the step time set-up measured. Every ``zoo.*`` key stays at its default.
``train_tokens_per_s`` is every token of every step that call completed over
the wall time of the call.

After the window: peak memory is read, the program's state is freed, and the
configuration's plain reference runs the same three steps (``lib/
reference_run.py``); ``lib/compare.py`` decides ``correct``.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from ..lib import compare, reference_run

VERIFY_STEPS = 3
WARM_STEPS = 5
#: a traced window is cut to this length: a trace of 30 s of steps is
#: hundreds of MB to write, bring back and parse
TRACE_SECONDS = 6.0


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX did not come up on the accelerator the cell asks for."""


def make_trigger(steps: int):
    """The window's end trigger: fires once ``steps`` optimizer steps have
    been dispatched since it was made. The number is fixed BEFORE the window
    (``--seconds`` over the step time that set-up measured), because ``fit``
    reduces the losses of its N steps with programs whose shapes hold N: a
    window that ends by the clock ends on an N nobody compiled for, and the
    compilation lands inside it (PERF.md section 6, PR 25). It also keeps the
    host's clock at every step boundary the loop shows it."""
    from analytics_zoo_tpu.common.triggers import Trigger

    class WindowTrigger(Trigger):
        def __init__(self, steps: int):
            self.steps = int(steps)
            self.first = None
            self.t0 = time.perf_counter()
            self.stamps: List[float] = []

        def __call__(self, state) -> bool:
            self.stamps.append(time.perf_counter() - self.t0)
            if self.first is None:
                self.first = state.iteration - 1
            return state.iteration - self.first >= self.steps

    return WindowTrigger(steps)


class CompileWatch:
    """Counts XLA backend compilations (of any program, instrumented by the
    zoo or not) between ``start`` and ``stop``, from JAX's own monitoring
    events. A load from the persistent cache is not a compilation."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.seen: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if self.on and event == self.EVENT:
            self.seen.append(duration)

    def start(self) -> None:
        self.seen, self.on = [], True

    def stop(self) -> List[float]:
        self.on = False
        return self.seen


def warm_loss_reduction(steps: int) -> None:
    """Compile what ``fit`` reduces the losses of a ``steps``-step epoch
    with (``jnp.mean(jnp.concatenate([atleast_1d(l), ...]))``): the only
    programs of the loop whose shapes depend on how many steps ran."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.common.context import get_zoo_context
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    # a step's loss comes back replicated over the loop's mesh
    one = jax.device_put(np.float32(0.0), mesh_lib.replicated_sharding(
        get_zoo_context().mesh))
    float(jnp.mean(jnp.concatenate([jnp.atleast_1d(one)
                                    for _ in range(steps)])))


def describe_devices() -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def check_devices(chips: int, peaks: Optional[Dict[str, Any]]
                  ) -> Dict[str, Any]:
    """The device as JAX reports it, or ``NoChip`` where it is not the TPU
    the cell asks for, with as many chips, of a kind ``peaks.json`` knows."""
    import jax
    device = describe_devices()
    if device["platform"] != "tpu" or jax.default_backend() != "tpu":
        raise NoChip(f"the cell needs a TPU and JAX came up on "
                     f"{device['platform']!r} ({device['kind']!r})")
    if device["count"] != chips:
        raise NoChip(f"the cell asks for {chips} chip(s) and JAX reports "
                     f"{device['count']}")
    if device["kind"] not in (peaks or {}):
        raise NoChip(f"device kind {device['kind']!r} is not in "
                     f"benchmark/peaks.json")
    return device


def start_context(chips: int, peaks: Optional[Dict[str, Any]],
                  require_chip: bool = True) -> Dict[str, Any]:
    """Bring the program up as a user would, with no ``zoo.*`` key set, under
    the bf16-compute / f32-parameter policy; returns the device as JAX
    reports it. Everything the process compiles goes to the persistent
    cache, the small programs too: a warm run should compile nothing."""
    import jax

    from analytics_zoo_tpu.common.context import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras import set_policy
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    init_zoo_context()
    device = (check_devices(chips, peaks) if require_chip
              else describe_devices())
    set_policy(compute_dtype="bfloat16", param_dtype="float32")
    return device


def compile_counts() -> Dict[str, float]:
    """``zoo_jit_compile_total`` and the summed ``zoo_jit_compile_seconds``
    (copied from ``chip_smoke.compile_stats``)."""
    from analytics_zoo_tpu.observability import default_registry
    total = seconds = 0.0
    for m in default_registry().metrics():
        if m.name == "zoo_jit_compile_total":
            total += m.value
        elif m.name == "zoo_jit_compile_seconds":
            seconds += m.sum
    return {"total": total, "seconds": seconds}


def badput_seconds(category: str) -> float:
    from analytics_zoo_tpu.observability import default_registry
    return sum(m.value for m in default_registry().metrics()
               if m.name == "zoo_badput_seconds_total"
               and dict(m.labels).get("category") == category)


def _adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever it sits."""
    import jax
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def first_steps(model, model_lib, ref, cfg, seed: int, batches, batch: int
                ) -> Dict[str, Any]:
    """Install weights made from ``seed`` and drive ``model`` through its
    first optimizer steps, one ``fit`` call a step through the window's own
    call and feed, each on rows of its own. Returns what the comparison
    needs of them: each step's loss, the first gradient as the optimizer got
    it (Adam's first moment after one step is ``(1 - b1) g``) with its leaf
    norms, and the leaf norms of the parameters' change."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.feature import FeatureSet
    t_in = time.perf_counter()
    init = jax.jit(lambda key: ref.init_params(cfg, key))
    key = ref.B.seed_key(seed)
    model.params = model_lib.to_program(model, init(key))
    model.net_state = {}
    model.opt_state = None
    model.finished_epochs = model.finished_iterations = 0
    got: Dict[str, Any] = {"loss": [], "seconds": {}}
    records: List[Dict[str, Any]] = []
    jax.block_until_ready(model.params)
    clock = time.perf_counter()

    def lap(name):
        nonlocal clock
        now = time.perf_counter()
        got["seconds"][name] = round(now - clock, 2)
        clock = now
    got["seconds"]["weights"] = round(clock - t_in, 2)
    for k, (x, y) in enumerate(batches):
        records.clear()
        model.fit(FeatureSet.array(x, y, shuffle=False), batch_size=batch,
                  nb_epoch=1, callbacks=[records.append])
        got["loss"].append(float(records[-1]["loss"]))
        lap(f"fit{k + 1}")
        if k == 0:
            mu = model_lib.from_program(model, _adam_mu(model.opt_state))
            b1 = cfg["assumed"]["optimizer"]["b1"]
            got["grad"] = {p: v / (1.0 - b1)
                           for p, v in compare.leaf_norms(mu).items()}
            # kept on the host: the chip has no room for it in the window
            got["grad_tree"] = jax.tree.map(
                lambda a: np.asarray(a) / np.float32(1.0 - b1),
                jax.device_get(mu))
            del mu
            lap("first_gradient_to_host")
    records.clear()
    got["change"] = compare.leaf_norms(jax.jit(
        lambda a, key: jax.tree.map(lambda x, y: x - y, a, init(key)))(
            model_lib.from_program(model, model.params), key))
    lap("change")
    return got


def free_program_state() -> None:
    """Drop every device buffer the process holds, so that the reference
    starts on an empty chip."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()


def run(cell: Dict[str, Any], cfg: Dict[str, Any], traffic: Dict[str, Any],
        limits: Dict[str, float], readers: Dict[str, Callable], *,
        seed: int, seconds: float, trace: bool, t_process: float,
        peaks: Optional[Dict[str, Any]] = None, require_chip: bool = True,
        dump: Optional[str] = None) -> Dict[str, Any]:
    """One run of a training cell; returns the result object.

    ``require_chip=False`` is for the tests under ``benchmark/tests``, which
    drive a tiny cell on the CPU; ``readers`` maps each per-layer metric the
    cell reports to its reader."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.feature import FeatureSet

    phases: Dict[str, float] = {"imports": time.perf_counter() - t_process}

    def mark(name: str, since: float) -> float:
        now = time.perf_counter()
        phases[name] = now - since
        return now

    t = time.perf_counter()
    device = start_context(int(cell["chips"]), peaks, require_chip)
    t = mark("context", t)

    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    batch, steps_cap = int(traffic["batch"]), int(traffic["epoch_steps"])
    tokens_per_step = batch * model_lib.tokens_per_row(cfg, traffic)

    model = model_lib.build(cfg, traffic)
    t = mark("build", t)
    rng = np.random.default_rng(seed)
    verify = [model_lib.features(cfg, traffic, rng, batch)
              for _ in range(VERIFY_STEPS)]
    wx, wy = model_lib.features(cfg, traffic, rng, batch * steps_cap)
    window_set = FeatureSet.array(wx, wy, shuffle=True,
                                  seed=seed % (2 ** 31))
    t = mark("data", t)
    got = first_steps(model, model_lib, ref, cfg, seed, verify, batch)
    t = mark("first_steps", t)

    # -- warm the window's own path (a mid-epoch stop by the trigger), and
    # take the step time from two warm fits of 1 and WARM_STEPS steps: what
    # a fit costs beside its steps is in both and falls out
    walls = {}
    for n in (1, WARM_STEPS):
        trigger = make_trigger(n)
        t1 = time.perf_counter()
        model.fit(window_set, batch_size=batch, nb_epoch=10 ** 6,
                  end_trigger=trigger)
        jax.block_until_ready(model.params)
        walls[n] = time.perf_counter() - t1
    step_s = (walls[WARM_STEPS] - walls[1]) / (WARM_STEPS - 1)
    window_seconds = min(seconds, TRACE_SECONDS) if trace else seconds
    planned = max(2, math.ceil(window_seconds / step_s))
    if planned >= steps_cap:
        raise RuntimeError(f"{planned} steps do not fit the window's epoch "
                           f"of {steps_cap}: raise epoch_steps in the "
                           f"traffic file")
    warm_loss_reduction(planned)
    t = mark("warm_window", t)

    gc.collect()
    watch = CompileWatch()
    compiles_before = compile_counts()
    wait_before = badput_seconds("data_wait")
    iters_before = int(model.finished_iterations)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_process
    phases["setup_s"] = setup_s

    # ------------------------------ window ------------------------------
    watch.start()
    trigger = make_trigger(planned)
    t0 = time.perf_counter()
    model.fit(window_set, batch_size=batch, nb_epoch=10 ** 6,
              end_trigger=trigger)
    jax.block_until_ready(model.params)
    wall = time.perf_counter() - t0
    compiled_in_window = watch.stop()
    # ---------------------------------------------------------------------
    if trace:
        jax.profiler.stop_trace()
    steps = int(model.finished_iterations) - iters_before
    tokens = steps * tokens_per_step
    compiled = compile_counts()["total"] - compiles_before["total"]
    if compiled or compiled_in_window:
        raise RuntimeError(
            f"compilation inside the measured window: zoo_jit_compile_total "
            f"+{compiled:.0f}, XLA backend compilations "
            f"{[round(c, 3) for c in compiled_in_window]} s")
    if steps != planned:
        raise RuntimeError(f"the window made {steps} steps, not the "
                           f"{planned} it was planned for")
    memory_peak = reference_run.memory_stat("peak_bytes_in_use") or 0
    reserved_peak = reference_run.memory_stat("peak_bytes_reserved")
    rate = tokens / wall
    stamps = list(trigger.stamps)
    _log(f"window: {steps} steps (step {step_s:.4f} s in set-up), {tokens} "
         f"tokens in {wall:.3f} s = {rate:.1f} tokens/s; host ran ahead of "
         f"the device by {wall - stamps[-1]:.2f} s at the trigger; peak "
         f"{memory_peak / 1e9:.3f} GB")
    _log("set-up phases (s): " + json.dumps(
        {k: round(v, 2) for k, v in phases.items()})
         + " first_steps: " + json.dumps(got["seconds"]))
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, f"steps_{cell['name']}_{seed}_"
                               f"{os.getpid()}.json"), "w") as f:
            json.dump({"stamps": stamps, "wall": wall, "steps": steps,
                       "rate": rate, "phases": phases,
                       "compile": compiles_before,
                       "moe": (getattr(model, "last_fit_report", None)
                               or {}).get("moe")}, f)

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        view = {"cfg": cfg, "traffic": traffic,
                "model": model, "model_lib": model_lib, "device": device,
                "peaks": (peaks or {}).get(device["kind"]),
                "window_s": wall, "steps": steps,
                "setup_compile_s": compiles_before["seconds"],
                "data_wait_s": badput_seconds("data_wait") - wait_before,
                "memory_peak_bytes": memory_peak,
                "memory_reserved_peak_bytes": reserved_peak,
                "batch": verify[0]}
        from ..lib import trace as trace_lib
        view["trace"] = trace_lib.reduce(trace_dir, wall)
        if dump:
            with open(os.path.join(dump, f"trace_{cell['name']}.txt"),
                      "w") as f:
                f.write(trace_lib.describe(trace_lib.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for name, (reader, unit) in readers.items():
            value = reader(view)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": unit}
        if view["trace"] is not None:
            device["busy_s"] = view["trace"]["busy_s"]
            device["window_s"] = view["trace"]["window_s"]
            breakdown = view["trace"]["breakdown"]
        del view
    else:
        metrics["train_tokens_per_s"] = {"value": rate, "unit": "tokens/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    device["memory_peak_bytes"] = memory_peak

    # -- the reference, on a chip the program no longer holds -------------
    del model, window_set, wx, wy
    free_program_state()
    t = time.perf_counter()
    want = reference_run.three_steps(
        ref, cfg, seed, verify, int(traffic["reference_rows_per_chip"]))
    held, n = want["device_bytes"], want["parameters"]
    _log(f"reference: three steps in {time.perf_counter() - t:.1f} s; "
         + ("the device counts no bytes in use" if held is None else
            f"at most {held / 1e9:.3f} GB in use on the device = "
            f"{held / n:.2f} bytes a parameter ({n} parameters)"))
    correct, rows = compare.compare(got, want, limits)
    for r in rows:
        _log("compared {name}: {value:.3e} (limit {limit}) {mark} [{note}]"
             .format(mark="ok" if r["ok"] else "FAILED", **r))

    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": steps, "failed": 0,
        "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"steps": steps, "tokens": tokens, "seconds": wall}
    result["compared"] = {r["name"]: {"value": r["value"],
                                      "limit": r["limit"]} for r in rows}
    return result
