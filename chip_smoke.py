#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no options: ``python chip_smoke.py``. It drives the train,
kernel and serve paths once through the entry points a user calls
(``init_zoo_context`` → ``compile``/``fit``; ``InferenceModel.from_keras`` →
``ClusterServing`` → ``InputQueue``/``OutputQueue``) at the full width of
models the repo already runs, on every chip ``jax.devices()`` reports, and
checks what comes out against float32 references. Depth is what the repo's
own bench uses; weights and data are random from fixed seeds.

Phases (any failure makes the exit code non-zero; none is skipped):

* ``device``  — the platform must be ``tpu`` (JAX falls back to the CPU when
  libtpu cannot take the chip, and a CPU run must not pass as a chip run)
  and its ``device_kind`` must be in the peaks table.
* ``train``   — BERT-base (12x768x12, FFN 3072, vocab 30522) at seq 512,
  batch 32, bf16 compute / f32 params, AdamW, two ``fit`` calls.
* ``train_tp`` — only with >= 4 chips: the same model under
  ``init_zoo_context(mesh_data=n/2, mesh_model=2)``.
* ``kernels`` — a causal LM of the same width at seq 4096 through ``fit``
  with every conf key at its default; the step must call the six kernels
  (flash fwd/dq/dkv, fused-CE fwd/dh/dW) and its lowered module must hold
  them as Mosaic custom calls; then each Pallas kernel against its
  float32 ``jax.numpy`` reference.
* ``decoder`` — the decoder configuration's kernels at its widths: flash
  attention with 32 query / 4 key-value heads of 128 at T = 8192, a
  1024-key window and full, forward and dq/dk/dv against ``ops.attention``
  in float32; the experts' grouped product over 32768 rows in 8 groups of
  Zipf sizes (2304 x 896), plain and with the fused gate, forward and
  gradients against a per-group float32 product, and the device time of
  one call of each kernel on both paths.
* ``serve``   — ResNet-50 at 224x224 through the serving stack, 64 frames
  from two producer threads, every answer equal to a direct ``predict``.

The last two lines of stdout are JSON objects. The first, after the tag
``[chip_smoke] report:``, holds the versions, the compile-cache directory,
whether each native library loaded and per phase wall seconds, compile
seconds (``zoo_jit_compile_seconds``) and outcome. The last is the verdict
and nothing else, because the driver's check reads it by its exact keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
with the device as JAX reports it. On any platform but ``tpu`` nothing is
printed to stdout and the exit code is 2.

The phase bodies take their sizes as arguments so that
``tests/test_chip_smoke.py`` can run them tiny on the CPU mesh.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import re
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Mapping, Optional

import numpy as np

#: bf16 keeps 8 significand bits: one rounding moves a value by at most
#: 2**-8 of its magnitude. The kernel tolerances below are small multiples
#: of it, one per rounding step the kernel makes that the float32
#: reference does not.
BF16_EPS = 2.0 ** -8

#: pallas_call names the compiled `kernels` step must contain as Mosaic
#: custom calls (``name=`` at each call site in ops/pallas/)
STEP_KERNELS = ("zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv",
                "zoo_ce_fwd", "zoo_ce_bwd_dh", "zoo_ce_bwd_dw")


class WrongPlatform(RuntimeError):
    """JAX did not come up on the platform the smoke run is for."""


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def compile_stats() -> Dict[str, float]:
    """Process-wide ``zoo_jit_compile_total`` and the summed
    ``zoo_jit_compile_seconds`` over every instrumented entry point."""
    from analytics_zoo_tpu.observability import default_registry
    total = seconds = 0.0
    for m in default_registry().metrics():
        if m.name == "zoo_jit_compile_total":
            total += m.value
        elif m.name == "zoo_jit_compile_seconds":
            seconds += m.sum
    return {"total": total, "seconds": seconds}


def mosaic_kernel_names(lowered_text: str) -> Dict[str, int]:
    """``{kernel_name: count}`` of the Mosaic custom calls in a lowered
    (StableHLO) module. An interpreted pallas_call lowers to plain HLO
    and an XLA-path op has no custom call, so neither shows up here."""
    names: collections.Counter = collections.Counter()
    for line in lowered_text.splitlines():
        if "@tpu_custom_call" in line:
            m = re.search(r'kernel_name = "([^"]+)"', line)
            names[m.group(1) if m else "?"] += 1
    return dict(names)


def _scaled_err(got, want) -> float:
    """max|got - want| / max|want| in float64 — one number per tensor that
    does not blow up on the near-zero entries an elementwise relative
    error would."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _check(errors: Dict[str, float], bounds: Mapping[str, float]) -> None:
    bad = {k: (v, bounds[k]) for k, v in errors.items() if not v <= bounds[k]}
    if bad:
        raise AssertionError(
            "kernel disagrees with its float32 reference: " + ", ".join(
                f"{k} err {v:.3e} > bound {b:.3e}"
                for k, (v, b) in bad.items()))


def _run_kernel(fn: Callable, args, kernels, require_mosaic: bool):
    """Lower ``fn`` once, compile and run THAT module; on a chip run first
    check that each named pallas_call is a Mosaic custom call in it — a
    kernel that fell back to an XLA op or to interpret mode would agree
    with the reference and prove nothing."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    if require_mosaic:
        found = mosaic_kernel_names(lowered.as_text())
        missing = [k for k in kernels if found.get(k, 0) < 1]
        if missing:
            raise AssertionError(f"no Mosaic call for {missing} in the "
                                 f"lowered module (found {found})")
    return lowered.compile()(*args)


def _with_context(mesh: Optional[Mapping[str, int]],
                  conf: Optional[Mapping[str, Any]]):
    """Re-initialise the zoo context for a phase that needs its own mesh
    or conf; returns a zero-arg restore function (no-op when the phase
    rides the context it was given)."""
    if not mesh and not conf:
        return lambda: None
    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    reset_zoo_context()
    init_zoo_context(conf=dict(conf or {}),
                     **{f"mesh_{k}": v for k, v in (mesh or {}).items()})

    def restore():
        reset_zoo_context()
        init_zoo_context()
    return restore


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device(require_platform: Optional[str] = "tpu") -> Dict[str, Any]:
    import jax

    from analytics_zoo_tpu.utils import profiling
    devices = jax.devices()
    d0 = devices[0]
    backend = jax.default_backend()
    if require_platform is not None and (d0.platform != require_platform
                                         or backend != require_platform):
        raise WrongPlatform(
            f"chip_smoke needs platform {require_platform!r} but JAX came "
            f"up on jax.devices()[0].platform={d0.platform!r}, "
            f"jax.default_backend()={backend!r} "
            f"(device_kind={d0.device_kind!r}, {len(devices)} device(s))")
    # raises on a TPU kind that is not in the peaks table
    peak = profiling.device_peak_flops(d0)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "peak_bf16_flops": peak}


# ---------------------------------------------------------------------------
# phase: train (BERT-base classifier through compile -> fit)
# ---------------------------------------------------------------------------

def phase_train(*, seq_len: int = 512, batch: int = 32, n_examples: int = 128,
                n_block: int = 12, hidden: int = 768, n_head: int = 12,
                ffn: int = 3072, vocab: int = 30522, lr: float = 1e-4,
                mesh: Optional[Mapping[str, int]] = None,
                expect_model_sharded: bool = False) -> Dict[str, Any]:
    """Two ``fit`` calls of two epochs each on a task the model can learn:
    class-0 sequences draw their tokens from ids [1, 50], class-1 from
    [51, 100], so every token that matters is seen thousands of times.
    ``lr`` is 5x the bench's 2e-5 — at 2e-5 sixteen steps move the loss by
    less than the dropout noise."""
    import jax
    import optax

    from analytics_zoo_tpu.common.context import get_zoo_context
    from analytics_zoo_tpu.feature import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras import set_policy
    from analytics_zoo_tpu.pipeline.api.keras.engine import _reset_policy
    from analytics_zoo_tpu.tfpark import BERTClassifier

    restore = _with_context(mesh, None)
    set_policy(compute_dtype="bfloat16", param_dtype="float32")
    try:
        ctx_mesh = get_zoo_context().mesh
        rng = np.random.default_rng(21)
        y = rng.integers(0, 2, n_examples).astype(np.int32)
        tok = (rng.integers(1, 51, (n_examples, seq_len))
               + 50 * y[:, None]).astype(np.int32)
        m = BERTClassifier(num_classes=2, vocab=vocab, hidden_size=hidden,
                           n_block=n_block, n_head=n_head, seq_len=seq_len,
                           intermediate_size=ffn, attn_drop=0.0)
        m.compile(optimizer=optax.adamw(lr), loss="scce")
        fs = FeatureSet.array(m.make_inputs(tok), y, seed=0)

        records: List[Dict[str, Any]] = []
        c0 = compile_stats()["total"]
        m.fit(fs, batch_size=batch, nb_epoch=2, callbacks=[records.append])
        c1 = compile_stats()["total"]
        m.fit(fs, batch_size=batch, nb_epoch=2, callbacks=[records.append])
        c2 = compile_stats()["total"]

        losses = [float(r["loss"]) for r in records]
        if not all(np.isfinite(losses)):
            raise AssertionError(f"non-finite training loss: {losses}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"training loss did not fall: {losses}")
        if c1 - c0 < 1:
            raise AssertionError("first fit recorded no compilation in "
                                 "zoo_jit_compile_total")
        if c2 != c1:
            raise AssertionError(
                f"second fit compiled again: zoo_jit_compile_total "
                f"{c1:.0f} -> {c2:.0f}")

        n_dev = ctx_mesh.devices.size
        leaves = jax.tree_util.tree_flatten_with_path(m.params)[0]
        short = [jax.tree_util.keystr(p) for p, leaf in leaves
                 if len(leaf.sharding.device_set) != n_dev]
        if short:
            raise AssertionError(
                f"{len(short)} parameter leaves do not span all {n_dev} "
                f"mesh devices, first: {short[0]}")
        qkv = [(jax.tree_util.keystr(p), str(leaf.sharding.spec))
               for p, leaf in leaves
               if "qkv" in jax.tree_util.keystr(p)
               and jax.tree_util.keystr(p).endswith("['W']")]
        if expect_model_sharded and not all("model" in s for _, s in qkv):
            raise AssertionError(f"qkv weights are not model-sharded: "
                                 f"{qkv[:2]}")
        return {"losses": [round(l, 5) for l in losses],
                "mesh": {k: int(v) for k, v in ctx_mesh.shape.items()
                         if int(v) != 1},
                "param_leaves": len(leaves),
                "qkv_spec": qkv[0][1] if qkv else None,
                "compiles_first_fit": int(c1 - c0),
                "compiles_second_fit": int(c2 - c1),
                # information, not a metric: the last epoch's own rate
                "info_examples_per_s": round(
                    float(records[-1]["throughput"]), 1)}
    finally:
        _reset_policy()
        restore()


# ---------------------------------------------------------------------------
# phase: kernels (causal LM through fit, then each kernel vs its reference)
# ---------------------------------------------------------------------------

def train_step_kernels(model, x_batch, y_batch, per_device: bool = False):
    """``(routed, mosaic, shapes)`` for the loop's jitted ``train.step`` at
    this batch shape: the pallas_call names in its jaxpr (what the routers
    chose — the same on every platform); of those, the ones its lowered
    module holds as Mosaic custom calls (compiled, not interpreted); and,
    with ``per_device``, the distinct result shapes of those calls in the
    COMPILED per-device module — on several chips they show whether XLA
    split the batch across the chips or gathered all of it onto each
    (pallas_call has no SPMD partitioning rule). Tracing is deterministic
    in the abstract signature, so this is the program ``fit`` ran."""
    import jax

    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    loop = model._loop
    bsh = mesh_lib.batch_sharding(loop.mesh)
    traced = loop._train_step.trace(
        model.params, model.opt_state, model.net_state, jax.random.key(0),
        jax.device_put(x_batch, bsh), jax.device_put(y_batch, bsh))
    routed = collections.Counter(
        re.findall(r"\bname=(zoo_\w+)", str(traced.jaxpr)))
    lowered = traced.lower()
    shapes: List[str] = []
    if per_device:
        for line in lowered.compile().as_text().splitlines():
            if 'custom_call_target="tpu_custom_call"' in line:
                m = re.search(r"= (.*?) custom-call\(", line)
                if m and m.group(1) not in shapes:
                    shapes.append(m.group(1))
    return dict(routed), mosaic_kernel_names(lowered.as_text()), shapes


def _flash_heads_first(q, k, v, **kw):
    """The flash entry on (B, H, T, D) operands, the layout the XLA
    reference takes: the entry itself wants (B, T, H, D), where a
    projection's output reshapes to for nothing."""
    from analytics_zoo_tpu.ops.pallas import flash_attention
    return flash_attention(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                           **kw).transpose(0, 2, 1, 3)


def flash_parity(*, n_head: int, seq_len: int, head_dim: int,
                 require_mosaic: bool = True) -> Dict[str, float]:
    """Flash forward and dq/dk/dv, causal (B=1) and key-padding (B=2, the
    second row 3/8 padded), bf16 operands, against ``ops.attention`` in
    float32 at ``highest`` matmul precision on the same (upcast) inputs.

    Bounds, as a share of each tensor's max: the forward rounds the
    probabilities to bf16 for the PV matmul (half an eps each, averaged
    over the keys) and the output to bf16 (half an eps): 2 eps. The
    backward rounds p and ds to bf16 before its matmuls and dq/dk/dv on
    the way out: 4 eps. The softmax statistics and every accumulation are
    float32 in the stated configuration; keeping either in bf16 over
    thousands of keys costs sqrt(T) eps and lands far outside."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.attention import dot_product_attention

    errors: Dict[str, float] = {}
    rng = np.random.default_rng(31)
    for tag, b, causal in (("causal", 1, True), ("padded", 2, False)):
        shape = (b, n_head, seq_len, head_dim)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                   for _ in range(3))
        g = jnp.asarray(rng.normal(size=shape), jnp.float32)
        mask = None
        if not causal:
            keep = np.ones((b, seq_len), np.float32)
            keep[1, seq_len * 5 // 8:] = 0.0
            mask = jnp.asarray(keep)

        def kernel(q, k, v):
            def f(q, k, v):
                o = _flash_heads_first(q, k, v, mask=mask, causal=causal)
                return jnp.sum(o.astype(jnp.float32) * g), o
            (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                               has_aux=True)(q, k, v)
            return (o,) + grads

        def reference(q, k, v):
            def f(q, k, v):
                o = dot_product_attention(
                    q, k, v, causal=causal,
                    mask=None if mask is None else mask[:, None, None, :])
                return jnp.sum(o * g), o
            (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                               has_aux=True)(q, k, v)
            return (o,) + grads

        got = _run_kernel(kernel, (q, k, v),
                          ("zoo_flash_fwd", "zoo_flash_bwd_dq",
                           "zoo_flash_bwd_dkv"), require_mosaic)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*(a.astype(jnp.float32)
                                        for a in (q, k, v)))
        for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
            errors[f"flash_{tag}_{name}"] = _scaled_err(a, w)
    _check(errors, {k: (2 if k.endswith("_out") else 4) * BF16_EPS
                    for k in errors})
    return errors


def fused_ce_parity(*, rows: int, hidden: int, vocab: int,
                    require_mosaic: bool = True) -> Dict[str, float]:
    """Fused LM-head CE (forward, dh, dW, db) on bf16 hidden states
    against the full-logits objective in float32 at ``highest`` precision
    on the same inputs (W pre-rounded to bf16, as the step casts it).

    Bounds: the fused path rounds each logit to bf16 exactly as
    ``Dense.call`` does under the bf16 policy — at most eps/2 of a logit
    of a few units per row, with random sign, so 1e-4 of the mean loss
    over thousands of rows (an unmasked pad column or a dropped tile
    moves it by 1e-3 or more). The backward re-forms p from those logits
    and rounds dlogits to bf16 for both matmuls — half an eps each per
    element: 1 eps of the max for the float32 sums dW and db, 2 eps for
    dh, which is rounded to bf16 once more on the way out."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        fused_sparse_cross_entropy)
    from analytics_zoo_tpu.pipeline.api.keras import objectives

    rng = np.random.default_rng(32)
    h = jnp.asarray(rng.normal(size=(rows, hidden)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(hidden, vocab)) * 0.02,
                    jnp.bfloat16).astype(jnp.float32)
    b = jnp.asarray(rng.normal(size=(vocab,)) * 0.02, jnp.float32)
    y = jnp.asarray(rng.integers(0, vocab, rows), jnp.int32)

    def fused(h, w, b):
        return fused_sparse_cross_entropy(y, h, w, b)

    def full(h, w, b):
        logits = jnp.matmul(h, w) + b
        return objectives.sparse_categorical_crossentropy_from_logits(
            y, logits)

    got = _run_kernel(jax.value_and_grad(fused, argnums=(0, 1, 2)),
                      (h, w, b), ("zoo_ce_fwd", "zoo_ce_bwd_dh",
                                  "zoo_ce_bwd_dw"), require_mosaic)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(full, argnums=(0, 1, 2)))(
            h.astype(jnp.float32), w, b)
    errors = {"ce_loss": _scaled_err(got[0], want[0])}
    for name, a, r in zip(("dh", "dw", "db"), got[1], want[1]):
        errors[f"ce_{name}"] = _scaled_err(a, r)
    _check(errors, {"ce_loss": 1e-4, "ce_dh": 2 * BF16_EPS,
                    "ce_dw": BF16_EPS, "ce_db": BF16_EPS})
    return errors


def embed_expand_parity(*, capacity: int, dim: int, n: int,
                        require_mosaic: bool = True) -> Dict[str, float]:
    """``embed_expand`` against ``jnp.take``: a 0/1 selection must return
    the rows bit for bit, in float32 (full 24-bit significands, which an
    MXU pass at default precision rounds to bf16 — the kernel asks for
    ``highest``) and in bf16. Bound: 0."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.pallas import embed_expand

    rng = np.random.default_rng(33)
    inv = jnp.asarray(rng.integers(0, capacity, n), jnp.int32)
    errors: Dict[str, float] = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        rows = jnp.asarray(rng.normal(size=(capacity, dim)), dtype)
        got = np.asarray(_run_kernel(embed_expand, (rows, inv),
                                     ("zoo_embed_expand",), require_mosaic),
                         np.float32)
        want = np.asarray(jnp.take(rows, inv, axis=0), np.float32)
        errors[f"embed_expand_{jnp.dtype(dtype).name}"] = float(
            np.max(np.abs(got - want)))
    _check(errors, {k: 0.0 for k in errors})
    return errors


def int8_matmul_parity(*, m: int, k: int, n: int,
                       require_mosaic: bool = True) -> Dict[str, float]:
    """``int8_matmul`` against the dequantised product in float32 at
    ``highest`` precision. The kernel upcasts both operands to float32 in
    VMEM, multiplies at ``highest`` and accumulates in float32, so it owes
    float32 accuracy: 1e-5 of the output's max (summation order over K
    and the scale applied after the sum instead of before)."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.pallas import int8_matmul

    rng = np.random.default_rng(34)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    scales = jnp.asarray(rng.uniform(0.5, 1.5, n) / 127.0, jnp.float32)
    got = _run_kernel(int8_matmul, (x, w_q, scales), ("zoo_int8_matmul",),
                      require_mosaic)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, w, s: x @ (w.astype(jnp.float32) * s))(
            x, w_q, scales)
    errors = {"int8_matmul": _scaled_err(got, want)}
    _check(errors, {"int8_matmul": 1e-5})
    return errors


def phase_kernels(*, seq_len: int = 4096, batch: int = 4, n_seqs: int = 8,
                  n_block: int = 12, hidden: int = 768, n_head: int = 12,
                  vocab: int = 30522, lr: float = 1e-3, epochs: int = 3,
                  conf: Optional[Mapping[str, Any]] = None,
                  require_mosaic: bool = True, ce_rows: int = 4096,
                  embed_shape=(4096, 64, 8192),
                  int8_shape=(256, 768, 3072)) -> Dict[str, Any]:
    """``conf`` is for the CPU test only (it forces the kernels on, in
    interpret mode); ``main`` passes none, so every ``zoo.pallas.*`` and
    ``zoo.train.*`` key is at its default and the routing is what a user
    gets. Tokens come from the first 512 ids so that six steps are enough
    to move the loss; the logits still span the whole vocabulary."""
    import jax
    import optax

    from analytics_zoo_tpu.feature import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras import Sequential, set_policy
    from analytics_zoo_tpu.pipeline.api.keras.engine import _reset_policy
    from analytics_zoo_tpu.pipeline.api.keras.layers import (Dense,
                                                             TransformerLayer)

    restore = _with_context(None, conf)
    set_policy(compute_dtype="bfloat16", param_dtype="float32")
    try:
        used = min(vocab, 512)
        rng = np.random.default_rng(22)
        x = rng.integers(0, used, (n_seqs, seq_len)).astype(np.int32)
        y = ((7 * x + 13) % used).astype(np.int32)
        m = Sequential([
            TransformerLayer(vocab=vocab, seq_len=seq_len, n_block=n_block,
                             hidden_size=hidden, n_head=n_head,
                             hidden_drop=0.0, attn_drop=0.0,
                             embedding_drop=0.0, bidirectional=False,
                             input_shape=(seq_len,)),
            Dense(vocab),
        ])
        m.compile(optimizer=optax.adam(lr), loss="scce_with_logits")
        records: List[Dict[str, Any]] = []
        m.fit(FeatureSet.array(x, y, seed=0), batch_size=batch,
              nb_epoch=epochs, callbacks=[records.append])
        losses = [float(r["loss"]) for r in records]
        rate = float(records[-1]["throughput"])
        del records     # they hold the params and the optimizer state
        if not all(np.isfinite(losses)):
            raise AssertionError(f"non-finite LM loss: {losses}")
        if not losses[-1] < 0.98 * losses[0]:
            raise AssertionError(f"LM loss did not fall: {losses}")

        routed, mosaic, shapes = train_step_kernels(
            m, x[:batch], y[:batch], per_device=require_mosaic)
        unrouted = [k for k in STEP_KERNELS if routed.get(k, 0) < 1]
        if unrouted:
            raise AssertionError(
                f"the step never calls {unrouted} (pallas calls in its "
                f"jaxpr: {routed}) — the routers chose the XLA path")
        interpreted = [k for k in STEP_KERNELS if mosaic.get(k, 0) < 1]
        if require_mosaic and interpreted:
            raise AssertionError(
                f"the compiled step lacks Mosaic calls for {interpreted} "
                f"(found {mosaic}) — they ran in interpret mode")
        # free the model before the float32 references claim their HBM
        del m
        out: Dict[str, Any] = {
            "losses": [round(l, 4) for l in losses],
            # information, not a metric: the last epoch's own rate
            "info_sequences_per_s": round(rate, 2),
            "pallas_calls": routed, "mosaic_calls": mosaic,
            # information: global batch rows are `batch`; see the docstring
            "info_mosaic_result_shapes_per_device": shapes}
        errors: Dict[str, float] = {}
        errors.update(flash_parity(n_head=n_head, seq_len=seq_len,
                                   head_dim=hidden // n_head,
                                   require_mosaic=require_mosaic))
        errors.update(fused_ce_parity(rows=ce_rows, hidden=hidden,
                                      vocab=vocab,
                                      require_mosaic=require_mosaic))
        capacity, dim, n = embed_shape
        errors.update(embed_expand_parity(capacity=capacity, dim=dim, n=n,
                                          require_mosaic=require_mosaic))
        mm, kk, nn = int8_shape
        errors.update(int8_matmul_parity(m=mm, k=kk, n=nn,
                                         require_mosaic=require_mosaic))
        out["reference_errors"] = {k: float(f"{v:.3e}")
                                   for k, v in errors.items()}
        return out
    finally:
        _reset_policy()
        restore()


# ---------------------------------------------------------------------------
# phase: decoder (window / grouped-head flash, the experts' grouped product)
# ---------------------------------------------------------------------------

def grouped_flash_parity(*, n_head: int, n_kv_head: int, seq_len: int,
                         head_dim: int, window: int,
                         require_mosaic: bool = True) -> Dict[str, float]:
    """Flash forward and dq/dk/dv with grouped heads (B = 1), a
    ``window``-key window and full, bf16 operands, against
    ``ops.attention`` in float32 at ``highest`` precision on the same
    (upcast) inputs. The reference goes one key/value head at a time (its
    float32 scores of all 32 query heads at T = 8192 would be 8.6 GB).
    Bounds as ``flash_parity``'s; dk and dv sum ``group`` query heads'
    contributions in float32 inside the kernel, which adds no rounding."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.attention import dot_product_attention

    group = n_head // n_kv_head
    errors: Dict[str, float] = {}
    rng = np.random.default_rng(41)
    q = jnp.asarray(rng.normal(size=(1, n_head, seq_len, head_dim)),
                    jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(1, n_kv_head, seq_len, head_dim)),
                        jnp.bfloat16) for _ in range(2))
    g = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    for tag, win in (("window", window), ("full", None)):
        def kernel(q, k, v, win=win):
            def f(q, k, v):
                o = _flash_heads_first(q, k, v, causal=True, window=win)
                return jnp.sum(o.astype(jnp.float32) * g), o
            (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                               has_aux=True)(q, k, v)
            return (o,) + grads

        def reference(q, k, v, g, win=win):
            def f(q, k, v):
                o = dot_product_attention(q, k, v, causal=True, window=win)
                return jnp.sum(o * g), o
            (_, o), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                               has_aux=True)(q, k, v)
            return (o,) + grads

        suffix = "_win" if win is not None and win < seq_len else ""
        got = _run_kernel(kernel, (q, k, v), tuple(
            name + suffix for name in ("zoo_flash_fwd", "zoo_flash_bwd_dq",
                                       "zoo_flash_bwd_dkv")), require_mosaic)
        ref = jax.jit(reference)
        worst = dict.fromkeys(("out", "dq", "dk", "dv"), 0.0)
        for h in range(n_kv_head):
            heads = slice(h * group, (h + 1) * group)
            with jax.default_matmul_precision("highest"):
                want = ref(q[:, heads].astype(jnp.float32),
                           k[:, h:h + 1].astype(jnp.float32),
                           v[:, h:h + 1].astype(jnp.float32), g[:, heads])
            parts = (got[0][:, heads], got[1][:, heads], got[2][:, h:h + 1],
                     got[3][:, h:h + 1])
            for name, a, w in zip(worst, parts, want):
                worst[name] = max(worst[name], _scaled_err(a, w))
        for name, err in worst.items():
            errors[f"flash_gqa_{tag}_{name}"] = err
    _check(errors, {k: (2 if k.endswith("_out") else 4) * BF16_EPS
                    for k in errors})
    return errors


def _gmm_call_ms(fn, args, reps: int = 3) -> Dict[str, list]:
    """``{kernel: [device ms a call, calls a run]}`` of the grouped
    products' kernels in ``reps`` runs of the compiled ``fn``, from the
    device trace: XLA's ``ragged-dot*`` or the ``zoo_moe_gmm*`` calls, by
    instruction name and result shape."""
    import tempfile

    import jax
    from jax.profiler import ProfileData
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        paths = [os.path.join(root, f) for root, _, files in os.walk(tmp)
                 for f in files if f.endswith(".xplane.pb")]
        data = ProfileData.from_file(paths[0])
    spent: Dict[str, list] = {}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                own, _, rest = ev.name.partition(" = ")
                if "ragged-dot" in own or "zoo_moe_gmm" in own:
                    key = (re.sub(r"\.\d+$", "", own.lstrip("%")) + " "
                           + re.sub(r"\{[^}]*\}", "", rest.split(" ")[0]))
                    ns, n = spent.get(key, (0, 0))
                    spent[key] = [ns + int(ev.duration_ns), n + 1]
    return {k: [round(ns / n / 1e6, 4), n // reps]
            for k, (ns, n) in sorted(spent.items())}


def grouped_matmul_parity(*, rows: int, groups: int, d_in: int, d_out: int
                          ) -> Dict[str, float]:
    """``ops.grouped_matmul`` forward, ``dx`` and ``dW``, and the same of the
    fused gate ``ops.gated_grouped_matmul``, on bf16 operands against one
    float32 product a group. The group sizes follow a Zipf law and leave
    the last tenth of the rows to no group: those rows of the results and
    of ``dx`` must come back zero. One rounding to bf16 on the way out of
    each product: 2 eps (dW accumulates in float32 and is compared in the
    weights' dtype); the gated pair rounds gate, up and their product, and
    its gradients pass the rounded ``d_gate`` / ``d_up``: 4 eps. On the chip
    the phase also times one call of each kernel on both paths (XLA's
    ``ragged-dot`` and the Pallas ``zoo_moe_gmm*``) from the device trace
    and logs them."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops import grouped_matmul as gm

    rng = np.random.default_rng(43)
    share = 1.0 / np.arange(1, groups + 1)
    sizes = np.floor(share / share.sum() * rows * 0.9).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(rows, d_in)), jnp.bfloat16)
    w, wup = (jnp.asarray(rng.normal(size=(groups, d_in, d_out))
                          / np.sqrt(d_in), jnp.bfloat16) for _ in range(2))
    g = jnp.asarray(rng.normal(size=(rows, d_out)), jnp.bfloat16)
    names = ("out", "dx", "dw", "gated_out", "gated_dx", "gated_dwgate",
             "gated_dwup")

    def kernel(x, w, wup, sizes, impl=None):
        impl = impl or gm._impl(x, w, wup)
        y, vjp = jax.vjp(lambda x, w: gm.product(x, w, sizes, impl), x, w)
        act, gated_vjp = jax.vjp(
            lambda x, w, wup: gm.gated_product(x, w, wup, sizes, impl),
            x, w, wup)
        return (y,) + vjp(g) + (act,) + gated_vjp(g)

    def reference(x, w, wup, g):
        bounds = np.concatenate([[0], np.cumsum(sizes)])

        def f(x, w):
            parts = [x[lo:hi] @ w[i] for i, (lo, hi) in
                     enumerate(zip(bounds[:-1], bounds[1:]))]
            parts.append(jnp.zeros((rows - int(bounds[-1]), d_out),
                                   jnp.float32))
            return jnp.concatenate(parts, axis=0)
        y, vjp = jax.vjp(f, x, w)
        act, gated_vjp = jax.vjp(
            lambda x, w, wup: jax.nn.silu(f(x, w)) * f(x, wup), x, w, wup)
        return (y,) + vjp(g) + (act,) + gated_vjp(g)

    got = jax.jit(kernel)(x, w, wup, jnp.asarray(sizes))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(*(a.astype(jnp.float32)
                                    for a in (x, w, wup, g)))
    errors = {f"grouped_matmul_{name}": _scaled_err(a, b)
              for name, a, b in zip(names, got, want)}
    _check(errors, {k: (4 if "gated" in k else 2) * BF16_EPS
                    for k in errors})
    tail = int(sizes.sum())
    for name, a in zip(names, got):
        if name.endswith(("out", "dx")) and np.any(
                np.asarray(a[tail:], np.float32) != 0.0):
            raise AssertionError(f"grouped_matmul {name}: rows past the "
                                 f"last group are not zero")
    if jax.default_backend() == "tpu":
        for impl in ("xla", gm._impl(x, w, wup)):
            fn = jax.jit(functools.partial(kernel, impl=impl))
            print(f"[chip_smoke] grouped products, {impl}: " + json.dumps(
                _gmm_call_ms(fn, (x, w, wup, jnp.asarray(sizes)))),
                flush=True)
    return errors


def phase_decoder(*, n_head: int = 32, n_kv_head: int = 4,
                  seq_len: int = 8192, head_dim: int = 128,
                  window: int = 1024, gmm_shape=(32768, 8, 2304, 896),
                  conf: Optional[Mapping[str, Any]] = None,
                  require_mosaic: bool = True) -> Dict[str, Any]:
    restore = _with_context(None, conf)
    try:
        errors = grouped_flash_parity(
            n_head=n_head, n_kv_head=n_kv_head, seq_len=seq_len,
            head_dim=head_dim, window=window, require_mosaic=require_mosaic)
        rows, groups, d_in, d_out = gmm_shape
        errors.update(grouped_matmul_parity(rows=rows, groups=groups,
                                            d_in=d_in, d_out=d_out))
        return {"reference_errors": {k: float(f"{v:.3e}")
                                     for k, v in errors.items()}}
    finally:
        restore()


# ---------------------------------------------------------------------------
# phase: serve (ResNet-50 through the serving stack)
# ---------------------------------------------------------------------------

def phase_serve(*, hw: int = 224, n_frames: int = 64, batch_size: int = 8,
                classes: int = 1000, model_name: str = "resnet-50",
                producers: int = 2, answer_timeout_s: float = 600.0
                ) -> Dict[str, Any]:
    """Every record must answer with shape ``(classes,)`` and equal a
    direct ``predict`` of the same frame. Both sides run the same jitted
    ``inference.predict`` on the same weights at the same precision; only
    the batch bucket a frame lands in differs (ragged reads pad to a
    smaller power of two), so the two can differ by float32 summation
    order through ~50 layers and nothing else: 1e-3 of the output's max.
    Serving in bf16 instead of the stated float32 would miss it by an
    eps (4e-3) or more, and a record answered with another frame's result
    by the spread between frames, which the phase checks is at least 10x
    the bound."""
    from analytics_zoo_tpu.models.image.imageclassification import (
        ImageClassifier)
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           LocalBackend, OutputQueue)

    rng = np.random.default_rng(23)
    # per-frame contrast so that a random-weight network tells them apart
    frames = (rng.normal(size=(n_frames, hw, hw, 3))
              * np.linspace(0.25, 2.0, n_frames)[:, None, None, None]
              ).astype(np.float32)
    m = ImageClassifier(model_name, num_classes=classes,
                        input_shape=(hw, hw, 3))
    m.init_weights(sample_input=frames[:2])
    im = InferenceModel().from_keras(m)
    backend = LocalBackend()
    serving = ClusterServing(im, backend=backend,
                             batch_size=batch_size).start()
    inq, outq = InputQueue(backend), OutputQueue(backend)
    answers: List[Optional[np.ndarray]] = [None] * n_frames
    try:
        def produce(lo: int, hi: int) -> None:
            for i in range(lo, hi):
                inq.enqueue(f"smoke-{i}", frames[i])

        bounds = np.linspace(0, n_frames, producers + 1).astype(int)
        threads = [threading.Thread(target=produce, args=(lo, hi),
                                    name=f"smoke-producer-{j}")
                   for j, (lo, hi) in enumerate(zip(bounds[:-1],
                                                    bounds[1:]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=answer_timeout_s)
            if t.is_alive():
                raise AssertionError(f"{t.name} did not finish enqueueing")
        deadline = time.monotonic() + answer_timeout_s
        for i in range(n_frames):
            answers[i] = outq.query(
                f"smoke-{i}", timeout=max(deadline - time.monotonic(), 1.0))
    finally:
        serving.stop()
    missing = [i for i, a in enumerate(answers) if a is None]
    if missing:
        raise AssertionError(f"{len(missing)} of {n_frames} records never "
                             f"answered, first: smoke-{missing[0]}")
    shapes = {a.shape for a in answers}
    if shapes != {(classes,)}:
        raise AssertionError(f"answer shapes {shapes} != {{({classes},)}}")
    served = np.stack(answers)
    direct = np.concatenate([im.predict(frames[i:i + batch_size])
                             for i in range(0, n_frames, batch_size)])
    if not np.all(np.isfinite(direct)):
        raise AssertionError("direct predict returned non-finite values")
    scale = float(np.max(np.abs(direct)))
    err = float(np.max(np.abs(served - direct))) / scale
    spread = float(np.max(np.abs(direct - direct[::-1]))) / scale
    bound = 1e-3
    if not err <= bound:
        raise AssertionError(f"served answers differ from direct predict: "
                             f"err {err:.3e} > bound {bound:.3e}")
    if not spread >= 10 * bound:
        raise AssertionError(
            f"frames are indistinguishable to this model (spread "
            f"{spread:.3e}); the equality check would prove nothing")
    return {"records": n_frames, "served_vs_direct_err": float(f"{err:.3e}"),
            "frame_spread": float(f"{spread:.3e}")}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def run_phase(name: str, fn: Callable[[], Dict[str, Any]],
              results: Dict[str, Dict[str, Any]]) -> bool:
    """Run one phase at the boundary that must keep going: a failure is
    recorded with its traceback and the next phase still runs."""
    c0 = compile_stats()
    t0 = time.perf_counter()
    try:
        info = fn()
        outcome = "ok"
    except Exception as e:  # noqa: BLE001 — phase boundary, reported below
        traceback.print_exc()
        info = {"error": f"{type(e).__name__}: {e}"[:1500]}
        outcome = "failed"
    c1 = compile_stats()
    results[name] = {
        "outcome": outcome,
        "wall_s": round(time.perf_counter() - t0, 2),
        "compile_s": round(c1["seconds"] - c0["seconds"], 2),
        "compiles": int(c1["total"] - c0["total"]),
        **info}
    print(f"[chip_smoke] {name}: {outcome} wall={results[name]['wall_s']}s "
          f"compile={results[name]['compile_s']}s "
          f"({results[name]['compiles']} compilations)"
          + (f" — {info['error']}" if outcome == "failed" else ""),
          flush=True)
    return outcome == "ok"


def versions() -> Dict[str, str]:
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def print_result(results: Mapping[str, Mapping[str, Any]],
                 device: Mapping[str, Any]) -> bool:
    """Print the report line and then, as the last line of stdout, the
    verdict with exactly the keys the driver's check reads."""
    import jax

    from analytics_zoo_tpu.native import image as native_image
    from analytics_zoo_tpu.native import io as native_io

    ok = all(r["outcome"] == "ok" for r in results.values())
    print("[chip_smoke] report: " + json.dumps({
        "versions": versions(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "native": {"zoo_io": native_io.native_io_available(),
                   "zoo_image": native_image.available()},
        "phases": results,
    }), flush=True)
    print(json.dumps({
        "ok": ok,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    }), flush=True)
    return ok


def main() -> int:
    try:
        device = phase_device("tpu")
    except WrongPlatform as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2

    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context()          # mesh over every chip + the compile cache
    results: Dict[str, Dict[str, Any]] = {
        "device": {"outcome": "ok", "wall_s": 0.0, "compile_s": 0.0,
                   "compiles": 0, **device}}
    print(f"[chip_smoke] device: ok {device['count']} x {device['kind']}",
          flush=True)
    n_dev = device["count"]
    run_phase("train", phase_train, results)
    if n_dev >= 4 and n_dev % 2 == 0:
        run_phase("train_tp", lambda: phase_train(
            mesh={"data": n_dev // 2, "model": 2},
            expect_model_sharded=True), results)
    run_phase("kernels", phase_kernels, results)
    run_phase("decoder", phase_decoder, results)
    run_phase("serve", phase_serve, results)

    return 0 if print_result(results, device) else 1


if __name__ == "__main__":
    sys.exit(main())
