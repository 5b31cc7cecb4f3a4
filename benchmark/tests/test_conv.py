"""The short-convolution configuration's part of the yardstick: the counts of
``lib/kernel_cost_mixer.py`` and of ``layer_metrics/attn.grouped_flash_
roofline.py`` against the figures worked by hand in their docstrings, the
three readers this configuration brought on views that have nothing for them
(a program without the scopes reads nothing and does not raise) and on a few
lines of HLO text, the model file's FLOPs, and a whole run of the harness on
a tiny LFM2 cell: the sound run correct; the fp8 control, half a batch and a
fault planted in each thing that is new (the taps reversed in time, the ``C``
gate left out, q/k normalisation left out, the head untied) not."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.kinds import train
from benchmark.lib import compare, kernel_cost, reference_run
from benchmark.lib import kernel_cost_decoder as decoder_cost
from benchmark.lib import kernel_cost_mixer as cost
from benchmark.tests import tiny_lfm2

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2_train_s8192"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cell():
    with open(os.path.join(HERE, "configs", tiny_lfm2.NAME + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", "train_s8192_b4.json")) as f:
        return cfg, json.load(f)


def _reader(name):
    return reference_run.load("layer_metrics", name)


def test_gate_chain_costs_match_the_hand_worked_figures():
    shape = dict(rows=4 * 8192, hidden=2048, kernel=3)
    f_fwd, b_fwd = cost.gate_chain("forward", **shape)
    f_bwd, b_bwd = cost.gate_chain("backward", **shape)
    assert b_fwd == 4 * 134_217_728 + 24_576 == 536_895_488
    assert b_bwd == 7 * 134_217_728 + 2 * 24_576 == 939_573_248
    assert f_fwd == 7 * 67_108_864 == 469_762_048
    assert f_bwd == 21 * 67_108_864 == 1_409_286_144
    assert b_fwd + b_bwd == 1_476_468_736
    # bound by the memory's bandwidth: 1.803 ms a layer and step
    least = sum(kernel_cost.least_seconds(f, b, PEAKS)
                for f, b in ((f_fwd, b_fwd), (f_bwd, b_bwd)))
    assert abs(least - 1_476_468_736 / 819e9) < 1e-12
    assert round(least * 1e3, 3) == 1.803 and round(4 * least * 1e3, 2) == 7.21
    assert f_bwd / 197e12 < 1e-5
    # float32 activations double the tensors, not the taps
    assert cost.gate_chain("forward", act_bytes=4, **shape)[1] == (
        8 * 134_217_728 + 24_576)


def test_grouped_flash_costs_match_the_hand_worked_figures():
    cfg, traffic = _cell()
    shape = _reader("attn.grouped_flash_roofline").shape
    call = shape(cfg, traffic, 1)
    assert call == dict(batch=4, q_heads=32, kv_heads=8, seq=8192,
                        head_dim=64)
    f, b = decoder_cost.flash_call("zoo_flash_fwd", **call)
    assert f == 2 * 2 * 33_558_528 * 64 * 128 == 1_099_645_845_504
    assert b == 2 * 134_217_728 + 2 * 33_554_432 + 4_194_304 == 339_738_624
    three = sum(decoder_cost.flash_call(k, **call)[0]
                for k in decoder_cost.FLASH_TENSORS)
    assert three == 4.5 * f and round(three / 197e12 * 1e3, 1) == 25.1
    # nothing to read: a window (the Mellum cell), ungrouped heads (GLM),
    # no heads at all (GPT-1's keys)
    for name in ("Mellum2-12B-A2.5B-Instruct", "GLM-4.7-Flash",
                 "openai-gpt"):
        with open(os.path.join(HERE, "configs", name + ".json")) as g:
            assert shape(json.load(g), traffic, 1) is None, name


def test_model_flops_count_every_weight_a_token_meets():
    cfg, traffic = _cell()
    model_lib = reference_run.load("models", cfg["model"])
    conv, attn, expert = 16_783_360, 10_485_760, 9_437_184
    weights = (4 * conv + attn + 3 * 2048 * 11776
               + 4 * (2048 * 64 + 0.5 * expert) + 2048 * 8192)
    assert weights == 186_146_816
    assert model_lib.weights_a_token_meets(cfg) == weights
    pairs = 8192 * 8193 // 2
    assert model_lib.train_flops_per_row(cfg, traffic) == (
        6 * weights * 8192 + 3 * 2 * 2 * pairs * 2048)
    # ISSUE 36: 1.117 GFLOP of weights and 0.101 of attention a token
    per_token = model_lib.train_flops_per_row(cfg, traffic) / 8192
    assert round(6 * weights / 1e9, 3) == 1.117
    assert round(per_token / 1e9, 2) == 1.22


HLO = """HloModule step

%fused_computation.1 (p: bf16[64,8]) -> bf16[64,8] {
  ROOT %multiply.1 = bf16[64,8]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/zoo_conv.gate/mul"}
}

ENTRY %main.3 (x: bf16[64,8]) -> bf16[64,8] {
  %fusion.1 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/checkpoint/zoo_conv.gate/mul"}
  %fusion.2 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/zoo_conv.in_proj/dot_general"}
  %fusion.3 = f32[64]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(zoo_conv.out_proj))/dot_general"}
  %fusion.4 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/zoo_attn.qk_norm/mul"}
  %zoo_flash_fwd.5 = bf16[64,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call"}
  ROOT %copy.4 = bf16[8,64]{1,0} copy(%fusion.1), metadata={op_name="jit(step)/zoo_moe.route/top_k"}
}
"""

READERS = ("mixer.conv_time_share", "mixer.conv_gate_roofline",
           "attn.grouped_flash_roofline")


def test_new_readers_read_the_scopes_and_nothing_where_there_are_none():
    cfg, traffic = _cell()
    trace = {"busy_s": 40.0, "window_s": 41.0,
             "op_seconds": {"fusion bf16[64,8]": 6.0, "fusion f32[64]": 1.0,
                            "copy bf16[8,64]": 0.5,
                            "zoo_flash_fwd bf16[64,8]": 10.0},
             "op_calls": {"zoo_flash_fwd bf16[64,8]": 4}}
    view = {"trace": trace, "cfg": cfg, "traffic": traffic, "peaks": PEAKS,
            "device": {"count": 1}, "steps": 5, "_step_text": HLO}
    read = {name: _reader(name).read for name in READERS}
    # two of the three events of a key under zoo_conv.*, one of them the
    # gate; the event of another key under out_proj whole
    assert read["mixer.conv_time_share"](view) == 100 * (4.0 + 1.0) / 40
    least = 5 * 4 * 1_476_468_736 / 819e9
    assert abs(read["mixer.conv_gate_roofline"](view)
               - 100 * least / 2.0) < 1e-9
    flash = 4 * 1_099_645_845_504 / 197e12
    assert abs(read["attn.grouped_flash_roofline"](view)
               - 100 * flash / 10.0) < 1e-9
    # a program without the scopes and kernels (the parent of PR 36):
    # nothing to read, and no exception
    bare = dict(view, _step_text=HLO.replace("zoo_conv", "x"),
                trace=dict(trace, op_seconds={"fusion bf16[64,8]": 4.0},
                           op_calls={}))
    assert [r(bare) for r in read.values()] == [None, None, None]
    assert [r(dict(view, trace=None)) for r in read.values()] == [None] * 3
    # a configuration without conv layers or grouped full attention
    with open(os.path.join(HERE, "configs", "GLM-4.7-Flash.json")) as f:
        other = json.load(f)
    assert read["mixer.conv_gate_roofline"](dict(view, cfg=other)) is None
    assert read["attn.grouped_flash_roofline"](dict(view, cfg=other)) is None


def test_the_cell_resolves_by_name_with_its_readers():
    cell, cfg, traffic, limits, readers = bench_run.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        tiny_lfm2.NAME, "train_s8192_b4", 1)
    assert set(READERS) | {
        "fused_ce_roofline", "ffn.gated_time_share", "moe.experts_roofline",
        "moe.time_share", "moe.load_max_over_mean", "moe.dropped_assignments",
        "moe.rows_run_over_held", "moe.choice_passes_mean",
        "device.step_mfu", "device.peak_hbm_gb"} <= set(readers)
    for name in ("attn.window_flash_roofline", "attn.latent_flash_roofline",
                 "attn.latent_time_share", "flash_attn_roofline"):
        assert name not in readers
    # what the accepted readers take from the configuration
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            len(cfg["held_experts"]), cfg["vocab_size"]) == (
        2048, 1536, 8, 8192)
    assert set(limits) <= set(compare.NUMBERS)
    # the new cell's name was appended, and nothing else was, to the lists
    # of the accepted metrics it reports
    manifest = bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    for metric in manifest["per_layer"]:
        listed = metric.get("workloads")
        if listed and CELL in listed and metric["name"] not in READERS:
            assert listed[-1] == CELL and "glm47flash_train_s8192" in listed


#: the tiny LFM2 cell's limits on the CPU. ``grad_error_median_leaf``
#: between the program in bf16 (at most 7.4e-3, 3 seeds) and the fp8 control
#: (at least 6.3e-2, 3 seeds); the taps reversed read 3.3e-2 at the least,
#: the C gate left out 0.21, no q/k norm 0.38. ``grad_norm_worst_leaf``
#: between the program's 1.75e-2 and the untied head's 0.267 (the table's
#: leaf lacks the head's part; the control reads 1.6e-2 and is not told
#: from the program by it). ``grad_error_worst_leaf`` is not held (0.172 /
#: 0.161: a top-2 choice that flips between bf16 and float32 lands on one
#: expert leaf), nor the losses (4.3e-5 / 1.1e-5)
LIMITS = {"grad_norm_worst_leaf": 0.1, "grad_error_median_leaf": 2e-2,
          "change_norm_worst_leaf": 0.5}


def test_sound_run_of_a_tiny_lfm2_cell_is_correct():
    cfg, traffic = tiny_lfm2.lfm2()
    result = train.run({"name": "tiny_lfm2", "chips": 4}, cfg, traffic,
                       LIMITS, {}, seed=2 ** 31 + 13, seconds=0.5,
                       trace=False, t_process=time.perf_counter(),
                       require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


def planted(ref):
    """fault -> ``(object, attribute, replacement)``: the faults in what
    this configuration brought, planted in its reference (which is then put
    in the program's place)."""
    real_conv = ref.C.short_conv

    def no_c_gate(p, x, mode):
        b, _, u = jnp.split(ref.B.mm(x, p["Win"], mode), 3, axis=-1)
        v, t, k = b * u, x.shape[1], p["conv"].shape[1]
        c = sum(p["conv"][:, k - 1 - s]
                * jnp.pad(v, ((0, 0), (s, 0), (0, 0)))[:, :t]
                for s in range(k))
        return ref.B.mm(c, p["Wout"], mode)
    return {
        "taps_reversed": (ref.C, "short_conv", lambda p, x, mode: real_conv(
            dict(p, conv=p["conv"][:, ::-1]), x, mode)),
        "c_gate_left_out": (ref.C, "short_conv", no_c_gate),
        "qk_norm_left_out": (ref.C, "head_norm", lambda p, x, eps: x),
        # a head of its own that starts as the table: its gradient does
        # not reach the table's leaf
        "head_untied": (ref, "head_matrix",
                        lambda p: jax.lax.stop_gradient(p["wte"]).T),
    }


#: fault -> the number that has to catch it
FAULTS = {
    "fp8_control": "grad_error_median_leaf",
    "half_batch": "grad_error_median_leaf",
    "taps_reversed": "grad_error_median_leaf",
    "c_gate_left_out": "grad_error_median_leaf",
    "qk_norm_left_out": "grad_error_median_leaf",
    "head_untied": "grad_norm_worst_leaf",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_control_and_planted_faults_of_a_tiny_lfm2_cell_are_not_correct(
        fault, monkeypatch):
    cfg, traffic = tiny_lfm2.lfm2()
    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    kw = {"fp8_control": dict(mode="fp8"),
          "half_batch": dict(keep_rows=0.5)}.get(fault, {})
    faults = planted(ref)
    for seed in (21, 22):
        rng = np.random.default_rng(seed)
        batches = [model_lib.features(cfg, traffic, rng, traffic["batch"])
                   for _ in range(train.VERIFY_STEPS)]
        want = reference_run.three_steps(ref, cfg, seed, batches, 1)
        with monkeypatch.context() as m:
            if fault in faults:
                m.setattr(*faults[fault])
            side = reference_run.three_steps(ref, cfg, seed, batches, 1,
                                             **kw)
        ok, rows = compare.compare(side, want, LIMITS)
        failed = {r["name"] for r in rows if not r["ok"]}
        assert not ok and FAULTS[fault] in failed, (fault, rows)
