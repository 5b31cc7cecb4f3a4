"""The latent-attention configuration's part of the yardstick:
the count of ``layer_metrics/attn.latent_flash_roofline.py`` against the
figures worked by hand in its docstring, the three readers this configuration brought on views that have
nothing for them (a program without the scopes reads nothing and does not
raise) and on a few lines of HLO text, the model file's FLOPs, and a whole
run of the harness on a tiny GLM cell (sound run correct, fp8 control and
half a batch not)."""

import json
import os
import time

import numpy as np

from benchmark import run as bench_run
from benchmark.kinds import train
from benchmark.lib import compare, kernel_cost_decoder as cost, reference_run
from benchmark.tests import tiny_glm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "glm47flash_train_s8192"


def _cell():
    with open(os.path.join(HERE, "configs", tiny_glm.NAME + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", "train_s8192_b4.json")) as f:
        return cfg, json.load(f)


def test_latent_flash_costs_match_the_hand_worked_figures():
    cfg, traffic = _cell()
    head_dim = reference_run.load("layer_metrics",
                                  "attn.latent_flash_roofline").head_dim
    assert head_dim(cfg) == 256
    n = cfg["num_attention_heads"]
    shape = dict(batch=traffic["batch"], seq=traffic["seq"], q_heads=n,
                 kv_heads=n, head_dim=256)
    f, b = cost.flash_call("zoo_flash_fwd", **shape)
    assert f == 2 * 2 * 33_558_528 * 256 * 80 == 2_749_114_613_760
    assert b == 4 * 335_544_320 + 2_621_440 == 1_344_798_720
    f_dq, b_dq = cost.flash_call("zoo_flash_bwd_dq", **shape)
    f_dkv, b_dkv = cost.flash_call("zoo_flash_bwd_dkv", **shape)
    assert (f_dq, f_dkv) == (4_123_671_920_640, 5_498_229_227_520)
    assert b_dq == 5 * 335_544_320 + 2 * 2_621_440 == 1_682_964_480
    assert b_dkv == 6 * 335_544_320 + 5_242_880 == 2_018_508_800
    # keys and values of different widths: no one head size, no count
    assert head_dim(dict(cfg, v_head_dim=128)) is None
    assert head_dim({"hidden_size": 768}) is None


def test_model_flops_count_every_weight_a_token_meets():
    cfg, traffic = _cell()
    model_lib = reference_run.load("models", cfg["model"])
    attn, expert = 21_759_232 - 768 - 512, 9_437_184     # less the norms
    weights = (5 * attn + 3 * 2048 * 10240
               + 4 * (2048 * 64 + expert + 0.5 * expert) + 2048 * 19360)
    assert model_lib.weights_a_token_meets(cfg) == weights
    pairs = 8192 * 8193 // 2
    assert model_lib.train_flops_per_row(cfg, traffic) == (
        6 * weights * 8192 + 3 * 5 * 2 * pairs * 20 * (256 + 256))
    # the two attention products a token and layer, forward, against every
    # matrix a token meets in a routed block (ISSUE 33: 84 against 72 M)
    per_token = 2 * pairs * 20 * 512 / 8192
    assert round(per_token / 1e6) == 84
    assert round(2 * (attn + 2048 * 64 + 1.5 * expert) / 1e6) == 72


HLO = """HloModule step

%fused_computation.1 (p: bf16[64,8]) -> bf16[64,8] {
  ROOT %multiply.1 = bf16[64,8]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/zoo_mla.rope/mul"}
}

ENTRY %main.3 (x: bf16[64,8]) -> bf16[64,8] {
  %fusion.1 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/checkpoint/zoo_mla.expand/concatenate"}
  %fusion.2 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/zoo_ffn.gated/mul"}
  %fusion.3 = f32[64]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(zoo_moe.shared))/dot_general"}
  %zoo_flash_fwd.5 = bf16[64,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/zoo_mla.attend/pallas_call"}
  ROOT %copy.4 = bf16[8,64]{1,0} copy(%fusion.1), metadata={op_name="jit(step)/zoo_moe.route/top_k"}
}
"""


def _readers():
    return {name: reference_run.load("layer_metrics", name).read
            for name in ("attn.latent_flash_roofline",
                         "attn.latent_time_share", "ffn.gated_time_share")}


def test_new_readers_read_the_scopes_and_nothing_where_there_are_none():
    cfg, traffic = _cell()
    trace = {"busy_s": 40.0, "window_s": 41.0,
             "op_seconds": {"fusion bf16[64,8]": 4.0, "fusion f32[64]": 1.0,
                            "copy bf16[8,64]": 0.5,
                            "zoo_flash_fwd bf16[64,8]": 10.0},
             "op_calls": {"zoo_flash_fwd bf16[64,8]": 4}}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    view = {"trace": trace, "cfg": cfg, "traffic": traffic, "peaks": peaks,
            "device": {"count": 1}, "_step_text": HLO}
    read = _readers()
    # two of the four events of a key under zoo_mla.*, and the kernel whole
    assert read["attn.latent_time_share"](view) == 100 * (2.0 + 10.0) / 40
    assert read["ffn.gated_time_share"](view) == 100 * (2.0 + 1.0) / 40
    least = 4 * 2_749_114_613_760 / 197e12
    assert abs(read["attn.latent_flash_roofline"](view)
               - 100 * least / 10.0) < 1e-9
    # a program without the scopes and kernels (the parent of PR 33; the
    # post-LN cells): nothing to read, and no exception
    bare = dict(view, _step_text=HLO.replace("zoo_mla", "x").replace(
        "zoo_ffn", "x").replace("zoo_moe.shared", "x"),
        trace=dict(trace, op_seconds={"fusion bf16[64,8]": 4.0},
                   op_calls={}))
    assert [r(bare) for r in read.values()] == [None, None, None]
    assert [r(dict(view, trace=None)) for r in read.values()] == [None] * 3
    other, _ = (json.load(open(os.path.join(
        HERE, "configs", "Mellum2-12B-A2.5B-Instruct.json"))), None)
    assert read["attn.latent_flash_roofline"](dict(view, cfg=other)) is None


def test_the_cell_resolves_by_name_with_its_readers():
    cell, cfg, traffic, limits, readers = bench_run.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        tiny_glm.NAME, "train_s8192_b4", 1)
    assert {"attn.latent_flash_roofline", "attn.latent_time_share",
            "ffn.gated_time_share", "moe.experts_roofline", "moe.time_share",
            "moe.load_max_over_mean", "moe.dropped_assignments",
            "moe.rows_run_over_held", "moe.choice_passes_mean",
            "device.step_mfu", "device.peak_hbm_gb"} <= set(readers)
    assert "attn.window_flash_roofline" not in readers
    assert "flash_attn_roofline" not in readers
    # what the moe.* readers take from the configuration
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            len(cfg["held_experts"])) == (2048, 1536, 8)
    assert set(limits) <= set(compare.NUMBERS)


#: the tiny GLM cell's limits on the CPU, between the program in bf16 (at
#: most, 3 seeds) and the fp8 control (at least, 3 seeds): losses 2.7e-5 /
#: 9.4e-5, grad_norm_worst_leaf 9.8e-3 / 2.2e-2, grad_error_median_leaf
#: 5.0e-3 / 5.4e-2; grad_error_worst_leaf is not held (0.111 / 0.134: a
#: top-2 choice that flips between bf16 and float32 lands on one expert leaf)
LIMITS = {"loss_step1": 6e-5, "loss_step2": 6e-5, "loss_step3": 6e-5,
          "grad_norm_worst_leaf": 1.5e-2, "grad_error_median_leaf": 2e-2,
          "change_norm_worst_leaf": 0.5}


def test_sound_run_of_a_tiny_glm_cell_is_correct():
    cfg, traffic = tiny_glm.glm()
    result = train.run({"name": "tiny_glm", "chips": 4}, cfg, traffic,
                       LIMITS, {}, seed=2 ** 31 + 13, seconds=0.5,
                       trace=False, t_process=time.perf_counter(),
                       require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_fp8_control_and_half_a_batch_of_a_tiny_glm_cell_are_not_correct():
    cfg, traffic = tiny_glm.glm()
    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    for seed in (21, 22):
        rng = np.random.default_rng(seed)
        batches = [model_lib.features(cfg, traffic, rng, traffic["batch"])
                   for _ in range(train.VERIFY_STEPS)]
        want = reference_run.three_steps(ref, cfg, seed, batches, 1)
        for fault in (dict(mode="fp8"), dict(keep_rows=0.5)):
            side = reference_run.three_steps(ref, cfg, seed, batches, 1,
                                             **fault)
            ok, rows = compare.compare(side, want, LIMITS)
            failed = {r["name"] for r in rows if not r["ok"]}
            assert not ok and "grad_error_median_leaf" in failed, rows
