"""The decoder configuration's part of the yardstick, checked as
``selfcheck.py`` checks the rest: ``lib/kernel_cost_decoder.py`` against the
figures worked by hand in its docstring, ``lib/scopes.py`` on a few lines of
HLO text, and a whole run of the harness on a tiny decoder cell (sound run
correct, fp8 control not)."""

import copy
import json
import os
import time

import numpy as np

from benchmark.kinds import train
from benchmark.lib import compare, kernel_cost, reference_run, scopes
from benchmark.lib import kernel_cost_decoder as cost

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "Mellum2-12B-A2.5B-Instruct"
SHAPE = dict(batch=4, q_heads=32, kv_heads=4, seq=8192, head_dim=128)


def test_visible_pairs_are_counted_exactly():
    assert cost.visible_pairs(8192) == 8192 * 8193 // 2 == 33_558_528
    assert cost.visible_pairs(8192, 1024) == 524_800 + 7_340_032 == 7_864_832
    assert cost.visible_pairs(8192, 1024) == sum(
        min(i + 1, 1024) for i in range(8192))
    assert cost.visible_pairs(512, 1024) == cost.visible_pairs(512)


def test_flash_costs_match_the_hand_worked_figures():
    f, b = cost.flash_call("zoo_flash_fwd", **SHAPE)
    assert f == 2 * 2 * 33_558_528 * 128 * 128 == 2_199_291_691_008
    assert b == 2 * 268_435_456 + 2 * 33_554_432 + 4_194_304 == 608_174_080
    f_win, b_win = cost.flash_call("zoo_flash_fwd", window=1024, **SHAPE)
    assert f_win == 515_429_629_952 and b_win == b
    f_dq, b_dq = cost.flash_call("zoo_flash_bwd_dq", **SHAPE)
    f_dkv, b_dkv = cost.flash_call("zoo_flash_bwd_dkv", **SHAPE)
    assert (f_dq, f_dkv) == (1.5 * f, 2.0 * f)
    assert b_dq == 3 * 268_435_456 + 2 * 33_554_432 + 2 * 4_194_304 \
        == 880_803_840
    assert b_dkv == 2 * 268_435_456 + 4 * 33_554_432 + 2 * 4_194_304 \
        == 679_477_248
    # with one head count and no window it is kernel_cost.flash_call's
    # operations up to the diagonal's half row (T (T + 1) / 2, not T^2 / 2)
    f_old, _ = kernel_cost.flash_call("zoo_flash_fwd", batch_heads=128,
                                      seq=8192, head_dim=128, causal=True)
    assert abs(f / f_old - 8193 / 8192) < 1e-12


def test_expert_costs_match_the_hand_worked_figures():
    f, b = cost.grouped_product(32768, 2304, 896, 8)
    assert f == 2 * 32768 * 2304 * 896 == 135_291_469_824
    assert b == 33_030_144 + 209_715_200 == 242_745_344
    nine = cost.expert_products(32768, 2304, 896, 8)
    assert len(nine) == 9 and sum(f for f, _ in nine) == 1_217_623_228_416
    # dW is written in float32: the weights' bytes double, the rows' do not
    assert nine[2][1] - nine[0][1] == 33_030_144
    # no rows, no operations: padding cannot earn a share
    assert cost.grouped_product(0, 2304, 896, 8)[0] == 0.0


HLO = """HloModule step

%fused_computation.1 (p: bf16[64,8]) -> bf16[64,8] {
  ROOT %multiply.1 = bf16[64,8]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/zoo_moe.experts/mul"}
}

%body.2 (t: (s32[], bf16[64,8])) -> (s32[], bf16[64,8]) {
  %fusion.7 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/zoo_moe.experts/mul"}
  %ragged-dot-none.1 = bf16[64,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}

ENTRY %main.3 (x: bf16[64,8]) -> bf16[64,8] {
  %fusion.8 = bf16[64,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/zoo_attn.rope/mul"}
  %fusion.9 = f32[64]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/zoo_moe.route/softmax"}
  ROOT %copy.4 = bf16[64,8]{1,0} copy(%fusion.8), metadata={op_name="jit(step)/zoo_moe.combine/add"}
}
"""


def test_scope_seconds_reads_scopes_from_the_step_text():
    trace = {"op_seconds": {"fusion bf16[64,8]": 4.0, "fusion f32[64]": 1.0,
                            "copy bf16[64,8]": 0.5,
                            "ragged-dot-none bf16[64,8]": 10.0,
                            "multiply bf16[64,8]": 100.0}}
    # two fusions share a key, one of them under the scope: half its time;
    # a fused computation's own instruction is no event
    assert scopes.scope_shares(HLO, "zoo_moe.experts") == {
        "fusion bf16[64,8]": 0.5}
    assert scopes.scope_seconds(trace, HLO, "zoo_moe.experts") == 2.0
    assert scopes.scope_seconds(trace, HLO, "zoo_moe.experts",
                                cost.EXPERT_KERNELS) == 12.0
    assert scopes.scope_seconds(trace, HLO, "zoo_moe.",
                                cost.EXPERT_KERNELS) == 2.0 + 1.0 + 0.5 + 10.0
    assert scopes.scope_seconds(trace, HLO, "zoo_nothing") == 0.0


def tiny():
    with open(os.path.join(HERE, "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, router_width=8, num_experts=4,
               held_experts=[0, 1, 2, 3], num_experts_per_tok=2,
               moe_intermediate_size=24, sliding_window=8, vocab_size=128,
               max_position_embeddings=32)
    cfg["rope_parameters"] = copy.deepcopy(cfg["rope_parameters"])
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    cfg["assumed"] = dict(cfg["assumed"], moe_token_chunk=64,
                          router_init_classes=4)
    traffic = {"kind": "train", "seq": 32, "batch": 8, "chips": 4,
               "epoch_steps": 4000, "reference_rows_per_chip": 1,
               "token_ids": "zipf", "zipf_s": 1.0}
    return cfg, traffic


#: the tiny decoder cell's limits on the CPU, between the program in bf16
#: (at most, 3 seeds) and the fp8 control (at least, 3 seeds):
#: grad_norm_worst_leaf 8.0e-3 / 4.2e-2, grad_error_worst_leaf 0.10 / 0.29,
#: grad_error_median_leaf 6.0e-3 / 6.1e-2
LIMITS = {"grad_norm_worst_leaf": 2e-2, "grad_error_worst_leaf": 0.18,
          "grad_error_median_leaf": 2e-2, "change_norm_worst_leaf": 0.5}


def test_sound_run_of_a_tiny_decoder_cell_is_correct():
    cfg, traffic = tiny()
    result = train.run({"name": "tiny_decoder", "chips": 4}, cfg, traffic,
                       LIMITS, {}, seed=2 ** 31 + 13, seconds=0.5,
                       trace=False, t_process=time.perf_counter(),
                       require_chip=False)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_fp8_control_of_a_tiny_decoder_cell_is_not_correct():
    cfg, traffic = tiny()
    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    for seed in (21, 22):
        rng = np.random.default_rng(seed)
        batches = [model_lib.features(cfg, traffic, rng, traffic["batch"])
                   for _ in range(train.VERIFY_STEPS)]
        want = reference_run.three_steps(ref, cfg, seed, batches, 1)
        control = reference_run.three_steps(ref, cfg, seed, batches, 1,
                                            mode="fp8")
        ok, rows = compare.compare(control, want, LIMITS)
        assert not ok, rows
