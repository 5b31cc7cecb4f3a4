"""chip_smoke.py's phase bodies at a tiny size on the 8-device CPU mesh
(kernels interpreted), and its refusal to pass on anything but a TPU."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_device_phase_names_the_platform_it_found():
    with pytest.raises(chip_smoke.WrongPlatform, match="'cpu'"):
        chip_smoke.phase_device("tpu")
    found = chip_smoke.phase_device(None)
    assert found["platform"] == "cpu" and found["count"] == 8
    assert found["peak_bf16_flops"] is None


def test_main_exits_nonzero_on_cpu_and_prints_no_result(capsys):
    assert chip_smoke.main() == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "platform='cpu'" in err and "needs platform 'tpu'" in err


def test_last_stdout_line_is_the_verdict_with_exactly_its_keys(capsys):
    """The driver's check reads the last line by its exact keys; everything
    else (versions, per-phase numbers) rides the line before it."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "peak_bf16_flops": 197e12}
    results = {"device": {"outcome": "ok", **device},
               "train": {"outcome": "ok", "wall_s": 1.0, "compile_s": 0.5}}
    assert chip_smoke.print_result(results, device) is True
    report, verdict = capsys.readouterr().out.splitlines()
    assert json.loads(verdict) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    tag = "[chip_smoke] report: "
    assert report.startswith(tag)
    body = json.loads(report[len(tag):])
    assert set(body) == {"versions", "compile_cache_dir", "native", "phases"}
    assert body["phases"]["train"]["compile_s"] == 0.5

    results["serve"] = {"outcome": "failed", "error": "boom"}
    assert chip_smoke.print_result(results, device) is False
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"] is False


def test_train_phase_tiny():
    out = chip_smoke.phase_train(seq_len=16, batch=8, n_examples=32,
                                 n_block=1, hidden=32, n_head=2, ffn=64,
                                 vocab=128, lr=1e-3)
    assert out["compiles_first_fit"] >= 1
    assert out["compiles_second_fit"] == 0
    assert out["losses"][-1] < out["losses"][0]
    assert out["mesh"] == {"data": 8}


def test_train_phase_model_sharded():
    out = chip_smoke.phase_train(seq_len=16, batch=8, n_examples=16,
                                 n_block=1, hidden=32, n_head=2, ffn=64,
                                 vocab=128, lr=1e-3,
                                 mesh={"data": 4, "model": 2},
                                 expect_model_sharded=True)
    assert out["mesh"] == {"data": 4, "model": 2}
    assert "model" in out["qkv_spec"]
    # the step pins its outputs to the declared shardings: no second,
    # sharding-only compilation of the same step
    assert out["compiles_first_fit"] == 1


def test_kernels_phase_tiny_interpreted():
    """The kernels are forced on (``auto`` means TPU only) and run in
    interpret mode, so the lowered step holds no Mosaic call; the phase
    still proves routing (the step's jaxpr), a falling loss and every
    reference comparison. V=1300 is not a multiple of 128: the padded-column path."""
    out = chip_smoke.phase_kernels(
        seq_len=128, batch=8, n_seqs=16, n_block=1, hidden=32, n_head=2,
        vocab=1300, lr=3e-3, epochs=3, require_mosaic=False, ce_rows=64,
        embed_shape=(128, 20, 64), int8_shape=(16, 32, 200),
        conf={"zoo.pallas.attention": True,
              "zoo.pallas.cross_entropy": True})
    assert out["mosaic_calls"] == {}
    assert set(out["pallas_calls"]) >= set(chip_smoke.STEP_KERNELS)
    assert set(out["reference_errors"]) >= {
        "flash_causal_dq", "flash_padded_dv", "ce_dw", "ce_db",
        "embed_expand_float32", "int8_matmul"}


def test_decoder_phase_tiny_interpreted():
    """Window / grouped-head flash (T not a multiple of the tile) and the
    grouped product, each against its float32 reference."""
    out = chip_smoke.phase_decoder(
        n_head=4, n_kv_head=2, seq_len=200, head_dim=16, window=24,
        gmm_shape=(96, 3, 16, 8), require_mosaic=False)
    assert set(out["reference_errors"]) == {
        f"flash_gqa_{tag}_{name}" for tag in ("window", "full")
        for name in ("out", "dq", "dk", "dv")} | {
        "grouped_matmul_out", "grouped_matmul_dx", "grouped_matmul_dw",
        "grouped_matmul_gated_out", "grouped_matmul_gated_dx",
        "grouped_matmul_gated_dwgate", "grouped_matmul_gated_dwup"}


def test_kernels_phase_fails_without_mosaic_calls():
    with pytest.raises(AssertionError, match="lacks Mosaic calls"):
        chip_smoke.phase_kernels(
            seq_len=128, batch=8, n_seqs=8, n_block=1, hidden=32, n_head=2,
            vocab=1300, lr=3e-3, epochs=2, require_mosaic=True, ce_rows=64,
            conf={"zoo.pallas.attention": True,
                  "zoo.pallas.cross_entropy": True})


def test_mosaic_kernel_names_reads_lowered_text():
    text = ('%3:2 = stablehlo.custom_call @tpu_custom_call(%0) {backend_config'
            ' = "{}", kernel_name = "zoo_flash_fwd"} : () -> ()\n'
            '%4 = stablehlo.custom_call @Sharding(%3) : () -> ()\n'
            '%5 = stablehlo.custom_call @tpu_custom_call(%4) {kernel_name = '
            '"zoo_flash_fwd"} : () -> ()\n')
    assert chip_smoke.mosaic_kernel_names(text) == {"zoo_flash_fwd": 2}


def test_serve_phase_tiny():
    out = chip_smoke.phase_serve(hw=32, n_frames=12, batch_size=4, classes=10,
                                 answer_timeout_s=240.0)
    assert out["records"] == 12


def test_kernels_phase_fails_when_the_routers_choose_xla():
    """Every conf key at its default on the CPU: ``auto`` keeps both
    kernels off, and the phase says so instead of passing."""
    with pytest.raises(AssertionError, match="routers chose the XLA path"):
        chip_smoke.phase_kernels(
            seq_len=128, batch=8, n_seqs=8, n_block=1, hidden=32, n_head=2,
            vocab=1300, lr=3e-3, epochs=2, require_mosaic=False, ce_rows=64)
