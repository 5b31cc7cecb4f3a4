"""What the reference side of ``correct`` keeps on the device, and that
keeping less changed none of its numbers.

``lib/reference_run.py::three_steps`` states its bytes a parameter
(``BYTES_PER_PARAMETER_IN_BLOCKS``: weights, the summed gradient and the
block being added, 4 less where a step is one block;
``BYTES_PER_PARAMETER_IN_STEP``: weights, gradient, Adam's two moments). The
probe here sums the buffers that ``jax.live_arrays()`` hold at the points
``three_steps`` calls it, its fullest. The literals are the readings
of ``three_steps`` as it was before it kept less (PR 31's tree), on the
same seed, and ``tools/size.py``'s count is the decoder configuration's
340.3 M parameters of PERF.md section 4.
"""

import gc
import importlib.util
import json
import math
import os

import numpy as np
import pytest

from benchmark.kinds import train
from benchmark.lib import reference_run
from benchmark.tests import test_decoder, tiny

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 41
#: what a probe finds beside parameters and a block's inputs: the key, the
#: step counter, the block's loss
SLACK_BYTES = 16 * 1024

CELLS = {"gpt": tiny.gpt, "decoder": test_decoder.tiny}
BEFORE = {
    "gpt": {
        "loss": [7.6528120040893555, 7.659448146820068, 7.640192985534668],
        "grad_total": 0.5695269479467812, "change_total": 0.4487879742732545,
        "grad": {"['block0']['attn']['proj']['W']": 0.003647695994004607,
                 "['block1']['attn']['qkv']['W']": 0.0037612824235111475,
                 "['wte']": 0.25174373388290405},
        "change": {"['block0']['attn']['proj']['W']": 0.06245065852999687,
                   "['block1']['attn']['qkv']['W']": 0.10782121866941452,
                   "['wte']": 0.18697503209114075}},
    "decoder": {
        "loss": [4.851214408874512, 4.846381664276123, 4.827338218688965],
        "grad_total": 3.4270181326324036,
        "change_total": 0.0008041235313269349,
        "grad": {"['block0']['attn']['Wk']": 0.010724008083343506,
                 "['block2']['attn']['Wo']": 0.8107895851135254,
                 "['wte']": 1.6799423694610596},
        "change": {"['block0']['attn']['Wk']": 0.00010343042958993465,
                   "['block2']['attn']['Wo']": 0.00015049547073431313,
                   "['wte']": 0.00016369127843063325}},
}


def _live_bytes():
    """Bytes of the buffers alive on the first device, each counted once:
    ``jax.device_get`` leaves a second array that holds the same buffer
    among the live ones, which is no second copy."""
    import jax
    first, seen = jax.devices()[0], {}
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            if shard.device == first:
                seen[shard.data.unsafe_buffer_pointer()] = shard.data.nbytes
    return sum(seen.values())


def _probed_steps(name, rows_per_chip):
    """``three_steps`` of a tiny cell under the probe: its record, the most
    bytes the probe found alive at each kind of point beyond what was alive
    before, the bytes of one block's inputs and the blocks a step."""
    import jax
    cfg, traffic = CELLS[name]()
    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    rng = np.random.default_rng(SEED)
    batches = [model_lib.features(cfg, traffic, rng, traffic["batch"])
               for _ in range(train.VERIFY_STEPS)]
    gc.collect()
    before, seen = _live_bytes(), []
    out = reference_run.three_steps(
        ref, cfg, SEED, batches, rows_per_chip,
        probe=lambda label: seen.append((label, _live_bytes() - before)))
    inputs = sum(np.asarray(a)[:rows_per_chip].nbytes
                 for a in jax.tree.leaves(batches[0]))
    blocks = traffic["batch"] // (rows_per_chip * len(jax.devices()))
    assert [label for label, _ in seen] == (
        ["block"] * blocks + ["step"]) * train.VERIFY_STEPS
    most = {label: max(b for at, b in seen if at == label)
            for label in ("block", "step")}
    return out, most, inputs, blocks


@pytest.mark.parametrize("name,rows_per_chip", [
    ("gpt", 1), ("decoder", 1), ("gpt", 2)],
    ids=["gpt", "decoder", "gpt_one_block_a_step"])
def test_three_steps_keeps_its_stated_bytes_a_parameter(name, rows_per_chip):
    out, most, inputs, blocks = _probed_steps(name, rows_per_chip)
    n = out["parameters"]
    in_blocks, in_step = (reference_run.BYTES_PER_PARAMETER_IN_BLOCKS,
                          reference_run.BYTES_PER_PARAMETER_IN_STEP)
    assert (in_blocks, in_step) == (12, 16)
    if blocks == 1:
        in_blocks -= 4          # no sum for the block's gradient to join
    assert most["block"] <= in_blocks * n + inputs + SLACK_BYTES, (
        most["block"] / n)
    assert most["step"] <= in_step * n + SLACK_BYTES, most["step"] / n
    # bounds that bind: the stated trees were all alive at once
    assert most["block"] >= in_blocks * n and most["step"] >= in_step * n
    # the CPU counts no bytes in use: nothing is claimed for it
    assert out["device_bytes"] is None


@pytest.mark.parametrize("name", ["gpt", "decoder"])
def test_three_steps_reads_what_it_read_before(name):
    import jax
    out, _, _, _ = _probed_steps(name, 1)
    want = BEFORE[name]
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(out["grad_tree"])), (
        "the first gradient is handed out on the host")
    assert out["loss"] == pytest.approx(want["loss"], rel=1e-6)
    for side in ("grad", "change"):
        total = math.sqrt(sum(v * v for v in out[side].values()))
        assert total == pytest.approx(want[side + "_total"], rel=1e-6)
        for leaf, norm in want[side].items():
            assert out[side][leaf] == pytest.approx(norm, rel=1e-6), leaf
    # the tree handed out is the gradient whose norms were taken
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            out["grad_tree"])[0]:
        norm = float(np.sqrt(np.sum(np.square(leaf, dtype=np.float64))))
        assert norm == pytest.approx(
            out["grad"][jax.tree_util.keystr(path)], rel=1e-5)


def test_size_tool_counts_the_decoder_configuration():
    spec = importlib.util.spec_from_file_location(
        "bench_tools_size", os.path.join(HERE, "tools", "size.py"))
    size = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(size)
    cfg = size.read_config("Mellum2-12B-A2.5B-Instruct")
    with open(os.path.join(HERE, "traffic", "train_s8192_b4.json")) as f:
        traffic = json.load(f)
    s = size.size(cfg, traffic)
    assert s["parameters"] == 340_349_184            # 340.3 M
    assert round(s["parameters"] / 1e6, 1) == 340.3
    assert s["reference_blocks_a_step"] == 4
    assert s["reference_bytes_in_blocks"] == 12 * s["parameters"]
    assert s["reference_bytes_in_step"] == 16 * s["parameters"]
    assert s["program_bytes"] == 16 * s["parameters"]
