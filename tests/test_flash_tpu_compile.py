"""The flash kernels, and the experts' grouped products, compiled FOR the
chip from here: Mosaic's layout and scoped-VMEM refusals (what interpret
mode cannot show) without a chip.

The TPU's compiler is installed in this sandbox and compiles for a chip
that is described, not attached. Only one process may hold the TPU
library, so the topology is described inside a fixture (never at import)
and every such compile lives in this one file: under xdist's
``--dist loadfile`` one worker gets it.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

# the package __init__ rebinds `flash_attention` to the function
fa = importlib.import_module("analytics_zoo_tpu.ops.pallas.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_grads(one_chip, b, h, t_q, t_kv, d, dtype, causal, masked,
                   window=None, group=1):
    q = jax.ShapeDtypeStruct((b, t_q, h, d), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, t_kv, h // group, d), dtype,
                             sharding=one_chip)
    keep = jax.ShapeDtypeStruct((b, t_kv), jnp.float32, sharding=one_chip)

    # the kernels themselves at the schedule the public entry resolves:
    # the entry's per-data-shard wrapper would take the test process's
    # CPU mesh
    sched = fa._auto_blocks(q.shape, t_kv, dtype, causal, masked, False,
                            window, group)

    def grads(q, k, v, keep):
        return jax.grad(lambda q, k, v: jnp.sum(fa._flash(
            q, k, v, keep if masked else None, causal, sched,
            False, window).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    return jax.jit(grads).lower(q, k, k, keep).compile()


@pytest.mark.parametrize("b,h,t_q,t_kv,d,dtype,causal,masked", [
    # the benchmark's GPT-1 cell: whole sequence resident, tile (512, 512),
    # two heads a cell (as every D = 64 case below with an even head count)
    (8, 12, 4096, 4096, 64, jnp.bfloat16, True, False),
    # long context: several major windows, clamped causal index maps
    (1, 12, 32768, 32768, 64, jnp.bfloat16, True, False),
    # BERT-style key padding at the routing threshold and above
    (2, 12, 2048, 2048, 64, jnp.bfloat16, False, True),
    (2, 12, 4096, 4096, 64, jnp.bfloat16, True, True),
    # unaligned T, cross lengths, wide heads, f32 operands
    (2, 4, 1000, 1000, 64, jnp.bfloat16, True, False),
    (2, 4, 2048, 4096, 128, jnp.bfloat16, True, False),
    (2, 4, 4096, 2048, 64, jnp.float32, True, False),
    (1, 2, 8192, 8192, 256, jnp.float32, False, False),
    # a head width that is no lane tile nor half of one, and an odd count
    # of half-tile heads: the wrapper splits the heads out
    (1, 2, 40, 40, 8, jnp.float32, True, False),
    (2, 3, 2048, 2048, 64, jnp.bfloat16, True, False),
])
def test_flash_kernels_compile_for_v5e(one_chip, b, h, t_q, t_kv, d, dtype,
                                       causal, masked):
    text = _compile_grads(one_chip, b, h, t_q, t_kv, d, dtype, causal,
                          masked).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    # three kernels, each under the name the benchmark's roofline reads
    # it by (the op's OWN name, left of the "=")
    names = sorted(ln.split("=")[0].strip() for ln in calls)
    assert len(names) == 3, names
    for marker in ("zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"):
        assert sum(marker in n for n in names) == 1, (marker, names)
    # the forward's saved statistic is one float a row: no f32 result of
    # any kernel is 128 wide per row
    assert not re.search(r"f32\[\d+,%d,128\]" % t_q, "\n".join(calls))


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_grouped_window_flash_compiles_for_v5e(one_chip, window):
    """The two signatures of the decoder configuration's step: 32 query /
    4 key-value heads of 128 at T = 8192, a 1024-key window and full. K/V
    come in at 4 heads and dk/dv go out at 4, side by side in one
    (T, 4 x 128) plane as the projections wrote them: nothing is repeated
    and nothing transposed."""
    text = _compile_grads(one_chip, 1, 32, 8192, 8192, 128, jnp.bfloat16,
                          True, False, window=window, group=8).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    names = sorted(ln.split("=")[0].strip() for ln in calls)
    assert len(names) == 3, names
    suffix = "_win" if window else ""
    for marker in ("zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"):
        assert sum(marker + suffix in n for n in names) == 1, (marker, names)
        if not window:
            assert not any("_win" in n for n in names), names
    dkv = next(ln for ln in calls if "zoo_flash_bwd_dkv" in ln)
    assert "bf16[1,8192,512]" in dkv.split("custom-call(")[0], dkv[:200]
    assert "bf16[1,8192,4096]" not in dkv.split("custom-call(")[0]


def test_grouped_flash_at_64_compiles_for_v5e(one_chip):
    """The LFM2 configuration's signature: 32 query / 8 key-value heads of
    64 at T = 8192, no window: GPT-1's head width with grouped heads, a
    pair no other cell has. dk/dv go out at 8 heads, in place as four
    pairs of them."""
    text = _compile_grads(one_chip, 1, 32, 8192, 8192, 64, jnp.bfloat16,
                          True, False, group=4).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    names = sorted(ln.split("=")[0].strip() for ln in calls)
    assert len(names) == 3 and not any("_win" in n for n in names), names
    dkv = next(ln for ln in calls if "zoo_flash_bwd_dkv" in ln)
    assert "bf16[1,8192,512]" in dkv.split("custom-call(")[0], dkv[:200]
    assert "bf16[1,8192,2048]" not in dkv.split("custom-call(")[0]


def test_a_short_conv_block_keeps_its_chain_in_the_compute_dtype(one_chip):
    """A rematerialised ``x + ShortConvMixer(RMSNorm(x))`` at the LFM2
    cell's shapes, forward and backward, compiled by the chip's compiler:
    no Mosaic call, and the in_proj's (B, T, 3 H) output never stands in
    float32 (with ``v = B * u`` taken in float32 it did: 805 MB a layer
    where 403 do; PR 36)."""
    from analytics_zoo_tpu.common.context import reset_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras import set_policy
    from analytics_zoo_tpu.pipeline.api.keras.layers import (RMSNorm,
                                                             ShortConvMixer)
    set_policy(compute_dtype="bfloat16", param_dtype="float32")
    try:
        mixer, norm = ShortConvMixer(2048), RMSNorm(1e-5)
        shapes = jax.eval_shape(
            lambda k: {"mixer": mixer.build(k, (None, 8192, 2048)),
                       "norm": norm.build(k, (None, 8192, 2048))},
            jax.random.key(0))
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), shapes)
        x = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16,
                                 sharding=one_chip)

        @jax.checkpoint
        def block(p, x):
            return x + mixer.call(p["mixer"], norm.call(p["norm"], x))

        def step(p, x, co):
            y, vjp = jax.vjp(block, p, x)
            return y, vjp(co)
        text = jax.jit(step).lower(params, x, x).compile().as_text()
    finally:
        reset_zoo_context()             # the float32 default again
    assert "tpu_custom_call" not in text
    entry = text[text.index("\nENTRY "):]
    assert "bf16[4,8192,6144]" in entry
    assert not re.search(r"= f32\[4,8192,6144\]", entry), re.findall(
        r"\S+ = f32\[4,8192,6144\]\S* \w+", entry)[:3]


def test_latent_attention_flash_compiles_for_v5e_with_the_sequence_resident(
        one_chip):
    """The GLM configuration's signature: 20 query and 20 key heads of 256
    at T = 8192. A head wider than a lane tile is fitted into two VMEM
    budgets (``common.attention_budget_scale``), so the tile stays
    (512, 512) and the whole sequence resident, as at D = 64 and 128; the
    calls ask Mosaic for their own limit and the chip's compiler takes
    them."""
    sched = fa._auto_blocks((1, 8192, 20, 256), 8192, jnp.bfloat16, True,
                            False, False)
    assert fa._choice_label(sched) == (
        "fwd=512x1024/kmajor8192,dq=512x512/kmajor8192,"
        "dkv=512x512/qmajor8192")
    for d in (64, 128):     # the accepted cells' choices stand
        assert fa._choice_label(fa._auto_blocks(
            (1, 8192, 12, d), 8192, jnp.bfloat16, True, False, False)) == (
            "fwd=512x1024/kmajor8192,dq=512x512/kmajor8192,"
            "dkv=512x512/qmajor8192")
    text = _compile_grads(one_chip, 1, 20, 8192, 8192, 256, jnp.bfloat16,
                          True, False).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    names = sorted(ln.split("=")[0].strip() for ln in calls)
    assert len(names) == 3, names
    for marker in ("zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"):
        assert sum(marker in n for n in names) == 1, (marker, names)


def _attention_layers():
    """name -> (layer, input shape, layout its flash call takes): one
    attention layer of each configuration that trains through the
    kernels, at its cell's shapes."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        DecoderAttention, LatentAttention, MultiHeadSelfAttention)
    rot = {"rope_theta": 10000.0}
    return {
        "gpt1": (MultiHeadSelfAttention(768, 12, causal=True),
                 (8, 4096, 768), "pair"),
        "gpt1_s2048_b16": (MultiHeadSelfAttention(768, 12, causal=True),
                           (16, 2048, 768), "pair"),
        "mellum_window": (DecoderAttention(2304, 32, 4, 128, rot,
                                           window=1024),
                          (4, 8192, 2304), "inplace"),
        "mellum_full": (DecoderAttention(2304, 32, 4, 128, rot),
                        (4, 8192, 2304), "inplace"),
        "glm_latent": (LatentAttention(2048, 20, 768, 512, 192, 64, 256,
                                       rot), (4, 8192, 2048), "inplace"),
        "lfm2_qk_norm": (DecoderAttention(2048, 32, 8, 64, rot,
                                          qk_norm=True),
                         (4, 8192, 2048), "pair"),
    }


@pytest.mark.parametrize("name", ["gpt1", "gpt1_s2048_b16", "mellum_window",
                                  "mellum_full", "glm_latent",
                                  "lfm2_qk_norm"])
def test_an_attention_layer_compiles_with_no_copy_of_a_head_shaped_tensor(
        one_chip, monkeypatch, name):
    """One attention layer, forward and backward through its own ``call``,
    compiled by the chip's compiler at its cell's shapes: beside the three
    kernels the program holds NO ``copy`` of an activation (XLA used to
    put eleven round a GPT-1 layer, twelve round a latent one: every
    change of layout between the projections' (B, T, H*D) and the
    kernels' (B*H, T, D)). The layers keep q, k and v as (B, T, H*D) all
    the way: a (B, T, H, D) view is another TILING of the same bytes on a
    TPU, so the rotary pass runs in place too. The one layer that still
    pays is LFM2's: its q/k norm needs a head's 64 columns as an axis of
    their own, and XLA puts a float32 copy on either side of that view,
    four of q's size and four of k's (on the chip the cell still reads
    0.7 % above the parent's ten copies; PERF.md section 6)."""
    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib
    from analytics_zoo_tpu.pipeline.api.keras import set_policy
    layer, shape, layout = _attention_layers()[name]
    b, t = shape[:2]
    # route as on the chip (`_use_flash` and the entry ask the backend),
    # on a mesh of one device (the context's is the test process's 8 CPUs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    init_zoo_context()
    set_policy(compute_dtype="bfloat16", param_dtype="float32")
    mesh_lib.set_global_mesh(mesh_lib.create_mesh(devices=jax.devices()[:1]))
    try:
        n_kv = getattr(layer, "n_kv_head", layer.n_head)
        d = getattr(layer, "head_dim", None) or (
            layer.qk_nope_dim + layer.qk_rope_dim
            if hasattr(layer, "qk_nope_dim")
            else layer.hidden_size // layer.n_head)
        assert fa._head_layout(layer.n_head, n_kv, d).layout == layout
        shapes = jax.eval_shape(
            lambda k: layer.build(k, (None,) + shape[1:]), jax.random.key(0))
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), shapes)
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

        def step(p, x, co):
            y, vjp = jax.vjp(lambda p, x: layer.call(p, x), p, x)
            return y, vjp(co)
        text = jax.jit(step).lower(params, x, x).compile().as_text()
    finally:
        reset_zoo_context()
        mesh_lib.reset_global_mesh()
    names = [ln.split("=")[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    for marker in ("zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"):
        assert sum(marker in n for n in names) == 1, (marker, names)
    # copies of activations: B first, T among the dimensions, at least a
    # key/value tensor's size (the rotary tables, (T, H*D), are made once
    # a step and shared by the layers of a kind; weights are smaller)
    entry = text[text.index("\nENTRY "):]
    copies = []
    for dtype, dims in re.findall(r"= (\w+)\[([\d,]+)\]\S* copy\(", entry):
        dims = [int(n) for n in dims.split(",")]
        size = 1
        for n in dims:
            size *= n
        if dims[0] == b and t in dims and size >= b * t * n_kv * d:
            copies.append(f"{dtype}{dims}")
    if getattr(layer, "qk_norm", None) is not None:
        assert len(copies) <= 8, copies
    else:
        assert not copies, copies


@pytest.mark.parametrize("policy,forwards", [(False, 2), (True, 1)],
                         ids=["bare_checkpoint", "keeps_flash_saved"])
def test_a_checkpoint_that_keeps_the_named_residuals_compiles_one_forward(
        one_chip, policy, forwards):
    """The GLM cell's call (20 heads of 256 at T = 8192, one row) between
    two projections under ``jax.checkpoint``, compiled by the chip's
    compiler: the program holds the forward kernel twice under a bare
    checkpoint, once where the policy keeps ``FLASH_SAVED``."""
    shape = (1, 8192, 20, 256)
    sched = fa._auto_blocks(shape, 8192, jnp.bfloat16, True, False, False)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16, sharding=one_chip)

    def block(w, x):
        q = x @ w
        return fa._flash(q, q, x, None, True, sched, False, None) @ w
    keep = jax.checkpoint_policies.save_only_these_names(*fa.FLASH_SAVED)
    run = jax.checkpoint(block, policy=keep if policy else None)
    # a loss that needs the block's output, or the first forward is dead
    text = jax.jit(jax.grad(lambda w, x: jnp.sum(jnp.square(
        run(w, x).astype(jnp.float32))))).lower(w, x).compile().as_text()
    names = [ln.split("=")[0].strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert sum("zoo_flash_fwd" in n for n in names) == forwards, names
    assert sum("zoo_flash_bwd" in n for n in names) == 2, names


@pytest.mark.parametrize("rows,groups,d,h,dtype", [
    # the decoder cell's row buffers, cut to the rows held and whole
    (32768, 8, 2304, 896, jnp.bfloat16),
    (131072, 8, 2304, 896, jnp.bfloat16),
    # float32 operands, rows that are no whole tile
    (1000, 4, 512, 256, jnp.float32),
], ids=["cell", "cell_whole_buffers", "float32_ragged_rows"])
def test_grouped_products_compile_for_v5e(one_chip, rows, groups, d, h,
                                          dtype):
    """A routed layer's step through the entry's custom VJPs on the kernels'
    path: six Mosaic calls under the names the benchmark's readers know
    (``zoo_moe_gmm*``), ``dW`` in float32, no ``ragged-dot``."""
    from analytics_zoo_tpu.ops import grouped_matmul as gm

    x = jax.ShapeDtypeStruct((rows, d), dtype, sharding=one_chip)
    up = jax.ShapeDtypeStruct((groups, d, h), dtype, sharding=one_chip)
    down = jax.ShapeDtypeStruct((groups, h, d), dtype, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)

    def step(x, wgate, wup, wdown, sizes):
        def f(x, wgate, wup, wdown):
            act = gm.gated_product(x, wgate, wup, sizes, "pallas")
            y = gm.product(act, wdown, sizes, "pallas")
            return jnp.sum(y.astype(jnp.float32)), y
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(
            x, wgate, wup, wdown)

    text = jax.jit(step).lower(x, up, up, down, sizes).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    names = sorted(re.sub(r"\.\d+$", "", ln.split("=")[0].strip()
                          .lstrip("%")) for ln in calls)
    assert sorted(n.split("zoo_moe_")[-1].rstrip("_") for n in names) == [
        "gmm", "gmm_dw", "gmm_dw", "gmm_dx", "gmm_dx_gated", "gmm_gated",
        ], names
    assert "ragged-dot" not in text
    for ln in calls:
        if "zoo_moe_gmm_dw" in ln:
            assert f"f32[{groups}," in ln.split("custom-call(")[0], ln[:200]
