"""A rematerialised decoder block keeps its flash call's output and row
statistics (PR 35): the kernel's forward rule names them
(``flash_attention.FLASH_SAVED``), ``DecoderStack(remat=True)``'s block
checkpoints keep exactly those names, and the backward pass recomputes the
projections but does not run the flash forward a second time. On the CPU,
the kernels under the interpreter (``zoo.pallas.attention`` on)."""

import collections
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.common.context import reset_zoo_context
from analytics_zoo_tpu.feature import FeatureSet
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import (
    DecoderStack, Dense, GatedFeedForward, LatentAttention)
from analytics_zoo_tpu.pipeline.api.keras.layers.self_attention import (
    remat_saved_bytes)

# the package rebinds ``ops.pallas.flash_attention`` to the function
F = importlib.import_module("analytics_zoo_tpu.ops.pallas.flash_attention")

ROTARY = {"rope_type": "default", "rope_theta": 1000000}
VOCAB, HIDDEN, B, T = 50, 32, 2, 40
#: attention -> (heads, width of a head's output): what one block keeps is
#: B x heads x T x width in the compute dtype, and B x heads x T floats
KINDS = {"grouped_window": (4, 8), "latent": (4, 16)}


@pytest.fixture
def flash(request):
    reset_zoo_context()
    init_zoo_context(conf={"zoo.pallas.attention":
                           getattr(request, "param", True)})
    yield getattr(request, "param", True)
    reset_zoo_context()


def _stack(kind, remat):
    if kind == "latent":
        how = dict(layer_types=["full_attention"] * 2,
                   attn=lambda i: LatentAttention(
                       HIDDEN, 4, q_lora_rank=24, kv_lora_rank=16,
                       qk_nope_dim=12, qk_rope_dim=4, v_dim=16,
                       rotary=ROTARY))
    else:
        how = dict(layer_types=["sliding_attention", "full_attention"],
                   n_head=4, n_kv_head=2, head_dim=8, sliding_window=16,
                   rope_parameters=ROTARY)
    return DecoderStack(vocab=VOCAB, hidden_size=HIDDEN, remat=remat,
                        ffn=lambda i: GatedFeedForward(48),
                        input_shape=(T,), **how)


def _ids(seed=0, rows=B):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (rows, T)), jnp.int32)


def _loss(stack, ids, training=True):
    def loss(p):
        h, _ = stack.apply(p, {}, ids, training=training)
        return jnp.mean(jnp.square(h.astype(jnp.float32)))
    return loss


def _kernels(jaxpr):
    """Flash kernels in a jaxpr's text, by direction: window calls count as
    calls of their kernel."""
    names = re.findall(r"name=(zoo_flash_(?:fwd|bwd_dq|bwd_dkv))", str(jaxpr))
    return collections.Counter(names)


def _kept(kind, blocks=2):
    heads, width = KINDS[kind]
    rows = B * heads * T
    return {"flash_out": blocks * rows * width * 4,
            "flash_lse": blocks * rows * 4}


@pytest.mark.parametrize("kind", list(KINDS))
def test_one_flash_forward_a_rematerialised_block(flash, kind):
    """The gradient of a two-block stack holds one forward, one dq and one
    dkv call a block, with ``remat`` as without; the gauge reads what the
    two checkpoints keep."""
    ids = _ids()
    for remat in (False, True):
        stack = _stack(kind, remat)
        params = stack.build(jax.random.key(0), (None, T))
        remat_saved_bytes({})
        calls = _kernels(jax.make_jaxpr(jax.grad(_loss(stack, ids)))(params))
        assert calls == {"zoo_flash_fwd": 2, "zoo_flash_bwd_dq": 2,
                         "zoo_flash_bwd_dkv": 2}, (remat, calls)
        assert remat_saved_bytes() == (
            _kept(kind) if remat else {"flash_out": 0, "flash_lse": 0})


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_bare_checkpoint_of_the_same_block_runs_the_forward_twice(flash,
                                                                    kind):
    """What the policy is for: the same block under ``jax.checkpoint``
    alone recomputes ``out`` and ``lse`` with everything else, and they can
    only come from the forward kernel."""
    stack = _stack(kind, False)
    params = stack.build(jax.random.key(0), (None, T))
    blk, p = stack.blocks[1], params["block1"]
    tab = blk.attn.tables(T)
    h = jax.random.normal(jax.random.key(1), (B, T, HIDDEN))

    def run(p, h):
        return blk.apply(p, {}, [h, tab], training=True)[0]

    def forwards(fn):
        return _kernels(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(fn(p, h) ** 2)))(p))["zoo_flash_fwd"]
    keep = jax.checkpoint_policies.save_only_these_names(*F.FLASH_SAVED)
    assert forwards(run) == 1
    assert forwards(jax.checkpoint(run)) == 2
    assert forwards(jax.checkpoint(run, policy=keep)) == 1
    # a name put on the layer's output from OUTSIDE marks another variable
    # than the residual: the kernel still runs again
    outside = jax.checkpoint(
        lambda p, h: jax.ad_checkpoint.checkpoint_name(run(p, h), "kept"),
        policy=jax.checkpoint_policies.save_only_these_names("kept"))
    assert forwards(outside) == 2


@pytest.mark.parametrize("kind", list(KINDS))
def test_gradients_are_those_of_the_stack_that_keeps_everything(flash, kind):
    """``out`` kept is the ``out`` recomputed, so a rematerialised stack's
    gradient is the plain stack's: bit for bit here, as the bare
    checkpoint's was before the policy (a backend that fuses the
    recomputed projections otherwise may differ in the last place)."""
    ids = _ids()
    grads = {}
    for remat in (False, True):
        stack = _stack(kind, remat)
        params = stack.build(jax.random.key(0), (None, T))
        grads[remat] = jax.jit(jax.value_and_grad(_loss(stack, ids)))(params)
    np.testing.assert_allclose(grads[True][0], grads[False][0], rtol=1e-6)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(grads[True][1]),
            jax.tree.leaves(grads[False][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kind", list(KINDS))
def test_three_steps_through_fit_and_what_the_report_says(flash, kind):
    """Three optimizer steps through ``fit`` with and without ``remat``:
    the same losses, and ``last_fit_report["remat_saved_bytes"]`` is what
    the step's checkpoints keep on a device (nothing without ``remat``),
    also in a second ``fit`` that compiles nothing."""
    rows = 8        # one a device on the tests' mesh of eight
    x = np.asarray(_ids(3, 3 * rows))
    y = np.roll(x, -1, axis=1)
    losses, reports = {}, {}
    for remat in (False, True):
        model = Sequential([_stack(kind, remat), Dense(VOCAB, bias=False)])
        model.compile(optimizer="adam", loss="scce_with_logits", lr=1e-3)
        model.init(jax.random.key(0))
        records = []
        model.fit(FeatureSet.array(x, y, shuffle=False), batch_size=rows,
                  nb_epoch=1, callbacks=[records.append])
        losses[remat] = float(records[-1]["loss"])
        reports[remat] = [model.last_fit_report["remat_saved_bytes"]]
        assert model.last_fit_report["steps"] == 3
        model.fit(FeatureSet.array(x, y, shuffle=False), batch_size=rows,
                  nb_epoch=1)
        assert "train.step" not in model.last_fit_report["compile"]
        reports[remat].append(model.last_fit_report["remat_saved_bytes"])
    assert losses[True] == pytest.approx(losses[False], rel=1e-6)
    # the kernels run once a ``data`` shard, so a device's rows count
    kept = {k: v * (rows // jax.device_count()) // B
            for k, v in _kept(kind).items()}
    assert reports[True] == [kept, kept]
    assert reports[False] == [{"flash_out": 0, "flash_lse": 0}] * 2


@pytest.mark.parametrize("flash", [False], indirect=True)
@pytest.mark.parametrize("kind", list(KINDS))
def test_on_the_xla_op_the_step_is_the_bare_checkpoints(flash, kind,
                                                        monkeypatch):
    """No kernel, no name: the policy finds nothing to keep, the gradient's
    jaxpr is the one a bare ``jax.checkpoint`` gives, and the gauge reads
    0."""
    ids = _ids()

    def text():
        stack = _stack(kind, True)
        params = stack.build(jax.random.key(0), (None, T))
        remat_saved_bytes({})
        jaxpr = str(jax.make_jaxpr(jax.grad(_loss(stack, ids)))(params))
        assert remat_saved_bytes() == {"flash_out": 0, "flash_lse": 0}
        assert "zoo_flash" not in jaxpr and "policy=" in jaxpr
        # the policy's address is the one thing that may differ
        return re.sub(r"policy=[^\n\]]*", "policy=", jaxpr)
    ours = text()
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    assert text() == ours


def test_outside_a_checkpoint_the_names_lower_to_nothing(flash, monkeypatch):
    """A plain ``jax.grad`` through ``flash_attention``: the jaxpr carries
    the two names, the lowered module is the text it is without them: the
    same operations line for line (a name advances the counter behind the
    private functions' symbols, ``@_pad_70`` for ``@_pad_69``, no more)."""
    q, k, v = (jax.random.normal(key, (2, 40, 4, 16))       # (B, T, H, D)
               for key in jax.random.split(jax.random.key(0), 3))

    def both():
        def loss(q, k, v):
            return jnp.sum(F.flash_attention(q, k, v, causal=True) ** 2)
        grad = jax.grad(loss, argnums=(0, 1, 2))
        return (str(jax.make_jaxpr(grad)(q, k, v)),
                re.sub(r"@(\w+?)_\d+\b", r"@\1",
                       jax.jit(grad).lower(q, k, v).as_text()))
    named_jaxpr, named_text = both()
    assert [f"name={n}" in named_jaxpr for n in F.FLASH_SAVED] == [True] * 2
    monkeypatch.setattr(F, "checkpoint_name", lambda x, name: x)
    bare_jaxpr, bare_text = both()
    assert "zoo_flash_out" not in bare_jaxpr
    assert named_text == bare_text


def test_the_log_counts_forward_rules_and_nothing_else(flash):
    """``saved_bytes_log`` adds up the forward rules traced inside it: none
    for a forward alone, none once it is closed."""
    q = jax.random.normal(jax.random.key(0), (2, 40, 4, 16))  # (B, T, H, D)

    def loss(q):
        return jnp.sum(F.flash_attention(q, q, q, causal=True))
    with F.saved_bytes_log() as log:
        jax.make_jaxpr(loss)(q)
        assert log == {}
        jax.make_jaxpr(jax.grad(loss))(q)
        jax.make_jaxpr(jax.grad(loss))(q)
    once = {"zoo_flash_out": q.size * 4, "zoo_flash_lse": 2 * 4 * 40 * 4}
    assert log == {k: 2 * n for k, n in once.items()}
    jax.make_jaxpr(jax.grad(loss))(q)
    assert log == {k: 2 * n for k, n in once.items()}
