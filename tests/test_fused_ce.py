"""Fused blockwise LM-head cross-entropy (``ops/fused_cross_entropy`` +
``ops/pallas/cross_entropy`` + the keras loss resolution) vs the full-logits
objectives oracle — forward loss and dlogits-derived dW/dx/db grads within
tolerance, including padded/masked labels, row counts not divisible by the
chunk, vocab not divisible by the pallas tile, and the end-to-end training
wiring (losses/params bit-comparable to the unfused path). The CPU runs use
the pallas interpreter; the same code compiles on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from analytics_zoo_tpu.common.context import (init_zoo_context,
                                              reset_zoo_context)
from analytics_zoo_tpu.ops.fused_cross_entropy import (
    fused_cross_entropy_rows, fused_sparse_cross_entropy)
from analytics_zoo_tpu.pipeline.api.keras import Sequential, objectives
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

RTOL, ATOL = 1e-4, 1e-5


def _sub_jaxprs(eqn):
    """Sub-jaxprs ride an equation's params (scan/cond/shard_map bodies,
    custom_vjp calls), bare or closed, singly or in a sequence."""
    from jax.extend import core as jex_core
    for p in eqn.params.values():
        for sub in (p if isinstance(p, (list, tuple)) else (p,)):
            if isinstance(sub, jex_core.ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, jex_core.Jaxpr):
                yield sub


def _setup(n=37, h=24, v=130, seed=0):
    rng = np.random.default_rng(seed)
    hid = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.normal(size=(v,)) * 0.1, jnp.float32)
    y = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    return hid, w, b, y


def _oracle(y, hid, w, b):
    logits = hid @ w + (0.0 if b is None else b)
    return objectives.sparse_categorical_crossentropy_from_logits(y, logits)


# ---------------------------------------------------------------------------
# numerics vs the objectives oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_forward_matches_oracle(use_pallas):
    """Odd N (37) not divisible by the chunk (8); odd V (130) not divisible
    by the pallas vocab tile — both padded paths must stay exact."""
    hid, w, b, y = _setup()
    got = fused_sparse_cross_entropy(y, hid, w, b, chunk=8,
                                     use_pallas=use_pallas, interpret=True)
    np.testing.assert_allclose(float(got), float(_oracle(y, hid, w, b)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_grads_match_oracle(use_pallas):
    hid, w, b, y = _setup()

    def fused(hid, w, b):
        return fused_sparse_cross_entropy(y, hid, w, b, chunk=8,
                                          use_pallas=use_pallas,
                                          interpret=True)

    gf = jax.grad(fused, argnums=(0, 1, 2))(hid, w, b)
    go = jax.grad(lambda hid, w, b: _oracle(y, hid, w, b),
                  argnums=(0, 1, 2))(hid, w, b)
    for a, bb in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=RTOL, atol=ATOL)


def test_no_bias_grads():
    hid, w, _, y = _setup()
    gf = jax.grad(lambda hid, w: fused_sparse_cross_entropy(
        y, hid, w, None, chunk=16), argnums=(0, 1))(hid, w)
    go = jax.grad(lambda hid, w: _oracle(y, hid, w, None),
                  argnums=(0, 1))(hid, w)
    for a, bb in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=RTOL, atol=ATOL)


def test_masked_labels_drop_out_of_loss_and_grads():
    """Labels < 0 (padding/ignore) contribute zero loss and exactly zero
    gradient — the mean runs over valid rows only."""
    hid, w, b, y = _setup()
    ym = y.at[::3].set(-1)
    got = fused_sparse_cross_entropy(ym, hid, w, b, chunk=8)
    pe = objectives.sparse_categorical_crossentropy_from_logits_pe(
        jnp.where(ym < 0, 0, ym), hid @ w + b)
    valid = np.asarray(ym) >= 0
    ref = float(np.sum(np.asarray(pe) * valid) / valid.sum())
    np.testing.assert_allclose(float(got), ref, rtol=1e-6, atol=1e-6)
    gh = jax.grad(lambda hid: fused_sparse_cross_entropy(
        ym, hid, w, b, chunk=8))(hid)
    np.testing.assert_array_equal(np.asarray(gh)[~valid], 0.0)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_out_of_range_labels_poison_like_the_oracle(use_pallas):
    """Labels >= V must NaN the loss exactly as loudly as the oracle's
    fill-mode take_along_axis does — a dataset off-by-one can never train
    on silently under the fused path while the full-logits path would
    scream. Per-row: only the bad rows are NaN; grads go NaN too."""
    hid, w, b, _ = _setup(n=24, h=8, v=48, seed=6)
    y = np.arange(24, dtype=np.int32)
    y[[5, 11, 17]] = [48, 49, 1000]          # over-range
    y = jnp.asarray(y)
    assert np.isnan(float(_oracle(y, hid, w, b)))     # the oracle screams
    got = fused_sparse_cross_entropy(y, hid, w, b, chunk=8,
                                     use_pallas=use_pallas, interpret=True)
    assert np.isnan(float(got))                       # so do we
    rows = fused_cross_entropy_rows(hid, w, b, y, chunk=8,
                                    use_pallas=use_pallas, interpret=True)
    assert np.isnan(np.asarray(rows)[[5, 11, 17]]).all()
    assert np.isfinite(np.delete(np.asarray(rows), [5, 11, 17])).all()
    gw = jax.grad(lambda w: fused_sparse_cross_entropy(
        y, hid, w, b, chunk=8, use_pallas=use_pallas, interpret=True))(w)
    assert np.isnan(np.asarray(gw)).any()


def test_padded_backward_rows_stay_inert_under_huge_bias():
    """N not divisible by the chunk + a bias entry > ~88: the backward's
    pad rows (h = 0) see logits = bias, and exp(bias - pad_lse) must not
    overflow to inf (inf * zero grad-scale = NaN spread across dW by the
    matmul). The lse pad is +inf so pad rows contribute exactly 0."""
    hid, w, b, y = _setup(n=10, h=6, v=32, seed=8)
    b = b.at[3].set(100.0)                   # diverging-run-sized bias

    def fused(hid, w, b):
        return fused_sparse_cross_entropy(y, hid, w, b, chunk=8)

    gf = jax.grad(fused, argnums=(0, 1, 2))(hid, w, b)
    go = jax.grad(lambda hid, w, b: _oracle(y, hid, w, b),
                  argnums=(0, 1, 2))(hid, w, b)
    for a, bb in zip(gf, go):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=RTOL, atol=ATOL)


def test_rows_form_and_label_shapes():
    """(B, T) labels against (B, T, H) hidden states — the LM layout."""
    rng = np.random.default_rng(3)
    b_, t, h, v = 2, 9, 8, 64
    hid = jnp.asarray(rng.normal(size=(b_, t, h)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, v, (b_, t)), jnp.int32)
    got = fused_sparse_cross_entropy(y, hid, w, None, chunk=4)
    ref = _oracle(y.reshape(-1), hid.reshape(-1, h), w, None)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6, atol=1e-6)
    rows = fused_cross_entropy_rows(hid.reshape(-1, h), w, None,
                                    y.reshape(-1), chunk=4)
    assert rows.shape == (b_ * t,)


def test_bf16_hidden_states_close_to_f32_oracle():
    hid, w, b, y = _setup(n=64, h=16, v=256, seed=4)
    got = fused_sparse_cross_entropy(y, hid.astype(jnp.bfloat16), w, b,
                                     chunk=16)
    np.testing.assert_allclose(float(got), float(_oracle(y, hid, w, b)),
                               rtol=2e-2, atol=2e-2)


def test_chunk_invariance_and_validation():
    hid, w, b, y = _setup(n=32, h=8, v=64, seed=5)
    l1 = fused_sparse_cross_entropy(y, hid, w, b, chunk=5)
    l2 = fused_sparse_cross_entropy(y, hid, w, b, chunk=32)
    l3 = fused_sparse_cross_entropy(y, hid, w, b, chunk=999)  # > N clamps
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(float(l1), float(l3), rtol=1e-6)
    with pytest.raises(ValueError):
        fused_sparse_cross_entropy(y, hid, w, b, chunk=0)
    with pytest.raises(ValueError):
        fused_cross_entropy_rows(hid, w, b, y[:-1], chunk=8)


def test_no_full_logits_tensor_in_backward():
    """The point of the exercise: grad of the fused loss at an LM-head
    shape must never materialize the (N, V) tensor — walk every sub-jaxpr
    (scan bodies included) like test_pallas's quadratic-memory check."""
    n, h, v, chunk = 4096, 64, 8192, 128
    hid = jnp.zeros((n, h), jnp.float32)
    w = jnp.zeros((h, v), jnp.float32)
    b = jnp.zeros((v,), jnp.float32)
    y = jnp.zeros((n,), jnp.int32)

    def loss(hid, w, b):
        return fused_sparse_cross_entropy(y, hid, w, b, chunk=chunk,
                                          use_pallas=False)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(hid, w, b)
    biggest = 0

    def walk(jx):
        nonlocal biggest
        for eqn in jx.eqns:
            for var in eqn.outvars:
                if hasattr(var.aval, "shape"):
                    size = int(np.prod(var.aval.shape)) if var.aval.shape \
                        else 1
                    biggest = max(biggest, size)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr.jaxpr)
    # largest live tensor: the (H, V) weight grad / (chunk, V) tile —
    # nowhere near the (N, V) logits
    assert biggest < n * v // 8, f"(N, V)-scale intermediate: {biggest}"


# ---------------------------------------------------------------------------
# keras wiring: resolution + end-to-end parity
# ---------------------------------------------------------------------------

def _fit_once(conf, n=192, h=12, v=2048, epochs=2, neg_every=0):
    reset_zoo_context()
    init_zoo_context(conf=conf)
    from analytics_zoo_tpu.pipeline.api.keras.engine import reset_uids
    reset_uids()
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n, h)).astype(np.float32)
    y = rng.integers(0, v, n).astype(np.int32)
    if neg_every:
        y[::neg_every] = -1
    m = Sequential([Dense(16, activation="relu", input_shape=(h,)),
                    Dense(v)])
    m.compile(optimizer=optax.adam(1e-2), loss="scce_with_logits")
    hist = m.fit(x, y, batch_size=64, nb_epoch=epochs)
    return hist["loss"], m.params


def test_training_loop_fused_matches_full_path():
    """fused on/off/auto: identical rng schedule, losses and params agree
    to float tolerance — the fused path is a memory-layout change, not a
    numerics change."""
    l_off, p_off = _fit_once({"zoo.train.fused_ce": False})
    l_on, p_on = _fit_once({"zoo.train.fused_ce": True})
    l_auto, _ = _fit_once({"zoo.train.fused_ce": "auto"})  # V=2048 >= 1024
    np.testing.assert_allclose(l_off, l_on, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(l_off, l_auto, rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), p_off, p_on)


def test_bf16_policy_fused_matches_full_path():
    """Under bf16 compute the oracle's logits carry Dense's round-to-cd
    (+ bias-in-cd) — the fused path must replicate that rounding, not be
    quietly more precise, or fused on/off loss values drift."""
    conf = {"zoo.compute.dtype": "bfloat16"}
    l_off, _ = _fit_once({**conf, "zoo.train.fused_ce": False})
    l_on, _ = _fit_once({**conf, "zoo.train.fused_ce": True})
    np.testing.assert_allclose(l_off, l_on, rtol=1e-5, atol=1e-6)


def test_substitution_matches_oracle_on_negative_labels():
    """The silent substitution must replicate the oracle EXACTLY, negative
    labels included: the oracle's take_along_axis wraps label -1 to column
    V-1 and keeps the row in the mean. Toggling zoo.train.fused_ce can
    never change a training run's loss values — ignore-label masking is
    the op-level fused_sparse_cross_entropy API, not this substitution."""
    l_off, p_off = _fit_once({"zoo.train.fused_ce": False}, neg_every=5)
    l_on, p_on = _fit_once({"zoo.train.fused_ce": True}, neg_every=5)
    np.testing.assert_allclose(l_off, l_on, rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), p_off, p_on)


def test_fused_engages_and_registers_metric():
    from analytics_zoo_tpu.observability import default_registry
    _fit_once({"zoo.train.fused_ce": True}, epochs=1)
    snap = default_registry().snapshot()
    assert any(k.startswith("zoo_train_fused_ce") for k in snap), \
        f"no fused-CE info gauge in {sorted(snap)[:5]}..."
    # a later NON-fused loop must zero the stale series — the scrape can
    # never claim fusion is active when the current loop runs the oracle
    _fit_once({"zoo.train.fused_ce": False}, epochs=1)
    snap = default_registry().snapshot()
    vals = {k: v for k, v in snap.items()
            if k.startswith("zoo_train_fused_ce")}
    assert vals and all(v["value"] == 0 if isinstance(v, dict) else v == 0
                        for v in vals.values()), vals


def test_resolution_declines_non_matching_patterns():
    from analytics_zoo_tpu.pipeline.api.keras.fused_loss import \
        resolve_fused_loss
    init_zoo_context(conf={"zoo.train.fused_ce": True})
    big = Sequential([Dense(8, input_shape=(4,)), Dense(2048)])
    # logits loss + linear head: resolves
    assert resolve_fused_loss(
        big, objectives.sparse_categorical_crossentropy_from_logits)
    # softmax head + probability scce: resolves under the EXPLICIT flag
    # (the conf above is True) — the eps-clipped probability objective is
    # only approximated by the exact logits CE, so this pattern is never
    # an auto substitution
    soft = Sequential([Dense(8, input_shape=(4,)),
                       Dense(2048, activation="softmax")])
    assert resolve_fused_loss(
        soft, objectives.sparse_categorical_crossentropy)
    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": "auto"})
    assert resolve_fused_loss(
        soft, objectives.sparse_categorical_crossentropy) is None
    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": True})
    # activation="linear" is the identity — still a raw-logits head
    lin = Sequential([Dense(8, input_shape=(4,)),
                      Dense(2048, activation="linear")])
    assert resolve_fused_loss(
        lin, objectives.sparse_categorical_crossentropy_from_logits)
    # activated head + logits loss: the output is not raw logits
    relu = Sequential([Dense(8, input_shape=(4,)),
                       Dense(2048, activation="relu")])
    assert resolve_fused_loss(
        relu, objectives.sparse_categorical_crossentropy_from_logits) is None
    # non-CE loss
    assert resolve_fused_loss(big, objectives.mean_squared_error) is None
    # custom callable
    assert resolve_fused_loss(big, lambda y, yp: jnp.mean(yp)) is None
    # non-Dense tail
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dropout
    drop = Sequential([Dense(2048, input_shape=(4,)), Dropout(0.1)])
    assert resolve_fused_loss(
        drop, objectives.sparse_categorical_crossentropy_from_logits) is None


def test_auto_threshold_and_off_switch():
    from analytics_zoo_tpu.pipeline.api.keras.fused_loss import \
        resolve_fused_loss
    small = Sequential([Dense(8, input_shape=(4,)), Dense(5)])
    loss = objectives.sparse_categorical_crossentropy_from_logits
    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": "auto"})
    assert resolve_fused_loss(small, loss) is None      # V=5 < 1024
    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": True})
    assert resolve_fused_loss(small, loss) is not None  # forced on
    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": False})
    big = Sequential([Dense(8, input_shape=(4,)), Dense(2048)])
    assert resolve_fused_loss(big, loss) is None        # forced off


def test_softmax_head_scce_training_matches_full_path():
    """The probability-form pattern: Dense(V, softmax) + loss='scce' —
    fused computes the exact logits CE the clipped form approximates."""
    def run(fused):
        reset_zoo_context()
        init_zoo_context(conf={"zoo.train.fused_ce": fused})
        from analytics_zoo_tpu.pipeline.api.keras.engine import reset_uids
        reset_uids()
        rng = np.random.default_rng(9)
        x = rng.normal(size=(128, 10)).astype(np.float32)
        y = rng.integers(0, 1500, 128).astype(np.int32)
        m = Sequential([Dense(12, activation="relu", input_shape=(10,)),
                        Dense(1500, activation="softmax")])
        m.compile(optimizer=optax.adam(1e-2), loss="scce")
        return m.fit(x, y, batch_size=64, nb_epoch=2)["loss"]

    np.testing.assert_allclose(run(False), run(True), rtol=1e-4, atol=1e-5)


def test_bert_classifier_head_resolves():
    """tfpark's BERTClassifier exposes its dispatched softmax head through
    ``fused_head`` — forced fused training matches the full path."""
    from analytics_zoo_tpu.pipeline.api.keras.fused_loss import (
        find_head, resolve_fused_loss)
    from analytics_zoo_tpu.tfpark import BERTClassifier

    def run(fused):
        reset_zoo_context()
        init_zoo_context(conf={"zoo.train.fused_ce": fused})
        from analytics_zoo_tpu.pipeline.api.keras.engine import reset_uids
        reset_uids()
        rng = np.random.default_rng(11)
        ids = rng.integers(1, 50, (32, 8)).astype(np.int32)
        y = rng.integers(0, 2, 32).astype(np.int32)
        clf = BERTClassifier(num_classes=2, vocab=64, hidden_size=16,
                             n_block=1, n_head=2, seq_len=8,
                             intermediate_size=32, hidden_drop=0.0,
                             attn_drop=0.0, name="bertft")
        if fused:
            head = find_head(clf)
            assert head is not None and head[1] == ("cls",)
            assert resolve_fused_loss(
                clf, objectives.sparse_categorical_crossentropy) is not None
        x = clf.make_inputs(ids)
        clf.compile(optimizer=optax.adam(1e-3), loss="scce")
        return clf.fit(x, y, batch_size=16, nb_epoch=1)["loss"]

    np.testing.assert_allclose(run(False), run(True), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# remat policy (zoo.train.remat)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [True, "dots", "full"])
def test_remat_is_numerics_preserving(mode):
    l_off, p_off = _fit_once({"zoo.train.fused_ce": False}, v=64)
    l_on, p_on = _fit_once({"zoo.train.fused_ce": False,
                            "zoo.train.remat": mode}, v=64)
    np.testing.assert_allclose(l_off, l_on, rtol=1e-6, atol=1e-7)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), p_off, p_on)


def test_remat_rejects_unknown_mode():
    with pytest.raises(ValueError):
        _fit_once({"zoo.train.remat": "bogus"}, v=64)


def test_remat_composes_with_fused():
    """Remat around the fused CE's custom VJP against the plain loss
    without remat: same losses, same parameters."""
    l_a, p_a = _fit_once({"zoo.train.fused_ce": True,
                          "zoo.train.remat": True})
    l_b, p_b = _fit_once({"zoo.train.fused_ce": False})
    np.testing.assert_allclose(l_a, l_b, rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), p_a, p_b)


# ---------------------------------------------------------------------------
# vocab-sharded fused CE (model-parallel head) vs the unsharded op
# ---------------------------------------------------------------------------

def _sharded_setup(n=37, h=24, v=130, seed=0):
    """Odd N (37, not divisible by chunk or row divisor) and odd V (130,
    not divisible by model=4) on purpose — the padding paths are part of
    the parity gate."""
    hid, w, b, y = _setup(n=n, h=h, v=v, seed=seed)
    y = np.array(y)              # writable host copy
    y[::5] = -1                  # masked rows
    return hid, w, b, jnp.asarray(y)


# tier-1 keeps one cell per independent axis of the matrix — XLA on the
# even {model:2} mesh, XLA on the (data,seq)-row-sharded mesh, pallas on
# the PADDED {model:4} mesh (the riskiest combination); the remaining
# cells re-run the same code paths and ride the slow marker to keep the
# tier-1 wall-clock inside its budget (run with -m slow for the full
# matrix)
@pytest.mark.parametrize("meshkw,use_pallas", [
    ({"mesh_model": 2}, False),
    ({"mesh_data": 2, "mesh_model": 2, "mesh_seq": 2}, False),
    ({"mesh_model": 4}, True),
    pytest.param({"mesh_model": 2}, True, marks=pytest.mark.slow),
    pytest.param({"mesh_data": 2, "mesh_model": 2, "mesh_seq": 2}, True,
                 marks=pytest.mark.slow),
    pytest.param({"mesh_model": 4}, False, marks=pytest.mark.slow),
])
def test_sharded_matches_unsharded(meshkw, use_pallas):
    """The bit-parity gate: vocab-sharded loss rows AND dh/dW/db grads
    match the unsharded op on {model:2} / {data:2,seq:2,model:2} /
    {model:4} (V=130 % 4 != 0 exercises the padded-shard path), masked
    labels and N % chunk != 0 included. The row max, label logit and
    every per-element term are computed identically; only the
    cross-shard denominator psum re-associates the sum, so the
    comparison allows reassociation-level float32 rounding and nothing
    more."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        sharded_fused_cross_entropy_rows, sharded_fused_sparse_cross_entropy)

    reset_zoo_context()
    init_zoo_context(**meshkw)
    hid, w, b, y = _sharded_setup()
    rows_u = np.asarray(fused_cross_entropy_rows(hid, w, b, y, chunk=8,
                                                 use_pallas=False))
    rows_s = np.asarray(sharded_fused_cross_entropy_rows(
        hid, w, b, y, chunk=8, use_pallas=use_pallas, interpret=True))
    np.testing.assert_allclose(rows_s, rows_u, rtol=1e-6, atol=1e-6)

    g_u = jax.grad(lambda hid, w, b: fused_sparse_cross_entropy(
        y, hid, w, b, chunk=8, use_pallas=False),
        argnums=(0, 1, 2))(hid, w, b)
    g_s = jax.grad(lambda hid, w, b: sharded_fused_sparse_cross_entropy(
        y, hid, w, b, chunk=8, use_pallas=use_pallas, interpret=True),
        argnums=(0, 1, 2))(hid, w, b)
    for a, bb in zip(g_s, g_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-6, atol=1e-7)
    # masked rows: exactly zero hidden-state grad, like the unsharded op
    np.testing.assert_array_equal(np.asarray(g_s[0])[::5], 0.0)


def test_sharded_over_range_labels_poison_all_shards():
    """A label >= V NaNs its row and the FULL sharded dW — the poison
    must not stay confined to the owning shard (the unsharded op NaNs
    the whole (H, V) gradient through the matmul)."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        sharded_fused_cross_entropy_rows, sharded_fused_sparse_cross_entropy)

    reset_zoo_context()
    init_zoo_context(mesh_model=2)
    hid, w, b, y = _sharded_setup()
    y = jnp.asarray(np.where(np.arange(37) == 3, 500,
                             np.maximum(np.asarray(y), 0)).astype(np.int32))
    rows = np.asarray(sharded_fused_cross_entropy_rows(hid, w, b, y,
                                                       chunk=8))
    assert np.isnan(rows[3]) and np.isfinite(np.delete(rows, 3)).all()
    gw = np.asarray(jax.grad(lambda w: sharded_fused_sparse_cross_entropy(
        y, hid, w, b, chunk=8))(w))
    # every vocab shard's dW columns carry the poison
    assert np.isnan(gw[:, :65]).any() and np.isnan(gw[:, 65:]).any()


def test_sharded_bf16_policy_matches_unsharded():
    """bf16 hidden states: the sharded tiles carry the same
    compute-dtype rounding, so sharded-vs-unsharded stays at float32
    reassociation level even when the logits themselves are bf16-rounded."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        sharded_fused_sparse_cross_entropy)

    reset_zoo_context()
    init_zoo_context(mesh_data=2, mesh_model=2, mesh_seq=2)
    hid, w, b, y = _sharded_setup(n=64, h=16, v=256, seed=4)
    hb = hid.astype(jnp.bfloat16)
    got = sharded_fused_sparse_cross_entropy(y, hb, w, b, chunk=16)
    ref = fused_sparse_cross_entropy(y, hb, w, b, chunk=16,
                                     use_pallas=False)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6, atol=1e-6)


def test_sharded_no_bias_and_model1_fallback():
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        sharded_fused_cross_entropy_rows)

    reset_zoo_context()
    init_zoo_context(mesh_model=2)
    hid, w, _, y = _sharded_setup()
    rows_u = np.asarray(fused_cross_entropy_rows(hid, w, None, y, chunk=8,
                                                 use_pallas=False))
    rows_s = np.asarray(sharded_fused_cross_entropy_rows(
        hid, w, None, y, chunk=8))
    np.testing.assert_allclose(rows_s, rows_u, rtol=1e-6, atol=1e-6)
    # model == 1 mesh: the sharded entry IS the unsharded op
    reset_zoo_context()
    init_zoo_context()
    rows_1 = np.asarray(sharded_fused_cross_entropy_rows(
        hid, w, None, y, chunk=8, use_pallas=False))
    np.testing.assert_array_equal(rows_1, rows_u)


def test_sharded_backward_no_full_vocab_per_rank():
    """The jaxpr gate: grad of the SHARDED loss at an LM-head shape must
    contain neither an (N, V)-scale intermediate nor a full-V-per-rank
    tile — inside the shard_map every logits/probability tile is
    (chunk, V/n), and dW stays (H, V/n) per rank."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        sharded_fused_sparse_cross_entropy)

    reset_zoo_context()
    init_zoo_context(mesh_model=2)
    n, h, v, chunk = 4096, 64, 8192, 128
    hid = jnp.zeros((n, h), jnp.float32)
    w = jnp.zeros((h, v), jnp.float32)
    b = jnp.zeros((v,), jnp.float32)
    y = jnp.zeros((n,), jnp.int32)

    def loss(hid, w, b):
        return sharded_fused_sparse_cross_entropy(y, hid, w, b,
                                                  chunk=chunk,
                                                  use_pallas=False)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(hid, w, b)
    biggest = 0

    def walk_all(jx):
        nonlocal biggest
        for eqn in jx.eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                size = int(np.prod(aval.shape)) if aval.shape else 1
                biggest = max(biggest, size)
            for sub in _sub_jaxprs(eqn):
                walk_all(sub)

    walk_all(jaxpr.jaxpr)
    # largest live tensor anywhere (shard_map bodies included — their
    # jaxprs carry the PER-RANK avals, so a full-V-per-rank (chunk, V)
    # tile or an (N, V) global would both trip this): the (H, V) weight
    # grad assembled outside the ranks / the (chunk, V/n) local tiles
    assert biggest < n * v // 8, f"(N, V)-scale intermediate: {biggest}"


def test_sharded_training_loop_matches_unsharded(caplog):
    """End to end: a big-vocab head training under {model:2} rides the
    VOCAB-SHARDED fused CE (the log proves the engagement, the gauge
    carries sharded=1) and the losses match the pure-DP full-logits
    path — the model-parallel head is a layout choice, not a numerics
    change."""
    import logging

    from analytics_zoo_tpu.observability import default_registry

    l_dp, p_dp = _fit_once({"zoo.train.fused_ce": False})
    with caplog.at_level(logging.INFO, logger="analytics_zoo_tpu.training"):
        l_tp, p_tp = _fit_once({"zoo.train.fused_ce": True,
                                "zoo.mesh.model": 2})
    assert any("VOCAB-SHARDED" in r.message for r in caplog.records)
    np.testing.assert_allclose(l_dp, l_tp, rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), p_dp, p_tp)
    snap = default_registry().snapshot()
    hits = [k for k, v in snap.items()
            if k.startswith("zoo_train_fused_ce") and 'sharded="1"' in k
            and (v["value"] if isinstance(v, dict) else v) == 1]
    assert hits, f"no sharded=1 fused-CE gauge in {sorted(snap)[:8]}"


def test_sharded_resolution_respects_divisibility():
    """A head width the model axis does not divide falls back to the
    UNSHARDED fused loss (sharded=0) — matching param_shardings'
    replicated fallback for the same head, so the loss collectives
    always agree with the actual param layout."""
    from analytics_zoo_tpu.pipeline.api.keras.fused_loss import \
        resolve_fused_loss

    reset_zoo_context()
    init_zoo_context(conf={"zoo.train.fused_ce": True}, mesh_model=2)
    from analytics_zoo_tpu.pipeline.api.keras.engine import reset_uids
    reset_uids()
    odd = Sequential([Dense(8, input_shape=(4,)), Dense(2049)])
    spec = resolve_fused_loss(
        odd, objectives.sparse_categorical_crossentropy_from_logits)
    assert spec is not None and not spec.sharded
    even = Sequential([Dense(8, input_shape=(4,)), Dense(2048)])
    spec = resolve_fused_loss(
        even, objectives.sparse_categorical_crossentropy_from_logits)
    assert spec is not None and spec.sharded
