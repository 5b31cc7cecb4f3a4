"""``RoutedExperts`` — the dropless, share-aware expert layer — and the
grouped product under it: the shares add up to the uncut layer (outputs and
input gradients), nothing is dropped even when the router sends everything
to one expert, chunks of tokens change nothing, and ``grouped_matmul``
against a loop over groups."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.ops.grouped_matmul import grouped_matmul
from analytics_zoo_tpu.pipeline.api.keras.layers import (RoutedExperts,
                                                         SparseMoE)
from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (top_k_routing,
                                                             wide_value)

E, D, H, K = 8, 16, 12, 2


def _full_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"Wg": jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
            "Wgate": jnp.asarray(rng.normal(size=(E, D, H)) / 4, jnp.float32),
            "Wup": jnp.asarray(rng.normal(size=(E, D, H)) / 4, jnp.float32),
            "Wdown": jnp.asarray(rng.normal(size=(E, H, D)) / 4, jnp.float32)}


def _share(params, held):
    idx = jnp.asarray(held)
    return {"Wg": params["Wg"], **{k: params[k][idx]
                                   for k in ("Wgate", "Wup", "Wdown")}}


def _reference_layer(params, x):
    """The uncut layer by the benchmark's plain reference."""
    D_ = importlib.import_module("benchmark.reference._blocks_decoder")
    return D_.routed_experts(params, x, held=list(range(E)), top_k=K,
                             norm_topk=True, mode="f32")


def test_shares_add_up_to_the_uncut_layer():
    """The share test: the layer built four times, each holding a quarter
    of the experts, gives parts whose sum is the uncut reference layer's
    output; so do the gradients with respect to the input."""
    init_zoo_context()
    params = _full_params()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 10, D)),
                    jnp.float32)
    co = jnp.asarray(np.random.default_rng(2).normal(size=x.shape),
                     jnp.float32)
    quarters = [(0, 1), (2, 3), (4, 5), (6, 7)]
    layers = [RoutedExperts(E, H, top_k=K, held=q) for q in quarters]

    def parts(x):
        return [layer.call(_share(params, q), x)
                for layer, q in zip(layers, quarters)]
    want = _reference_layer(params, x.reshape(-1, D)).reshape(x.shape)
    np.testing.assert_allclose(sum(parts(x)), want, rtol=1e-4, atol=1e-5)
    # no single share is the whole: the test would pass trivially else
    assert float(jnp.abs(parts(x)[0] - want).max()) > 1e-2
    got_dx = jax.grad(lambda x: jnp.sum(sum(parts(x)) * co))(x)
    want_dx = jax.grad(lambda x: jnp.sum(_reference_layer(
        params, x.reshape(-1, D)).reshape(x.shape) * co))(x)
    np.testing.assert_allclose(got_dx, want_dx, rtol=1e-3, atol=1e-5)
    # and the whole layer in one piece is the reference too
    whole = RoutedExperts(E, H, top_k=K)
    np.testing.assert_allclose(whole.call(params, x), want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("target,held", [(1, (0, 1, 2, 3)), (6, (0, 1, 2, 3))],
                         ids=["to_a_held_expert", "to_an_absent_expert"])
def test_nothing_is_dropped_when_the_router_sends_all_to_one(target, held):
    """A router forced to one expert: that expert's group holds every
    token, no assignment is dropped, held + absent = N x k, and the
    counters add up over calls."""
    init_zoo_context()
    params = _share(_full_params(), held)
    wg = np.zeros((D, E), np.float32)
    wg[0, target] = 50.0
    params["Wg"] = jnp.asarray(wg)
    x = jnp.asarray(np.abs(np.random.default_rng(3).normal(size=(20, D)))
                    + 0.5, jnp.float32)
    layer = RoutedExperts(E, H, top_k=K, held=held)
    y, state = layer.apply(params, layer.initial_state(), x)
    tokens = np.asarray(state["moe_expert_tokens"])
    assert tokens[target] == 20 and tokens.sum() == 20 * K
    assert wide_value(state["moe_dropped"]) == 0
    assert (wide_value(state["moe_held"]) + wide_value(state["moe_absent"])
            == 20 * K)
    assert wide_value(state["moe_held"]) == int(
        np.asarray(state["moe_held_tokens"]).sum()) == tokens[list(held)].sum()
    assert np.all(np.isfinite(np.asarray(y)))
    _, state = layer.apply(params, state, x)
    assert (wide_value(state["moe_held"]) + wide_value(state["moe_absent"])
            == 2 * 20 * K)


def test_wide_counter_carries_past_int32():
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import _wide_add
    acc = jnp.zeros((2,), jnp.int32)
    for _ in range(5):
        acc = _wide_add(acc, jnp.asarray(2 ** 30 - 7, jnp.int32))
    assert wide_value(np.asarray(acc)) == 5 * (2 ** 30 - 7) > 2 ** 31


def test_token_chunks_change_nothing():
    init_zoo_context()
    params = _share(_full_params(4), (1, 3, 4, 6))
    x = jnp.asarray(np.random.default_rng(5).normal(size=(4, 8, D)),
                    jnp.float32)
    whole = RoutedExperts(E, H, top_k=K, held=(1, 3, 4, 6))
    chunked = RoutedExperts(E, H, top_k=K, held=(1, 3, 4, 6), token_chunk=8)

    def loss(layer, p, x):
        y, state = layer.apply(p, layer.initial_state(), x)
        return jnp.sum(y ** 2), state
    (la, sa), ga = jax.value_and_grad(
        lambda p, x: loss(whole, p, x), (0, 1), has_aux=True)(params, x)
    (lb, sb), gb = jax.value_and_grad(
        lambda p, x: loss(chunked, p, x), (0, 1), has_aux=True)(params, x)
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    for key in sa:
        np.testing.assert_array_equal(sa[key], sb[key])


def test_both_layers_route_through_one_function():
    """``SparseMoE`` and ``RoutedExperts`` share ``top_k_routing``: softmax
    over all outputs, top-k, renormalised."""
    logits = jnp.asarray(np.random.default_rng(6).normal(size=(5, E)),
                         jnp.float32)
    probs, w, idx = top_k_routing(logits, 3)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(idx, np.argsort(-np.asarray(probs),
                                                  axis=-1)[:, :3])
    _, raw, _ = top_k_routing(logits, 3, renormalize=False)
    np.testing.assert_allclose(raw, np.take_along_axis(
        np.asarray(probs), np.asarray(idx), axis=-1), rtol=1e-6)
    import inspect
    assert "top_k_routing" in inspect.getsource(SparseMoE._route)
    assert "top_k_routing" in inspect.getsource(RoutedExperts._run)


def test_constructor_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="held"):
        RoutedExperts(8, 4, held=(0, 0))
    with pytest.raises(ValueError, match="held"):
        RoutedExperts(8, 4, held=(8,))
    with pytest.raises(ValueError, match="top_k"):
        RoutedExperts(8, 4, top_k=9)


def test_expert_mesh_axis_is_refused():
    """One chip's share has no exchange: on an ``expert`` axis the layer
    says so and points at ``SparseMoE``."""
    init_zoo_context(mesh_expert=2)
    layer = RoutedExperts(E, H, top_k=K)
    with pytest.raises(NotImplementedError, match="SparseMoE"):
        layer.call(_full_params(), jnp.zeros((4, D)))


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

def _loop(x, w, sizes):
    out, lo = [], 0
    for g, n in enumerate(sizes):
        out.append(x[lo:lo + n] @ w[g])
        lo += n
    out.append(jnp.zeros((x.shape[0] - lo, w.shape[2]), x.dtype))
    return jnp.concatenate(out, axis=0)


@pytest.mark.parametrize("sizes", [
    (10, 0, 17, 5),         # an empty group
    (130, 3, 0, 123),       # a group that ends inside a 128-row tile
    (0, 0, 0, 0),           # nothing held at all
    (64, 64, 64, 64),       # every row in a group
], ids=["empty_group", "ends_inside_a_tile", "no_rows", "full"])
def test_grouped_matmul_against_a_loop_over_groups(sizes):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(256, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 24, 8)), jnp.float32)
    co = jnp.asarray(rng.normal(size=(256, 8)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    np.testing.assert_allclose(grouped_matmul(x, w, gs), _loop(x, w, sizes),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda x, w: jnp.sum(grouped_matmul(x, w, gs) * co),
                   (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(_loop(x, w, sizes) * co),
                    (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # rows past the last group: zero out, zero gradient in
    tail = sum(sizes)
    np.testing.assert_array_equal(grouped_matmul(x, w, gs)[tail:], 0.0)
    np.testing.assert_array_equal(got[0][tail:], 0.0)
