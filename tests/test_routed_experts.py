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
from analytics_zoo_tpu.pipeline.api.keras.layers.moe import (
    _ROW_TILE, WIDE_COUNTERS, _loop_passes, bound_ratios, routed_layer_totals,
    top_k_routing, wide_value)

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
    # 40 assignments are under a tile: the buffers cannot be cut; every
    # token makes the same choices, so each holds the most any holds
    assert layer.compact_rows(20) == 20 * K
    assert wide_value(state["moe_rows_run"]) == 20 * K
    assert wide_value(state["moe_compact_runs"]) == 0
    assert (wide_value(state["moe_choice_passes"]) * 20
            == wide_value(state["moe_held"]))
    _, state = layer.apply(params, state, x)
    assert (wide_value(state["moe_held"]) + wide_value(state["moe_absent"])
            == 2 * 20 * K)
    assert wide_value(state["moe_chunk_runs"]) == 2


def test_wide_counter_carries_past_int32():
    from analytics_zoo_tpu.pipeline.api.keras.layers.moe import _wide_add
    acc = jnp.zeros((2,), jnp.int32)
    for _ in range(5):
        acc = _wide_add(acc, jnp.asarray(2 ** 30 - 7, jnp.int32))
    assert wide_value(np.asarray(acc)) == 5 * (2 ** 30 - 7) > 2 ** 31


def _chosen(choices, seed=0):
    """``(params, x)`` whose router sends token ``n`` to exactly the
    experts ``choices[n]``, in that order: the router reads the first E
    features, which hold the token's logits."""
    rng = np.random.default_rng(seed)
    params = _full_params(seed)
    wg = np.zeros((D, E), np.float32)
    wg[np.arange(E), np.arange(E)] = 1.0
    params["Wg"] = jnp.asarray(wg)
    x = rng.normal(size=(len(choices), D)).astype(np.float32)
    x[:, :E] = rng.normal(size=(len(choices), E)) * 0.1
    for n, chosen in enumerate(choices):
        x[n, list(chosen)] = 6.0 - 0.5 * np.arange(len(chosen))
    return params, jnp.asarray(x)


def _against_the_reference(layer, params, x, top_k):
    """Output, d tokens, d Wg and the three expert gradients of ``layer``
    (a share of ``params``) against the dense masked reference; the state
    the layer left."""
    held = list(layer.held)
    share = _share(params, held)
    co = jnp.asarray(np.random.default_rng(9).normal(size=x.shape),
                     jnp.float32)
    D_ = importlib.import_module("benchmark.reference._blocks_decoder")

    def got_loss(p, x):
        y, state = layer.apply(p, layer.initial_state(), x)
        return jnp.sum(y * co), (y, state)

    def want_loss(p, x):
        y = D_.routed_experts(p, x, held=held, top_k=top_k, norm_topk=True,
                              mode="f32")
        return jnp.sum(y * co), y
    (_, (y, state)), got = jax.jit(jax.value_and_grad(
        got_loss, (0, 1), has_aux=True))(share, x)
    (_, want_y), want = jax.value_and_grad(
        want_loss, (0, 1), has_aux=True)(share, x)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-5)
    assert set(got[0]) == {"Wg", "Wgate", "Wup", "Wdown"}
    for name in got[0]:
        np.testing.assert_allclose(got[0][name], want[0][name], rtol=1e-3,
                                   atol=1e-5, err_msg=name)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-3, atol=1e-5)
    return state


N_CUT = 256                     # tokens; x K = 512 assignments, C = 128


@pytest.mark.parametrize("held,n_held", [
    ((2,), 100), ((2,), 128), ((2,), 129), ((2,), 0), ((2, 5), 512)],
    ids=["under_C", "exactly_C", "one_row_over_C", "none_held", "share_1"])
def test_row_buffers_hold_the_rows_held_or_the_worst_case(held, n_held):
    """The row side over ``C`` rows where the held assignments fit, over
    all ``N x k`` where they do not, one row over included: the dense
    masked reference's output and gradients either way, nothing dropped,
    and the counters say which ran."""
    init_zoo_context()
    layer = RoutedExperts(E, H, top_k=K, held=held)
    n_rows, cut = N_CUT * K, layer.compact_rows(N_CUT)
    assert cut == 2 * n_rows * len(held) // E and cut % _ROW_TILE == 0
    if len(held) == 2:                      # every choice of every token
        choices = [held[::-1] if n % 2 else held for n in range(N_CUT)]
    else:                                   # n_held tokens hold one choice
        rng = np.random.default_rng(11)
        holders = set(rng.permutation(N_CUT)[:n_held].tolist())
        choices = [((0, 2) if n % 2 else (2, 7)) if n in holders else (1, 4)
                   for n in range(N_CUT)]
    params, x = _chosen(choices)
    state = _against_the_reference(layer, params, x, K)
    fits = n_held <= cut
    assert wide_value(state["moe_held"]) == n_held
    assert wide_value(state["moe_dropped"]) == 0
    assert wide_value(state["moe_rows_run"]) == (cut if fits else n_rows)
    assert wide_value(state["moe_compact_runs"]) == fits
    assert wide_value(state["moe_chunk_runs"]) == 1
    assert wide_value(state["moe_choice_passes"]) == min(n_held, len(held))


@pytest.mark.parametrize("held_choices", [(0,), (0, 1), (1, 2), (0, 1, 3),
                                          (0, 1, 3, 4), (4,)],
                         ids=["no_token_holds_any", "at_most_1", "at_most_2",
                              "at_most_3", "up_to_k", "every_token_all_k"])
def test_gather_sums_stop_at_the_most_held_choices_any_token_has(
        held_choices):
    """Tokens that hold 0, 1, 3 and k of their k = 4 choices in one batch:
    the passes due are the largest count among them, wherever in a token's
    choices the held ones stand; the passes run are those where a loop
    over them is the cheaper (two of 4), else all k; and the result is the
    reference's either way."""
    init_zoo_context()
    top_k, held, absent = 4, (0, 1, 2, 3), (4, 5, 6, 7)
    rng = np.random.default_rng(13)
    choices = []
    for n in range(24):
        c = held_choices[n % len(held_choices)]
        mine = (list(rng.permutation(held)[:c])
                + list(rng.permutation(absent)[:top_k - c]))
        choices.append(tuple(int(e) for e in rng.permutation(mine)))
    params, x = _chosen(choices)
    layer = RoutedExperts(E, H, top_k=top_k, held=held)
    state = _against_the_reference(layer, params, x, top_k)
    assert (_loop_passes(8), _loop_passes(top_k), _loop_passes(2)) == (5, 2, 1)
    due = max(held_choices)
    assert wide_value(state["moe_choice_passes"]) == (due if due <= 2
                                                      else top_k)
    assert wide_value(state["moe_held"]) == sum(
        held_choices[n % len(held_choices)] for n in range(24))


def _loss_grads_state(layer, params, x):
    def loss(p, x):
        y, state = layer.apply(p, layer.initial_state(), x)
        return jnp.sum(y ** 2), state
    return jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x)


@pytest.mark.parametrize("cut", [False, True],
                         ids=["random_router", "buffers_cut"])
def test_token_chunks_change_nothing(cut):
    """Chunks of tokens give the loss, the gradients and the assignment
    counters of the whole, and the same ratios of what bounded the work
    (the runs themselves are counted a chunk)."""
    init_zoo_context()
    if cut:      # 512 tokens, a chunk 256: 60 of each chunk hold expert 2
        held, chunk = (2,), N_CUT
        params, x = _chosen([(2, 7) if n % 256 < 60 else (1, 4)
                             for n in range(2 * N_CUT)])
        params = _share(params, held)
    else:
        held, chunk = (1, 3, 4, 6), 8
        params = _share(_full_params(4), held)
        x = jnp.asarray(np.random.default_rng(5).normal(size=(4, 8, D)),
                        jnp.float32)
    whole = RoutedExperts(E, H, top_k=K, held=held)
    chunked = RoutedExperts(E, H, top_k=K, held=held, token_chunk=chunk)
    (la, sa), ga = _loss_grads_state(whole, params, x)
    (lb, sb), gb = _loss_grads_state(chunked, params, x)
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    for key in ("moe_expert_tokens", "moe_held_tokens", "moe_held",
                "moe_absent", "moe_dropped"):
        np.testing.assert_array_equal(sa[key], sb[key])
    n_chunks = x.size // D // chunk
    assert wide_value(sa["moe_chunk_runs"]) == 1
    assert wide_value(sb["moe_chunk_runs"]) == n_chunks
    a, b = (bound_ratios(routed_layer_totals({"ffn": s})["ffn"])
            for s in (sa, sb))
    assert a == b
    assert a["compact_share"] == float(cut)
    if cut:
        assert a["rows_run_over_held"] == whole.compact_rows(2 * N_CUT) / 120
        assert a["choice_passes_mean"] == 1.0


@pytest.mark.parametrize("held,conditionals", [(None, False), ((2,), True)],
                         ids=["every_expert_held", "a_share_held"])
def test_a_layer_that_holds_every_expert_lowers_with_no_conditional(
        held, conditionals):
    """With every expert held no bound can be under the static one: the
    layer emits no ``cond`` and no ``while`` at all, forward or backward."""
    init_zoo_context()
    layer = RoutedExperts(E, H, top_k=K, held=held)
    params = _share(_full_params(), layer.held)
    x = jnp.zeros((N_CUT, D), jnp.float32)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.call(p, x)),
                            (0, 1))).lower(params, x).as_text()
    found = any(op in text for op in ("stablehlo.case", "stablehlo.if",
                                      "stablehlo.while"))
    assert found == conditionals


def test_no_loop_or_conditional_stands_under_a_moe_scope():
    """A ``while`` or a ``conditional`` reaches the device trace as one
    whole event around what it runs: were one named ``zoo_moe.*``, a reader
    that sums device time by scope (``benchmark/lib/scopes.py``) would
    count its body twice. The scopes are opened inside, on the operations."""
    import re
    init_zoo_context()
    layer = RoutedExperts(E, H, top_k=K, held=(2,), token_chunk=N_CUT)
    params = _share(_full_params(), layer.held)
    x = jnp.zeros((2 * N_CUT, D), jnp.float32)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.call(p, x)),
                            (0, 1))).lower(params, x).compile().as_text()
    control, scoped = 0, set()
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if re.search(r" (while|conditional)\(", line):
            control += 1
            assert name is None or "zoo_moe." not in name.group(1), line
        elif name:
            scoped.update(re.findall(r"zoo_moe\.\w+", name.group(1)))
    assert control >= 4          # the chunks' map, cond and loops, both ways
    assert scoped == {"zoo_moe.route", "zoo_moe.dispatch", "zoo_moe.experts",
                      "zoo_moe.combine"}


@pytest.mark.parametrize("count", ["held", "rows_run", "choice_passes",
                                   "chunk_runs", "compact_runs"])
def test_layer_counters_carry_past_int32(count):
    """A counter of the layer state that stands one under 2**31 takes a
    run's count past it."""
    init_zoo_context()
    layer = RoutedExperts(E, H, top_k=K, held=(2,))
    params, x = _chosen([(2, 7)] * 100 + [(1, 4)] * (N_CUT - 100))
    params = _share(params, layer.held)
    _, once = layer.apply(params, layer.initial_state(), x)
    added = wide_value(once[WIDE_COUNTERS[count]])
    assert added > 0
    state = layer.initial_state()
    state[WIDE_COUNTERS[count]] = jnp.asarray([1, 2 ** 30 - 1], jnp.int32)
    _, state = layer.apply(params, state, x)
    assert (wide_value(state[WIDE_COUNTERS[count]])
            == 2 ** 31 - 1 + added > 2 ** 31 - 1)


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
