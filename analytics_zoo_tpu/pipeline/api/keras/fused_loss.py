"""Fused LM-head loss resolution — wires ``ops/fused_cross_entropy`` into
the training loop without touching model code.

``resolve_fused_loss(model, loss_fn)`` recognizes the (head Dense, sparse-CE
loss) pattern at step-build time and returns a spec that computes the loss
directly from the head's INPUT hidden states: the trunk runs normally, the
head layer's container dispatch is intercepted to identity
(``engine.intercept_layer_calls`` — the same hook the int8 inference runtime
uses), and the fused blockwise loss consumes the head's ``W``/``b`` params
straight from the param tree, so the ``(B·T, V)`` logits tensor is never
materialized in the training step. Gradients to the head weights flow
through the fused custom VJP; everything upstream is untouched.

Recognized patterns (``zoo.train.fused_ce``: auto | true | false):

* loss ``scce_with_logits`` + a linear head ``Dense(V)`` — exact fusion;
* loss ``scce`` + a ``Dense(V, activation="softmax")`` head — the fused
  logits-form objective, numerically the exact cross-entropy the clipped
  probability form approximates (equivalence-tested in
  ``tests/test_fused_ce.py``). EXPLICIT ``zoo.train.fused_ce=true``
  only: the probability form's eps-clip makes saturated-regime losses
  differ, so ``auto`` never silently substitutes this pattern.

``auto`` engages at ``V >= AUTO_MIN_VOCAB`` (the LM-head regime where the
logits memory dominates); small classifier heads stay on the full-logits
oracle. Heads are found on ``Sequential`` (last layer), ``Model`` (single
Dense output node), or any layer exposing ``fused_head() -> (dense,
param_path)`` (``tfpark``'s ``_BertClassifierNet`` does). The full-logits
objective remains the oracle: ``evaluate``/``predict`` and every
non-matching model keep it.

On a mesh with a ``model`` axis whose size divides the head width — the
predicate under which ``mesh.param_shardings`` actually shards the head
kernel ``P(None, model)`` — the resolved spec additionally routes the
loss through the VOCAB-SHARDED fused CE
(``ops.fused_cross_entropy.sharded_fused_cross_entropy_rows``): each rank
streams only its ``(chunk, V/n)`` weight slice and ``dW`` stays sharded
end to end, so the model-parallel LM head trains without a full-vocab
tensor ever forming on any chip. The ``zoo_train_fused_ce`` gauge carries
a ``sharded`` label so the scrape shows which form is live.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional, Tuple

from ....ops.fused_cross_entropy import (AUTO_MIN_VOCAB,
                                         fused_sparse_cross_entropy,
                                         sharded_fused_sparse_cross_entropy,
                                         vocab_shard_count)

log = logging.getLogger("analytics_zoo_tpu.training")


def find_head(model) -> Optional[Tuple[object, Tuple[str, ...]]]:
    """``(head_dense_layer, param_path)`` for the model's logits head, or
    None when no unique container-dispatched Dense head exists."""
    from .engine import Model, Sequential
    from .layers.core import Dense

    hook = getattr(model, "fused_head", None)
    if callable(hook):
        return hook()
    if isinstance(model, Sequential) and model.layers:
        head = model.layers[-1]
        hook = getattr(head, "fused_head", None)
        if callable(hook):
            # a head tied to a table of the last layer's own parameters
            found = hook()
            return found and (found[0], (head.name,) + tuple(found[1]))
        if (isinstance(head, Dense)
                and sum(1 for l in model.layers if l is head) == 1):
            return head, (head.name,)
        return None
    if isinstance(model, Model) and len(model.outputs) == 1:
        node = model.outputs[0].node
        if (node.parents and isinstance(node.layer, Dense)
                and sum(1 for n in model._topo
                        if n.layer is node.layer) == 1):
            return node.layer, (node.name,)
    return None


class FusedHeadSpec:
    """A resolved head: applies the trunk (head intercepted to identity)
    and the fused blockwise loss over the head's own params. ``sharded``
    marks the vocab-sharded (model-parallel) form — resolved once per
    loop from the mesh, so every step builder of a loop compiles the
    same collective structure. ``tied`` marks a head that is a (V, H)
    table the trunk also embeds with (``layers.TiedHead``): the path
    leads to the table and the loss reads it transposed."""

    def __init__(self, head, param_path: Tuple[str, ...],
                 sharded: bool = False):
        self.head = head
        self.param_path = tuple(param_path)
        self.tied = bool(getattr(head, "tied", False))
        self.sharded = bool(sharded) and not self.tied

    def head_params(self, params):
        p = params
        for k in self.param_path:
            p = p[k]
        return {"W": p.T} if self.tied else p

    def apply_and_loss(self, model, params, net_state, x, y, *, rng=None):
        """(loss, new_state) with the head fused into the loss."""
        import jax

        from .engine import intercept_layer_calls
        head = self.head

        def hook(layer, p, s, xx, training, lrng):
            if layer is head:
                return xx, s        # identity: expose the hidden states
            return None

        with intercept_layer_calls(hook):
            h, ns = model.apply(params, net_state, x, training=True, rng=rng)
        with jax.named_scope("zoo_loss"):
            return self._fused_loss(self.head_params(params), h, y), ns

    def _fused_loss(self, hp, h, y):
        """The fused blockwise cross-entropy of hidden states ``h`` against
        labels ``y`` over the head's ``W`` (and ``b``)."""
        import jax.numpy as jnp
        w = hp["W"]
        # the objectives oracle indexes numpy-style: a label in [-V, -1]
        # WRAPS (take_along_axis picks logits[V+label]) and still counts
        # in the mean over all rows; anything outside [-V, V) hits the
        # gather's fill mode and NaNs the loss. Replicate both exactly —
        # this silent substitution must be a memory-layout change, never
        # a numerics change (loss-gate comparability across the flag):
        # wrap the in-range negatives, and route doubly-invalid labels
        # to the op's over-range NaN poisoning. Ignore-label masking is
        # the op-level fused_sparse_cross_entropy API, opted into by
        # calling it directly with label<0 rows intact.
        v = w.shape[1]
        labels = jnp.asarray(y).reshape(-1).astype(jnp.int32)
        labels = jnp.where(labels < -v, v,
                           jnp.where(labels < 0, labels + v, labels))
        if self.sharded:
            return sharded_fused_sparse_cross_entropy(labels, h, w,
                                                      hp.get("b"))
        return fused_sparse_cross_entropy(labels, h, w, hp.get("b"))


def _mode() -> str:
    from ....common.context import tri_state_conf
    flag = tri_state_conf("zoo.train.fused_ce")
    if flag == "auto":
        return "auto"
    return "on" if flag else "off"


def resolve_fused_loss(model, loss_fn: Callable) -> Optional[FusedHeadSpec]:
    """The spec for (model, loss) when the fused path applies, else None."""
    import jax

    from . import objectives

    mode = _mode()
    if mode == "off":
        return None
    found = find_head(model)
    if found is None:
        return None
    head, path = found
    if loss_fn is objectives.sparse_categorical_crossentropy_from_logits:
        # activation="linear" resolves to the registry's identity lambda —
        # the same raw-logits head as activation=None
        from .layers.core import ACTIVATIONS
        if head.activation is not None \
                and head.activation is not ACTIVATIONS["linear"]:
            return None            # activated output: not raw logits
    elif loss_fn is objectives.sparse_categorical_crossentropy:
        if head.activation is not jax.nn.softmax:
            return None            # only softmax probabilities invert to CE
        # the probability-form objective eps-clips before the log, so in
        # saturated regimes its losses/grads genuinely differ from the
        # exact logits CE the fused path computes — a better objective,
        # but NOT the numerics-preserving substitution auto promises.
        # Opting in takes the explicit zoo.train.fused_ce=true.
        if mode != "on":
            return None
    else:
        return None
    if mode == "auto" and head.output_dim < AUTO_MIN_VOCAB:
        return None
    sharded = _head_sharded(head)
    if sharded and getattr(head, "tied", False):
        # FusedHeadSpec never shards a tied head: the table is embedded
        # with whole, so its loss is the unsharded one on every rank
        log.warning("fused LM-head cross-entropy: head=%s is tied to the "
                    "token table, which a `model` mesh axis does not shard: "
                    "every rank runs the UNSHARDED fused loss over all %d "
                    "classes", head.name, head.output_dim)
    return FusedHeadSpec(head, path, sharded=sharded)


def _head_sharded(head) -> bool:
    """Whether the resolved head's kernel is model-sharded under the
    current mesh — the same divisibility predicate
    ``mesh.param_shardings`` applies before committing the Dense
    ``P(None, model)`` spec (an indivisible head falls back to the
    replicated kernel AND the unsharded fused loss together, so the loss
    collectives always match the param layout)."""
    n_model = vocab_shard_count()
    return n_model > 1 and head.output_dim % n_model == 0
