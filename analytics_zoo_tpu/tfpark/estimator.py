"""TFEstimator — the TFPark generic model_fn estimator
(reference: ``pyzoo/zoo/tfpark/estimator.py:84-377``).

The reference wraps user TF graph code: ``model_fn(features, labels, mode,
params) -> TFEstimatorSpec(mode, predictions, loss)``, trained by a
TFOptimizer over a TFDataset. Here the same contract runs on the native
graph engine: ``features``/``labels`` arrive as graph ``Variable`` handles
(autograd operator overloading + any keras layer, including imported
``TFNet``/``Net.load*`` graphs), and the returned spec's ``loss``/
``predictions`` Variables close over one shared layer graph, so training and
prediction use the same weights without TF-style variable scoping:

* ``train`` builds ``Model(features+labels → loss)`` and runs the ordinary
  jitted fit loop (identity objective over the graph-computed loss).
* ``predict``/``evaluate`` build ``Model(features → predictions)`` over the
  SAME layer objects — the trained params transfer by layer name (names are
  assigned once, by the first Model constructed).

``model_fn`` signature is introspected like the reference's
``add_train_op`` (``estimator.py:32-46``): only the arguments it declares
are passed; declaring no ``labels`` while the dataset carries labels is an
error, mirroring the reference's check.
"""

from __future__ import annotations

import inspect
import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.triggers import MaxIteration
from ..feature import FeatureSet
from ..pipeline.api.keras.engine import Input, Model, Variable
from .tf_dataset import TFDataset, _flatten, _pack

__all__ = ["ModeKeys", "TFEstimatorSpec", "TFEstimator"]


class ModeKeys:
    """``tf.estimator.ModeKeys`` equivalents."""
    TRAIN = "train"
    EVAL = "eval"
    PREDICT = "infer"


class TFEstimatorSpec:
    """``zoo.tfpark.estimator.TFEstimatorSpec`` (``estimator.py:76-82``):
    what a model_fn returns. ``predictions`` may be a Variable, a list, or a
    dict of Variables; ``loss`` a (scalar- or per-example-valued)
    Variable."""

    def __init__(self, mode: str, predictions=None, loss: Optional[Variable] = None):
        self.mode = mode
        self.predictions = predictions
        self.loss = loss


def _call_input_fn(input_fn: Callable, mode: str) -> TFDataset:
    args = _fn_args(input_fn)
    ds = input_fn(mode=mode) if "mode" in args else input_fn()
    if not isinstance(ds, TFDataset):
        raise ValueError(f"input_fn must return a TFDataset, got "
                         f"{type(ds).__name__}")
    return ds


def _fn_args(fn) -> List[str]:
    return list(inspect.signature(fn).parameters)


class TFEstimator:
    """``TFEstimator(model_fn, optimizer=None, model_dir=None, config=None,
    params=None)`` — see ``estimator.py:86-148``. ``optimizer`` is anything
    the native ``compile`` accepts (an optax transformation or a name like
    ``"adam"``)."""

    def __init__(self, model_fn: Callable, optimizer=None,
                 model_dir: Optional[str] = None, config: Any = None,
                 params: Any = None, **optimizer_kwargs):
        self.model_fn = model_fn
        self.optimizer = optimizer
        self.optimizer_kwargs = optimizer_kwargs
        self.model_dir = model_dir
        self.config = config
        self.params = params
        self._train_model: Optional[Model] = None
        self._predict_model: Optional[Model] = None
        self._pred_def = None       # predictions structure treedef
        self._graph_ds_sig = None   # structure the graph was built for

    # -- graph construction -------------------------------------------------
    def _build_graph(self, ds: TFDataset, mode: str):
        """Call model_fn ONCE over Input variables shaped like ``ds``;
        construct the train and predict Models over the shared graph."""
        feat_metas, feat_def = _flatten(ds.tensor_structure)
        feat_inputs = [Input(shape=m.shape, name=m.name) for m in feat_metas]
        features = _pack(list(feat_inputs), feat_def)

        label_inputs: List[Variable] = []
        labels = None
        if ds.labels is not None:
            label_metas = [(a.dtype, a.shape[1:]) for a in ds.labels]
            label_inputs = [Input(shape=s, name=f"label_{i}")
                            for i, (d, s) in enumerate(label_metas)]
            packed = list(label_inputs)
            labels = (_pack(packed, ds._label_def)
                      if ds._label_def is not None else packed[0])

        fn_args = _fn_args(self.model_fn)
        kwargs: Dict[str, Any] = {}
        if "labels" in fn_args:
            kwargs["labels"] = labels
        elif labels is not None and mode == ModeKeys.TRAIN:
            raise ValueError("model_fn does not take labels, but input_fn "
                             "returns labels.")
        if "mode" in fn_args:
            kwargs["mode"] = mode
        if "params" in fn_args:
            kwargs["params"] = self.params
        if "config" in fn_args:
            kwargs["config"] = self.config
        spec = self.model_fn(features=features, **kwargs)
        if not isinstance(spec, TFEstimatorSpec):
            raise ValueError("model_fn must return a TFEstimatorSpec")

        # ORDER MATTERS: the first Model assigns the deterministic layer
        # names every later Model over the same nodes inherits.
        if spec.loss is not None:
            self._train_model = Model(feat_inputs + label_inputs, spec.loss)
        if spec.predictions is not None:
            pred_leaves, self._pred_def = _flatten(spec.predictions)
            self._predict_model = Model(feat_inputs, list(pred_leaves))
        self._graph_ds_sig = tuple((m.dtype, m.shape) for m in feat_metas)
        return spec

    def _ensure_graph(self, ds: TFDataset, mode: str):
        sig = tuple((np.dtype(a.dtype), a.shape[1:]) for a in ds.features)
        if (self._graph_ds_sig is None
                or (mode == ModeKeys.TRAIN and self._train_model is None)):
            # (re)build — the second case is predict-before-train, whose
            # label-less graph carries no loss output; nothing trained is
            # lost by rebuilding
            self._build_graph(ds, mode)
        elif sig != self._graph_ds_sig:
            raise ValueError(
                f"input_fn structure changed: graph was built for "
                f"{self._graph_ds_sig}, got {sig}")

    # -- checkpointing ------------------------------------------------------
    def _weights_path(self) -> Optional[str]:
        if self.model_dir is None:
            return None
        os.makedirs(self.model_dir, exist_ok=True)
        return os.path.join(self.model_dir, "estimator_weights.npz")

    def _save_weights(self):
        path = self._weights_path()
        if path is None or self._train_model is None:
            return
        leaves, _ = jax.tree_util.tree_flatten_with_path(
            self._train_model.params)
        np.savez(path, **{jax.tree_util.keystr(k): np.asarray(v)
                          for k, v in leaves})

    def _load_weights(self, model: Model, checkpoint_path: Optional[str]):
        path = checkpoint_path or self._weights_path()
        if path is None or not os.path.exists(path):
            return False
        data = np.load(path)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(model.params)
        restored = []
        for k, v in leaves:
            key = jax.tree_util.keystr(k)
            if key not in data:
                raise ValueError(f"checkpoint {path} missing weight {key}")
            saved = data[key]
            if saved.shape != np.shape(v):
                raise ValueError(f"checkpoint {path} weight {key} shape "
                                 f"{saved.shape} != model {np.shape(v)}")
            restored.append(jnp.asarray(saved, np.asarray(v).dtype))  # zoolint: disable=ZL009 one-time checkpoint restore; leaf shapes differ
        model.params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(model.params), restored)
        return True

    def _share_params_into_predict(self):
        """Copy trained params into the predict model by layer name (the
        models share layer objects, so keys line up exactly)."""
        if self._predict_model is None:
            return
        if self._predict_model.params is None:
            self._predict_model.init_weights()
        if self._train_model is None or self._train_model.params is None:
            return
        trained = self._train_model.params
        self._predict_model.params = {
            name: trained.get(name, p)
            for name, p in self._predict_model.params.items()}

    # -- the estimator contract --------------------------------------------
    def train(self, input_fn: Callable, steps: Optional[int] = None,
              batch_size: Optional[int] = None, nb_epoch: Optional[int] = None
              ) -> "TFEstimator":
        """``estimator.py:194`` — train until ``steps`` optimizer steps (the
        reference's MaxIteration), or ``nb_epoch`` epochs if given."""
        ds = _call_input_fn(input_fn, ModeKeys.TRAIN)
        if ds.labels is None:
            raise ValueError("training requires an input_fn with labels")
        self._ensure_graph(ds, ModeKeys.TRAIN)
        if self._train_model is None:
            raise ValueError("model_fn returned no loss; cannot train")
        m = self._train_model
        if m._compiled is None:
            if self.optimizer is None:
                raise ValueError(
                    "optimizer should be set when used for training. For "
                    "example: TFEstimator(model_fn, 'adam')")
            # the graph output IS the loss — identity objective (mean to
            # scalar), dummy zero labels feed the fit contract
            m.compile(optimizer=self.optimizer,
                      loss=lambda y_true, y_pred: jnp.mean(y_pred),
                      **self.optimizer_kwargs)
        bs = batch_size or ds.effective_batch()
        n = ds.n_examples
        steps_per_epoch = max(n // bs, 1)
        if nb_epoch is None:
            if steps is None:
                nb_epoch = 1
            else:
                nb_epoch = max(-(-steps // steps_per_epoch), 1)
        x = list(ds.features) + list(ds.labels)
        y = np.zeros((n,), np.float32)  # unused by the identity objective
        end = MaxIteration(steps) if steps is not None else None
        try:
            m.fit(x, y, batch_size=bs, nb_epoch=nb_epoch, end_trigger=end)
        finally:
            # fit consumed the arrays the predict model shared with this
            # one: it gets what fit left, however fit ended
            self._share_params_into_predict()
        self._save_weights()
        return self

    def predict(self, input_fn: Callable, batch_size: Optional[int] = None,
                checkpoint_path: Optional[str] = None):
        """``estimator.py:315`` — run the PREDICT graph; returns ndarray(s)
        packed like the model_fn's ``predictions`` structure."""
        ds = _call_input_fn(input_fn, ModeKeys.PREDICT)
        self._ensure_graph(ds, ModeKeys.PREDICT)
        if self._predict_model is None:
            raise ValueError("model_fn returned no predictions")
        if self._predict_model.params is None:
            self._share_params_into_predict()
        if checkpoint_path or (self._train_model is None
                               or self._train_model.params is None):
            if self._predict_model.params is None:
                self._predict_model.init_weights()
            self._load_weights(self._predict_model, checkpoint_path)
        bs = batch_size or ds.effective_batch()
        outs = self._predict_model.predict(ds.feature_arrays(), batch_size=bs)
        if not isinstance(outs, list):
            outs = [outs]
        return _pack(outs, self._pred_def)

    def evaluate(self, input_fn: Callable, eval_methods: Sequence[str],
                 steps: Optional[int] = None, batch_size: Optional[int] = None,
                 checkpoint_path: Optional[str] = None) -> Dict[str, float]:
        """``estimator.py:253`` — named metrics over the EVAL dataset.
        Supported: accuracy/acc, top5accuracy/top5acc, mae, mse, loss (the
        graph-computed loss, exact batch weighting)."""
        ds = _call_input_fn(input_fn, ModeKeys.EVAL)
        if ds.labels is None:
            raise ValueError("evaluate requires an input_fn with labels")
        self._ensure_graph(ds, ModeKeys.EVAL)
        bs = batch_size or ds.effective_batch()
        n = ds.n_examples
        if steps is not None:
            n = min(n, steps * bs)
        out: Dict[str, float] = {}

        wants_loss = any(m.lower() == "loss" for m in eval_methods)
        other = [m for m in eval_methods if m.lower() != "loss"]
        if other:
            if self._predict_model is None:
                raise ValueError("model_fn returned no predictions — only "
                                 "the 'loss' eval_method is available")
            preds = self.predict(lambda: TFDataset(ds.features),
                                 batch_size=bs)
            flat_preds, _ = _flatten(preds)
            p = np.asarray(flat_preds[0])[:n]
            y = np.asarray(ds.labels[0])[:n]
            for mname in other:
                out[mname] = _host_metric(mname, y, p)
        if wants_loss:
            out["loss"] = self._exact_loss(ds, bs, n)
        return out

    def _exact_loss(self, ds: TFDataset, bs: int, n: int) -> float:
        """Graph loss with exact batch weighting (no pad bias): jit once per
        distinct tail shape — at most two compiles."""
        m = self._train_model
        if m is None:
            raise ValueError("model_fn returned no loss")
        if m.params is None:
            m.init_weights()
            self._load_weights(m, None)

        @jax.jit
        def batch_loss(params, state, xs):
            val, _ = m.apply(params, state, xs, training=False, rng=None)
            return jnp.mean(val)

        total, count = 0.0, 0
        for i in range(0, n, bs):
            # per-BATCH bulk transfers; the loop blocks on the scalar
            # loss each batch anyway, so prefetching buys nothing here
            xs = ([jnp.asarray(a[i:i + bs]) for a in ds.features]  # zoolint: disable=ZL009
                  + [jnp.asarray(a[i:i + bs]) for a in ds.labels])  # zoolint: disable=ZL009
            k = len(ds.features[0][i:i + bs])
            total += float(batch_loss(m.params, m.net_state or {}, xs)) * k
            count += k
        return total / max(count, 1)


def _host_metric(name: str, y: np.ndarray, p: np.ndarray) -> float:
    key = name.lower()
    if key in ("acc", "accuracy"):
        cls = p.argmax(-1) if p.ndim > 1 and p.shape[-1] > 1 else \
            (p.reshape(len(p), -1)[:, 0] > 0.5).astype(np.int64)
        return float((cls == y.reshape(len(y), -1)[:, 0]).mean())
    if key in ("top5acc", "top5accuracy"):
        top5 = np.argsort(p, axis=-1)[:, -5:]
        y1 = y.reshape(len(y), -1)[:, 0]
        return float((top5 == y1[:, None]).any(axis=1).mean())
    if key == "mae":
        return float(np.abs(p.reshape(len(p), -1)
                            - y.reshape(len(y), -1)).mean())
    if key == "mse":
        return float(((p.reshape(len(p), -1)
                       - y.reshape(len(y), -1)) ** 2).mean())
    raise ValueError(f"unsupported eval_method {name!r}; choose from "
                     f"accuracy, top5accuracy, mae, mse, loss")
