"""BERTClassifier + HuggingFace/torch weight import — parity with
``pyzoo/zoo/tfpark/text/estimator/bert_classifier.py`` (the reference fine-
tunes a TF BERT under a TFEstimator; here the native ``layers.BERT`` encoder
fine-tunes under the ordinary jitted compile/fit stack) and TFPark's
checkpoint-import role (``bert_estimator.py`` init_from_checkpoint).

Numerical parity with the transformers implementation is golden-tested in
``tests/test_bert_oracle.py`` (same weights → same sequence/pooled outputs).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..pipeline.api.keras.engine import Layer, dispatch_layer
from ..pipeline.api.keras.layers import BERT, Dense, Dropout
from ..models.common.zoo_model import ZooModel, register_model


class _BertClassifierNet(Layer):
    """BERT encoder → pooled → dropout → softmax head, as one Layer."""

    def __init__(self, spec: "BERTClassifier", **kwargs):
        super().__init__(**kwargs)
        self.spec = spec
        self.bert = BERT(vocab=spec.vocab, hidden_size=spec.hidden_size,
                         n_block=spec.n_block, n_head=spec.n_head,
                         seq_len=spec.seq_len,
                         intermediate_size=spec.intermediate_size,
                         hidden_drop=spec.hidden_drop,
                         attn_drop=spec.attn_drop,
                         name=f"{self.name}_bert")
        self.drop = Dropout(spec.hidden_drop, name=f"{self.name}_drop")
        self.cls = Dense(spec.num_classes, activation="softmax",
                         name=f"{self.name}_cls")

    @property
    def input_shape(self):
        t = self.spec.seq_len
        return [(None, t)] * 4

    def build(self, rng, input_shape=None):
        shapes = input_shape or self.input_shape
        k1, k2 = jax.random.split(rng)
        return {"bert": self.bert.build(k1, shapes),
                "cls": self.cls.build(k2, (None, self.spec.hidden_size))}

    def initial_state(self, input_shape=None):
        return {}

    def param_sharding(self, params):
        # without this the Layer default replicates every leaf and a
        # `model` mesh axis buys no tensor parallelism at all
        return {"bert": self.bert.param_sharding(params["bert"]),
                "cls": self.cls.param_sharding(params["cls"])}

    def call(self, params, x, *, training=False, rng=None):
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        _, pooled = self.bert.call(params["bert"], x, training=training,
                                   rng=r1)
        pooled = self.drop.call({}, pooled, training=training, rng=r2)
        # the head goes through dispatch_layer so loss resolution can fuse
        # it (keras/fused_loss.py) and the inference runtime can calibrate/
        # quantize it like any container-dispatched Dense
        y, _ = dispatch_layer(self.cls, params["cls"], {}, pooled,
                              training=training, rng=None)
        return y

    def fused_head(self):
        """Fused LM-head loss resolution (``keras/fused_loss.py``)."""
        return self.cls, ("cls",)


@register_model
class BERTClassifier(ZooModel):
    """``BERTClassifier(num_classes, bert_config...)`` — input
    ``[token_ids, token_type_ids, position_ids, attention_mask]`` (each
    (B, seq_len); build them with ``make_inputs``)."""

    def __init__(self, num_classes: int, vocab: int = 40990,
                 hidden_size: int = 768, n_block: int = 12, n_head: int = 12,
                 seq_len: int = 512, intermediate_size: int = 3072,
                 hidden_drop: float = 0.1, attn_drop: float = 0.1,
                 name: Optional[str] = None):
        self.num_classes = int(num_classes)
        self.vocab = int(vocab)
        self.hidden_size = int(hidden_size)
        self.n_block = int(n_block)
        self.n_head = int(n_head)
        self.seq_len = int(seq_len)
        self.intermediate_size = int(intermediate_size)
        self.hidden_drop = float(hidden_drop)
        self.attn_drop = float(attn_drop)
        super().__init__(name=name)

    def build_model(self) -> _BertClassifierNet:
        return _BertClassifierNet(self, name=self.name + "_net")

    def get_config(self) -> Dict[str, Any]:
        return {"num_classes": self.num_classes, "vocab": self.vocab,
                "hidden_size": self.hidden_size, "n_block": self.n_block,
                "n_head": self.n_head, "seq_len": self.seq_len,
                "intermediate_size": self.intermediate_size,
                "hidden_drop": self.hidden_drop,
                "attn_drop": self.attn_drop}

    def make_inputs(self, token_ids: np.ndarray,
                    token_type_ids: Optional[np.ndarray] = None,
                    attention_mask: Optional[np.ndarray] = None):
        """[ids, token_type, position, mask] from just token ids."""
        return make_bert_inputs(token_ids, token_type_ids, attention_mask)

    def load_pretrained(self, state_dict: Mapping[str, Any]) -> "BERTClassifier":
        """Install encoder weights from a HuggingFace/torch BERT
        ``state_dict`` (classifier head keeps its fresh init — the
        fine-tuning setup of ``bert_classifier.py``)."""
        return install_pretrained_bert(self, state_dict)


def make_bert_inputs(token_ids: np.ndarray,
                     token_type_ids: Optional[np.ndarray] = None,
                     attention_mask: Optional[np.ndarray] = None):
    """[ids, token_type, position, mask] from just token ids — the input
    assembly every BERT estimator shares."""
    ids = np.asarray(token_ids, np.int32)
    b, t = ids.shape
    tt = (np.asarray(token_type_ids, np.int32)
          if token_type_ids is not None else np.zeros((b, t), np.int32))
    pos = np.tile(np.arange(t, dtype=np.int32), (b, 1))
    mask = (np.asarray(attention_mask, np.float32)
            if attention_mask is not None else np.ones((b, t), np.float32))
    return [ids, tt, pos, mask]


def install_pretrained_bert(model, state_dict: Mapping[str, Any]):
    """Install torch BERT encoder weights into a ZooModel whose param tree
    has a ``"bert"`` entry; the task head keeps its fresh init."""
    if model.params is None:
        model.init_weights()
    bert_params = bert_params_from_torch(state_dict, model.n_block)
    params = dict(model.params)
    params["bert"] = _check_tree_shapes(model.params["bert"], bert_params)
    model.params = params
    return model


def _check_tree_shapes(template, loaded):
    t_leaves, t_def = jax.tree_util.tree_flatten(template)
    l_leaves, l_def = jax.tree_util.tree_flatten(loaded)
    if t_def != l_def:
        raise ValueError(f"imported BERT structure mismatch:\n{t_def}\nvs\n{l_def}")
    for a, b in zip(t_leaves, l_leaves):
        if np.shape(a) != np.shape(b):
            raise ValueError(f"shape mismatch: expected {np.shape(a)}, "
                             f"imported {np.shape(b)}")
    return jax.tree_util.tree_unflatten(
        t_def, [jnp.asarray(np.asarray(b), a.dtype)
                for a, b in zip(t_leaves, l_leaves)])


def bert_params_from_torch(state_dict: Mapping[str, Any],
                           n_block: int) -> Dict[str, Any]:
    """Map a transformers ``BertModel.state_dict()`` onto the native
    ``layers.BERT`` param tree. torch ``Linear.weight`` is (out, in) —
    transposed into this package's (in, out) ``W`` layout; per-head q/k/v
    projections concatenate into the fused qkv kernel."""

    def t(key):  # tensor → np, transposing Linear kernels at call sites
        v = state_dict[key]
        return np.asarray(v.detach().cpu().numpy()
                          if hasattr(v, "detach") else v)

    def dense(prefix):
        return {"W": t(f"{prefix}.weight").T, "b": t(f"{prefix}.bias")}

    def ln(prefix):
        return {"gamma": t(f"{prefix}.weight"), "beta": t(f"{prefix}.bias")}

    p: Dict[str, Any] = {
        "word": t("embeddings.word_embeddings.weight"),
        "position": t("embeddings.position_embeddings.weight"),
        "token_type": t("embeddings.token_type_embeddings.weight"),
        "emb_ln": ln("embeddings.LayerNorm"),
        "pooler": dense("pooler.dense"),
    }
    for i in range(n_block):
        b = f"encoder.layer.{i}"
        qkv_w = np.concatenate([t(f"{b}.attention.self.{m}.weight").T
                                for m in ("query", "key", "value")], axis=1)
        qkv_b = np.concatenate([t(f"{b}.attention.self.{m}.bias")
                                for m in ("query", "key", "value")])
        p[f"block{i}"] = {
            "attn": {"qkv": {"W": qkv_w, "b": qkv_b},
                     "proj": dense(f"{b}.attention.output.dense")},
            "ln1": ln(f"{b}.attention.output.LayerNorm"),
            "fc": dense(f"{b}.intermediate.dense"),
            "out": dense(f"{b}.output.dense"),
            "ln2": ln(f"{b}.output.LayerNorm"),
        }
    return p
