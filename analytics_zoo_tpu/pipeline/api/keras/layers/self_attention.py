"""Transformer layers — parity with the reference's attention stack
(``pipeline/api/keras/layers/TransformerLayer.scala:56``, ``BERT.scala:66``,
pyzoo ``pipeline/api/keras/layers/self_attention.py``).

* ``MultiHeadSelfAttention`` — fused QKV projection (one (B*T, H) x (H, 3H)
  matmul onto the MXU) + the swappable attention core in
  ``ops/attention.py``.
* ``TransformerBlock`` — post-LN residual block (attention → add&norm →
  gelu FFN → add&norm), the layout both the reference's GPT-style
  TransformerLayer and BERT use.
* ``TransformerLayer`` — word+position embeddings + N causal blocks
  (``bidirectional=False`` ≙ the reference's maskAttention GPT mode).
* ``BERT`` — word+position+token-type embeddings, N bidirectional blocks with
  an attention mask input, plus the tanh pooler over [CLS].
* ``DecoderAttention`` / ``DecoderBlock`` / ``DecoderStack`` — the pre-norm
  decoder of today's open models: RMSNorm, rotary positions (plain or YaRN)
  in place of a position table, grouped key/value heads with a head size
  of their own, a causal window per layer (``layer_types``), no biases, and
  any feed-forward layer per block (``RoutedExperts``,
  ``GatedFeedForward``) and, through ``DecoderStack(attn=)``, any mixer
  per block; optionally q/k normalisation per head and a head tied to the
  token table (``TiedHead``). The post-LN classes above keep their
  behaviour and their parameter trees.
* ``LatentAttention`` — multi-head latent attention (MLA): queries and
  keys/values through low-rank latents with an RMSNorm each, rotary
  positions on a slice of the head only, one rotary key head shared by all
  heads. Training computes the expanded form.
* ``ShortConvMixer`` — the LFM2 family's gated short convolution: a causal
  depthwise convolution of a few taps between two input-dependent gates,
  a block's mixer where it is not attention (``layer_types`` ``"conv"``).
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.attention import (apply_rotary,
                                             apply_rotary_in_place,
                                             rotary_tables_in_place,
                                             dot_product_attention,
                                             merge_heads, rotary_inv_freq,
                                             rotary_tables, split_heads)
from ..engine import (Layer, compute_dtype, dispatch_layer, get_initializer,
                      param_dtype)
from .normalization import LayerNorm, RMSNorm


def _dense_params(rng, d_in, d_out, init="glorot_uniform"):
    return {"W": get_initializer(init)(rng, (d_in, d_out), param_dtype()),
            "b": jnp.zeros((d_out,), param_dtype())}


def _dense(p, x, cd):
    y = jnp.einsum("...d,dk->...k", x.astype(cd), p["W"].astype(cd),
                   preferred_element_type=jnp.float32).astype(cd)
    return y + p["b"].astype(cd)


def _dropout(x, rate, rng, training):
    if not training or rate <= 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _stack_param_sharding(blocks, params, embed_keys=()):
    """Shared TP spec for the transformer stacks: per-block specs from the
    block layers, embedding TABLES sharded over their hidden dim (the same
    ``P(None, model)`` rule the standalone ``Embedding`` layer declares —
    the word table is BERT's largest tensor and must not replicate per
    model shard), everything else replicated."""
    from jax.sharding import PartitionSpec as P

    from .....parallel.mesh import MODEL_AXIS
    spec = {}
    for k, v in params.items():
        if k.startswith("block"):
            continue
        spec[k] = (P(None, MODEL_AXIS) if k in embed_keys
                   else jax.tree.map(lambda _: None, v))
    for i, blk in enumerate(blocks):
        spec[f"block{i}"] = blk.param_sharding(params[f"block{i}"])
    return spec


class MultiHeadSelfAttention(Layer):
    """Fused-QKV multi-head self-attention. Input (B, T, H) (optionally with a
    (B, 1, 1, T) keep-mask) → (B, T, H). Device time shows under
    ``zoo_attn.proj`` (the fused q/k/v product), ``zoo_attn.attend`` (the
    flash kernels, the XLA softmax attention or the ring, with the heads'
    layout round them) and ``zoo_attn.out`` (the output product and its
    dropout)."""

    layer_scope = False

    def __init__(self, hidden_size: int, n_head: int, causal: bool = False,
                 attn_drop: float = 0.0, out_drop: float = 0.0, **kwargs):
        super().__init__(**kwargs)
        if hidden_size % n_head != 0:
            raise ValueError(f"hidden_size {hidden_size} not divisible by "
                             f"n_head {n_head}")
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.causal = causal
        self.attn_drop = attn_drop
        self.out_drop = out_drop

    def build(self, rng, input_shape):
        k1, k2 = jax.random.split(rng)
        return {"qkv": _dense_params(k1, self.hidden_size, 3 * self.hidden_size),
                "proj": _dense_params(k2, self.hidden_size, self.hidden_size)}

    def param_sharding(self, params):
        """Attention TP: fused QKV column-parallel (output dim over
        ``model``), output projection row-parallel. Numerics equal the
        replicated form (equality-tested in ``test_parallel``). NOTE the
        fused ``[q|k|v]`` column layout is NOT head-interleaved, so GSPMD
        reshards the qkv activation at the head split instead of keeping
        whole heads shard-local (true Megatron fusion interleaves per
        head — future work); the annotation still shards the two big
        matmuls and their gradients."""
        from jax.sharding import PartitionSpec as P

        from .....parallel.mesh import MODEL_AXIS
        return {"qkv": {"W": P(None, MODEL_AXIS), "b": P(MODEL_AXIS)},
                "proj": {"W": P(MODEL_AXIS, None), "b": P()}}

    @staticmethod
    def _kv_mask(mask):
        """Reduce a broadcastable attention mask to the (B, Tk) key-padding
        form the flash kernel streams blockwise; None if it can't be (a
        genuinely per-query mask stays on the XLA op)."""
        if mask is None:
            return None
        if mask.ndim == 2:                      # (B, Tk)
            return mask
        if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
            return mask[:, 0, 0, :]             # (B, 1, 1, Tk)
        return None

    #: auto mode hands sequences this long to the flash kernel: below it the
    #: fused XLA softmax-attention wins (measured on a v5e, BERT-base bf16:
    #: XLA is 1.11x flash at T=512 and 1.06x at T=1024), at/above it the
    #: O(T²) HBM materialization dominates — XLA fails to even compile
    #: BERT-base at T=2048 on a 16 GB chip, where the blockwise kernel
    #: trains fine (52k tok/s; 4k/32k numbers in BENCH long_context).
    FLASH_AUTO_MIN_SEQ = 2048

    def _use_flash(self, mask, drop, seq_len: int) -> bool:
        """The pallas flash kernel covers key-padding masks (the BERT
        ``attention_mask`` form) and mask-free attention, forward AND
        backward; in-kernel dropout and per-query masks stay on the XLA op.
        ``zoo.pallas.attention``: True/False force it; ``auto`` (default)
        enables it on TPU backends for sequences ≥ FLASH_AUTO_MIN_SEQ (the
        CPU interpreter path is for tests, not speed)."""
        if drop > 0.0:
            return False
        if mask is not None and self._kv_mask(mask) is None:
            return False
        from .....parallel import mesh as mesh_lib
        if mesh_lib.global_mesh().shape[mesh_lib.MODEL_AXIS] > 1:
            # the flash entry splits the batch over `data` under shard_map
            # and nothing else: heads sharded over `model` stay on the XLA
            # op, which GSPMD partitions itself
            return False
        from .....common.context import tri_state_conf
        flag = tri_state_conf("zoo.pallas.attention")
        if flag == "auto":
            return (jax.default_backend() == "tpu"
                    and seq_len >= self.FLASH_AUTO_MIN_SEQ)
        return flag

    def _seq_fallback(self, reason: str, probe: bool = False):
        """A seq mesh exists but this call can't ride it. Default: warn ONCE
        — falling back to full O(T^2) attention at long-context scale is an
        OOM surprise, not a detail. ``zoo.seq.strict=True`` — or a
        training-loop-forced mode (``zoo.train.seq_attention``, which is
        an explicit contract): raise instead (VERDICT r4 weak #6 — a user
        who built a seq mesh must not silently get zero sequence
        parallelism)."""
        from .....common.context import get_zoo_context
        from ..seq_pipe import forced_seq_mode
        strict = (bool(get_zoo_context().get("zoo.seq.strict", False))
                  or forced_seq_mode() in ("ring", "ulysses"))
        if strict and not probe:
            raise RuntimeError(
                f"{self.name}: zoo.seq.strict is set and {reason} — "
                f"attention cannot ride the seq mesh (it would silently "
                f"fall back to full XLA attention)")
        if not getattr(self, "_warned_no_ring", False) and not probe:
            import logging
            logging.getLogger("analytics_zoo_tpu.attention").warning(
                "%s: seq-axis mesh active but %s — full O(T^2) attention "
                "for this layer (no sequence parallelism)", self.name,
                reason)
            self._warned_no_ring = True
        return None

    def _ring_mesh(self, mask, drop, seq_len, rng=None):
        """Sequence parallelism from the LAYER API: on a mesh with a ``seq``
        axis, attention shards the sequence dim over ICI — KV-rotation ring
        or Ulysses head/seq all-to-all (``parallel/ring_attention.py``) —
        instead of gathering the full sequence per chip: the long-context
        path (SURVEY §5). Key-padding masks (the BERT ``attention_mask``
        form) stream with the ring / all-gather under Ulysses; attention
        dropout runs in-ring with block-position-keyed masks. Only
        genuinely per-query masks (and dropout without an rng) stay on the
        full XLA op."""
        from ..seq_pipe import forced_seq_mode
        if forced_seq_mode() == "off":
            # inside a pipeline stage (or an explicit disable scope):
            # no seq routing, no warning — the caller made the choice
            return None
        from .....parallel import mesh as mesh_lib
        mesh = mesh_lib.global_mesh()
        n_seq = mesh.shape[mesh_lib.SEQ_AXIS]
        if n_seq <= 1:
            return None
        # shape-inference probes (placeholder batch dims) must neither warn
        # nor raise strict errors — and must not burn the warn-once flag
        # before the real call gets to warn
        from ..engine import in_shape_probe
        probe = in_shape_probe()
        if drop > 0.0 and rng is None:
            return self._seq_fallback(
                f"attn_drop={drop} with no rng (training=True without a "
                f"PRNG key cannot draw in-ring dropout masks)",
                probe=probe)
        if mask is not None and self._kv_mask(mask) is None:
            return self._seq_fallback(
                "the mask is per-query (not reducible to (B, Tk) "
                "key-padding form)", probe=probe)
        batch, t = seq_len  # (B, T): both must split over their axes
        if t % n_seq == 0 and batch % mesh.shape[mesh_lib.DATA_AXIS] == 0:
            return mesh
        return self._seq_fallback(
            f"shapes can't split (T={t} over seq={n_seq}, B={batch} over "
            f"data={mesh.shape[mesh_lib.DATA_AXIS]})", probe=probe)

    def _seq_routing(self, n_seq: int) -> str:
        """``zoo.seq.mode``: ``ring`` (default), ``ulysses``, or ``auto``
        (ulysses when n_head divides the seq axis — two all-to-alls beat
        n-1 ppermutes when the dense local score block fits). A
        training-loop-forced mode (``zoo.train.seq_attention``, scoped
        over the step trace) wins over the layer-level knob."""
        from .....common.context import get_zoo_context
        from ..seq_pipe import forced_seq_mode
        forced = forced_seq_mode()
        if forced in ("ring", "ulysses"):
            mode = forced
        else:
            mode = str(get_zoo_context().get("zoo.seq.mode", "ring")).lower()
        if mode not in ("ring", "ulysses", "auto"):
            raise ValueError(f"zoo.seq.mode must be ring|ulysses|auto, "
                             f"got {mode!r}")
        if mode == "ulysses" and self.n_head % n_seq != 0:
            raise ValueError(
                f"zoo.seq.mode=ulysses needs n_head ({self.n_head}) "
                f"divisible by the seq axis ({n_seq})")
        if mode == "auto":
            mode = "ulysses" if self.n_head % n_seq == 0 else "ring"
        return mode

    def call(self, params, x, *, training=False, rng=None):
        mask = None
        if isinstance(x, (list, tuple)):
            x, mask = x
        cd = compute_dtype()
        with jax.named_scope("zoo_attn.proj"):
            qkv = _dense(params["qkv"], x, cd)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        drop = self.attn_drop if training else 0.0
        ring_mesh = self._ring_mesh(mask, drop, q.shape[:2], rng=r1)
        # the flash entry finds a head where the projection wrote it; the
        # ring and the XLA op take the heads split out
        flash = ring_mesh is None and self._use_flash(mask, drop, q.shape[1])
        with jax.named_scope("zoo_attn.attend"):
            out = self._attend(q, k, v, mask, drop, r1, ring_mesh, flash)
        with jax.named_scope("zoo_attn.out"):
            out = _dense(params["proj"], out, cd)
            return _dropout(out, self.out_drop, r2, training)

    def _attend(self, q, k, v, mask, drop, rng, ring_mesh, flash):
        """Attention over q, k, v (B, T, H) on the branch ``call`` chose;
        (B, T, H)."""
        qh, kh, vh = (_heads(a, self.n_head, flash) for a in (q, k, v))
        if ring_mesh is not None:
            from .....parallel import mesh as mesh_lib
            from .....parallel.ring_attention import (ring_self_attention,
                                                      ulysses_self_attention)
            kv_mask = self._kv_mask(mask)
            if kv_mask is not None:
                kv_mask = kv_mask.astype(jnp.bool_)
            n_seq = ring_mesh.shape[mesh_lib.SEQ_AXIS]
            route = (ulysses_self_attention
                     if self._seq_routing(n_seq) == "ulysses"
                     else ring_self_attention)
            out = route(qh, kh, vh, mesh=ring_mesh, causal=self.causal,
                        mask=kv_mask, dropout_rate=drop,
                        dropout_rng=rng if drop > 0.0 else None)
        elif flash:
            from .....ops.pallas import flash_attention
            out = flash_attention(qh, kh, vh, mask=self._kv_mask(mask),
                                  causal=self.causal)
        else:
            out = dot_product_attention(qh, kh, vh, mask=mask,
                                        causal=self.causal,
                                        dropout_rate=drop, dropout_rng=rng)
        return _merged(out, flash)


class TransformerBlock(Layer):
    """Post-LN residual block: x = LN1(x + Attn(x)); x = LN2(x + FFN(x)).
    FFN = gelu (``TransformerLayer.scala`` uses gelu, as does BERT). Device
    time shows under ``zoo_norm`` (each add and its LayerNorm) and
    ``zoo_ffn.dense`` (the two products, gelu, dropout), the attention's
    under its own ``zoo_attn.*``."""

    layer_scope = False

    def __init__(self, hidden_size: int, n_head: int,
                 intermediate_size: Optional[int] = None,
                 causal: bool = False, hidden_drop: float = 0.0,
                 attn_drop: float = 0.0, epsilon: float = 1e-5,
                 gelu_approximate: bool = True, **kwargs):
        super().__init__(**kwargs)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.hidden_drop = hidden_drop
        self.gelu_approximate = gelu_approximate  # BERT parity needs exact
        self.attn = MultiHeadSelfAttention(
            hidden_size, n_head, causal=causal, attn_drop=attn_drop,
            out_drop=hidden_drop, name=(kwargs.get("name") or "tb") + "_attn")
        self.ln1 = LayerNorm(epsilon=epsilon)
        self.ln2 = LayerNorm(epsilon=epsilon)

    def build(self, rng, input_shape):
        shape = input_shape[0] if isinstance(input_shape, list) else input_shape
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        return {
            "attn": self.attn.build(k1, shape),
            "ln1": self.ln1.build(k2, shape),
            "fc": _dense_params(k3, self.hidden_size, self.intermediate_size),
            "out": _dense_params(k4, self.intermediate_size, self.hidden_size),
            "ln2": self.ln2.build(k5, shape),
        }

    def param_sharding(self, params):
        """Megatron block TP: attention specs from the attention layer, MLP
        fc column-parallel / out row-parallel, LayerNorms replicated."""
        from jax.sharding import PartitionSpec as P

        from .....parallel.mesh import MODEL_AXIS
        return {
            "attn": self.attn.param_sharding(params["attn"]),
            "ln1": jax.tree.map(lambda _: None, params["ln1"]),
            "fc": {"W": P(None, MODEL_AXIS), "b": P(MODEL_AXIS)},
            "out": {"W": P(MODEL_AXIS, None), "b": P()},
            "ln2": jax.tree.map(lambda _: None, params["ln2"]),
        }

    def call(self, params, x, *, training=False, rng=None):
        mask = None
        if isinstance(x, (list, tuple)):
            x, mask = x
        r1 = r2 = None
        if rng is not None:
            r1, r2 = jax.random.split(rng)
        cd = compute_dtype()
        a = self.attn.call(params["attn"], [x, mask] if mask is not None else x,
                           training=training, rng=r1)
        with jax.named_scope("zoo_norm"):
            x = self.ln1.call(params["ln1"], x + a)
        with jax.named_scope("zoo_ffn.dense"):
            h = jax.nn.gelu(_dense(params["fc"], x, cd),
                            approximate=self.gelu_approximate)
            h = _dropout(_dense(params["out"], h, cd), self.hidden_drop, r2,
                         training)
        with jax.named_scope("zoo_norm"):
            return self.ln2.call(params["ln2"], x + h)


class TransformerLayer(Layer):
    """GPT-style decoder stack — ``TransformerLayer.scala:56`` /
    pyzoo ``self_attention.py``. Input int ids (B, T) → hidden states
    (B, T, H). ``bidirectional=False`` applies the causal mask (the
    reference's ``maskAttention``). The two lookups, their sum and the
    embedding dropout run under the device scope ``zoo_embed``."""

    layer_scope = False

    def __init__(self, vocab: int, seq_len: int, n_block: int = 12,
                 hidden_size: int = 768, n_head: int = 12,
                 hidden_drop: float = 0.1, attn_drop: float = 0.1,
                 embedding_drop: float = 0.1, bidirectional: bool = False,
                 initializer_range: float = 0.02, **kwargs):
        super().__init__(**kwargs)
        self.vocab = vocab
        self.seq_len = seq_len
        self.n_block = n_block
        self.hidden_size = hidden_size
        self.embedding_drop = embedding_drop
        self.initializer_range = initializer_range
        self.blocks = [
            TransformerBlock(hidden_size, n_head, causal=not bidirectional,
                             hidden_drop=hidden_drop, attn_drop=attn_drop,
                             name=f"{self.name}_block{i}")
            for i in range(n_block)
        ]

    def build(self, rng, input_shape):
        keys = jax.random.split(rng, self.n_block + 2)
        std = self.initializer_range
        p: Dict[str, Any] = {
            "wte": jax.random.normal(keys[0], (self.vocab, self.hidden_size),
                                     param_dtype()) * std,
            "wpe": jax.random.normal(keys[1], (self.seq_len, self.hidden_size),
                                     param_dtype()) * std,
        }
        h_shape = (input_shape[0], input_shape[1], self.hidden_size)
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk.build(keys[i + 2], h_shape)
        return p

    def param_sharding(self, params):
        return _stack_param_sharding(self.blocks, params,
                                     embed_keys=("wte", "wpe"))

    def call(self, params, x, *, training=False, rng=None):
        ids = x.astype(jnp.int32)
        t = ids.shape[1]
        r = rng
        with jax.named_scope("zoo_embed"):
            h = (jnp.take(params["wte"], ids, axis=0)
                 + params["wpe"][None, :t, :]).astype(compute_dtype())
            if rng is not None:
                r, re = jax.random.split(rng)
                h = _dropout(h, self.embedding_drop, re, training)
        for i, blk in enumerate(self.blocks):
            br = jax.random.fold_in(r, i) if r is not None else None
            h = blk.call(params[f"block{i}"], h, training=training, rng=br)
        return h


class BERT(Layer):
    """BERT encoder — ``BERT.scala:66``. Input
    ``[token_ids, token_type_ids, position_ids, attention_mask]`` (mask is
    (B, 1, 1, T), 1.0 = attend) → ``[sequence_output, pooled_output]``.
    The three lookups, their LayerNorm and dropout run under the device
    scope ``zoo_embed``, the pooler (a head) under ``zoo_loss``."""

    layer_scope = False

    def __init__(self, vocab: int = 40990, hidden_size: int = 768,
                 n_block: int = 12, n_head: int = 12, seq_len: int = 512,
                 intermediate_size: int = 3072, hidden_drop: float = 0.1,
                 attn_drop: float = 0.1, initializer_range: float = 0.02,
                 type_vocab: int = 2, **kwargs):
        super().__init__(**kwargs)
        self.vocab = vocab
        self.hidden_size = hidden_size
        self.n_block = n_block
        self.seq_len = seq_len
        self.type_vocab = type_vocab
        self.hidden_drop = hidden_drop
        self.initializer_range = initializer_range
        self.emb_ln = LayerNorm(epsilon=1e-12)
        self.blocks = [
            TransformerBlock(hidden_size, n_head,
                             intermediate_size=intermediate_size,
                             causal=False, hidden_drop=hidden_drop,
                             attn_drop=attn_drop, epsilon=1e-12,
                             gelu_approximate=False,  # BERT's erf-form gelu
                             name=f"{self.name}_block{i}")
            for i in range(n_block)
        ]

    def build(self, rng, input_shape):
        shapes = input_shape if isinstance(input_shape, list) else [input_shape]
        b, t = shapes[0][0], shapes[0][1]
        keys = jax.random.split(rng, self.n_block + 5)
        std = self.initializer_range
        p: Dict[str, Any] = {
            "word": jax.random.normal(keys[0], (self.vocab, self.hidden_size),
                                      param_dtype()) * std,
            "position": jax.random.normal(
                keys[1], (self.seq_len, self.hidden_size), param_dtype()) * std,
            "token_type": jax.random.normal(
                keys[2], (self.type_vocab, self.hidden_size),
                param_dtype()) * std,
            "emb_ln": self.emb_ln.build(keys[3], (b, t, self.hidden_size)),
            "pooler": _dense_params(keys[4], self.hidden_size,
                                    self.hidden_size),
        }
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk.build(keys[i + 5] if self.n_block else keys[4],
                                       (b, t, self.hidden_size))
        return p

    def param_sharding(self, params):
        return _stack_param_sharding(
            self.blocks, params,
            embed_keys=("word", "position", "token_type"))

    def call(self, params, x, *, training=False, rng=None):
        if not isinstance(x, (list, tuple)) or len(x) != 4:
            raise ValueError(
                f"{self.name}: BERT expects [token_ids, token_type_ids, "
                f"position_ids, attention_mask]")
        ids, token_type, pos, mask = x
        cd = compute_dtype()
        # cast tables to the compute dtype BEFORE the gather: halves the
        # gather read and (more importantly) the backward scatter-add
        # traffic under bf16 — the table-sized cast is one cheap pass
        r = rng
        with jax.named_scope("zoo_embed"):
            h = (jnp.take(params["word"].astype(cd), ids.astype(jnp.int32),
                          axis=0)
                 + jnp.take(params["position"].astype(cd),
                            pos.astype(jnp.int32), axis=0)
                 + jnp.take(params["token_type"].astype(cd),
                            token_type.astype(jnp.int32), axis=0))
            h = self.emb_ln.call(params["emb_ln"], h).astype(cd)
            if rng is not None:
                r, re = jax.random.split(rng)
                h = _dropout(h, self.hidden_drop, re, training)
        if mask is not None and mask.ndim == 2:  # (B, T) → (B, 1, 1, T)
            mask = mask[:, None, None, :]
        for i, blk in enumerate(self.blocks):
            br = jax.random.fold_in(r, i) if r is not None else None
            h = blk.call(params[f"block{i}"], [h, mask], training=training,
                         rng=br)
        with jax.named_scope("zoo_loss"):
            pooled = jnp.tanh(_dense(params["pooler"], h[:, 0, :], cd))
        return [h, pooled]


# ---------------------------------------------------------------------------
# the pre-norm decoder
# ---------------------------------------------------------------------------

def _project(w, x, cd):
    return jnp.einsum("...d,dk->...k", x.astype(cd), w.astype(cd),
                      preferred_element_type=jnp.float32).astype(cd)


def _heads(x, n_head: int, in_place: bool):
    """(B, T, n_head * d) as the attention op of the branch takes it: the
    flash entry's (B, T, n_head, d), a reshape that moves nothing, or
    ``split_heads``' (B, n_head, T, d) for the XLA op."""
    if not in_place:
        return split_heads(x, n_head)
    return x.reshape(x.shape[:2] + (n_head, x.shape[2] // n_head))


def _merged(out, in_place: bool):
    """The attention op's output as (B, T, n_head * d)."""
    return out.reshape(out.shape[:2] + (-1,)) if in_place \
        else merge_heads(out)


class DecoderAttention(MultiHeadSelfAttention):
    """Causal self-attention of a pre-norm decoder: ``n_head`` query heads
    and ``n_kv_head`` key/value heads of ``head_dim`` (query head ``h``
    attends key/value head ``h // (n_head / n_kv_head)``; ``n_head *
    head_dim`` need not be the hidden size), rotary positions on q and k,
    an optional ``window`` (None = full: a query sees the ``window`` latest
    keys with its own position), no biases. ``rotary`` is a
    ``rope_parameters`` entry (``ops.attention.rotary_inv_freq``); its
    frequencies and attention factor are made once, here. Routing between
    the Pallas flash kernels and the XLA op is ``_use_flash``'s, as for
    ``MultiHeadSelfAttention``. ``qk_norm=True`` (off by default: the
    parameter tree then has the four matrices alone) puts an RMSNorm over
    each head's ``head_dim`` columns of q and of k before the rotation,
    one weight vector of ``head_dim`` each (``q_norm``, ``k_norm``) shared
    by the heads, at ``epsilon``; device time under ``zoo_attn.qk_norm``,
    beside ``zoo_attn.proj`` (the three products), ``zoo_attn.rope``,
    ``zoo_attn.attend`` and ``zoo_attn.out`` (``Wo``).
    Input (B, T, H), or ``[x, (cos, sin)]`` with the rotary tables of the
    call's positions (``DecoderStack`` forms them once per kind of
    layer)."""

    def __init__(self, hidden_size: int, n_head: int, n_kv_head: int,
                 head_dim: int, rotary: Mapping[str, Any],
                 window: Optional[int] = None, qk_norm: bool = False,
                 epsilon: float = 1e-6, **kwargs):
        Layer.__init__(self, **kwargs)
        if n_head % n_kv_head:
            raise ValueError(f"n_head {n_head} not divisible by n_kv_head "
                             f"{n_kv_head}")
        self.hidden_size = hidden_size
        self.n_head, self.n_kv_head, self.head_dim = n_head, n_kv_head, head_dim
        self.causal = True
        self.window = window
        self.qk_norm = RMSNorm(epsilon=epsilon) if qk_norm else None
        self.inv_freq, self.rotary_scale = rotary_inv_freq(head_dim, rotary)

    def build(self, rng, input_shape):
        k = jax.random.split(rng, 4)
        init = get_initializer("glorot_uniform")
        h, q, kv = (self.hidden_size, self.n_head * self.head_dim,
                    self.n_kv_head * self.head_dim)
        p = {"Wq": init(k[0], (h, q), param_dtype()),
             "Wk": init(k[1], (h, kv), param_dtype()),
             "Wv": init(k[2], (h, kv), param_dtype()),
             "Wo": init(k[3], (q, h), param_dtype())}
        if self.qk_norm is not None:
            p["q_norm"] = self.qk_norm.build(k[0], (self.head_dim,))
            p["k_norm"] = self.qk_norm.build(k[1], (self.head_dim,))
        return p

    def param_sharding(self, params):
        return jax.tree.map(lambda _: None, params)

    def _rotates_in_place(self, t: int) -> bool:
        """Whether a call at ``t`` positions rotates q and k as
        (B, T, heads * head_dim): on the flash branch, whose entry finds a
        head where the projection wrote it, unless a q/k norm needs a
        head's columns as an axis of their own."""
        return self.qk_norm is None and self._use_flash(None, 0.0, t)

    def tables(self, t: int):
        """float32 (cos, sin) of positions 0..t-1 for this layer's kind,
        each (t, head_dim); where the call rotates in place
        (``_rotates_in_place``), spread over the query heads' lanes
        (``rotary_tables_in_place``: (t, n_head * head_dim), the sine
        signed)."""
        with jax.named_scope("zoo_attn.rope"):
            cos, sin = rotary_tables(self.inv_freq, self.rotary_scale, t)
            if self._rotates_in_place(t):
                return rotary_tables_in_place(cos, sin, self.n_head,
                                              self.head_dim)
            return cos, sin

    def call(self, params, x, *, training=False, rng=None):
        tables = None
        if isinstance(x, (list, tuple)):
            x, tables = x
        cd = compute_dtype()
        t = x.shape[1]
        flash = self._use_flash(None, 0.0, t)
        in_place = self._rotates_in_place(t)
        with jax.named_scope("zoo_attn.proj"):
            q, k, v = (_project(params[w], x, cd)
                       for w in ("Wq", "Wk", "Wv"))
        cos, sin = tables if tables is not None else self.tables(t)
        if in_place:
            d, kv = self.head_dim, k.shape[-1]
            with jax.named_scope("zoo_attn.rope"):
                q = apply_rotary_in_place(q, cos, sin, d, d)
                k = apply_rotary_in_place(k, cos[:, :kv], sin[:, :kv], d, d)
        with jax.named_scope("zoo_attn.attend"):
            q = _heads(q, self.n_head, flash)
            k, v = (_heads(a, self.n_kv_head, flash) for a in (k, v))
        if not in_place:
            if self.qk_norm is not None:
                with jax.named_scope("zoo_attn.qk_norm"):
                    q = self.qk_norm.call(params["q_norm"], q)
                    k = self.qk_norm.call(params["k_norm"], k)
            with jax.named_scope("zoo_attn.rope"):
                q, k = (apply_rotary(a, cos, sin, heads_first=not flash)
                        for a in (q, k))
        with jax.named_scope("zoo_attn.attend"):
            if flash:
                from .....ops.pallas import flash_attention
                out = flash_attention(q, k, v, causal=True,
                                      window=self.window)
            else:
                out = dot_product_attention(q, k, v, causal=True,
                                            window=self.window)
            out = _merged(out, flash)
        with jax.named_scope("zoo_attn.out"):
            return _project(params["Wo"], out, cd)


class LatentAttention(MultiHeadSelfAttention):
    """Multi-head latent attention (DeepSeek-V2's MLA; GLM-4.7-Flash), the
    expanded form that training computes. With ``x`` (B, T, H):

    * ``c_q = RMSNorm(x Wqa)`` (H -> ``q_lora_rank``); ``q = c_q Wqb``
      (-> ``n_head`` heads of ``[q_nope (qk_nope_dim), q_pe (qk_rope_dim)]``);
    * ``[c_kv, k_pe] = x Wkva`` (H -> ``kv_lora_rank + qk_rope_dim``);
      ``c_kv = RMSNorm(c_kv)``; ``c_kv Wkvb`` (-> ``n_head`` heads of
      ``[k_nope (qk_nope_dim), v (v_dim)]``);
    * rotary positions (half-split pairs, ``rotary`` a ``rope_parameters``
      entry over ``qk_rope_dim``) on ``q_pe`` of every head and on the ONE
      ``k_pe``, which all heads share: ``k = [k_nope, k_pe]``;
    * ``o = softmax(q k^T / sqrt(qk_nope_dim + qk_rope_dim) + causal) v``;
      ``y = concat(o) Wo``.

    Keys and values of all ``n_head`` heads are materialised, as the
    published implementations do in training; the absorbed form (attention
    in the latent space) is decoding's and is not here. ``q, k, v`` go to
    the Pallas flash kernels under ``_use_flash``'s rule where the value
    heads are as wide as the key heads (one head size a call), to the XLA
    op elsewhere. No biases. Device time shows under ``zoo_mla.q_latent``,
    ``.kv_latent``, ``.expand`` (the two up-projections and the broadcast
    of ``k_pe``), ``.rope``, ``.attend`` and ``.out``. Input (B, T, H), or
    ``[x, (cos, sin)]`` with tables of ``qk_rope_dim`` columns."""

    mixer_kind = "latent"

    def __init__(self, hidden_size: int, n_head: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_dim: int, qk_rope_dim: int,
                 v_dim: int, rotary: Mapping[str, Any],
                 epsilon: float = 1e-6, **kwargs):
        Layer.__init__(self, **kwargs)
        self.hidden_size, self.n_head = hidden_size, n_head
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_dim, self.qk_rope_dim = qk_nope_dim, qk_rope_dim
        self.v_dim = v_dim
        self.causal = True
        self.q_norm = RMSNorm(epsilon=epsilon)
        self.kv_norm = RMSNorm(epsilon=epsilon)
        self.inv_freq, self.rotary_scale = rotary_inv_freq(qk_rope_dim,
                                                           rotary)

    def build(self, rng, input_shape):
        k = jax.random.split(rng, 5)
        init = get_initializer("glorot_uniform")
        h, n = self.hidden_size, self.n_head
        qk = self.qk_nope_dim + self.qk_rope_dim
        return {
            "Wqa": init(k[0], (h, self.q_lora_rank), param_dtype()),
            "q_norm": self.q_norm.build(k[0], (self.q_lora_rank,)),
            "Wqb": init(k[1], (self.q_lora_rank, n * qk), param_dtype()),
            "Wkva": init(k[2], (h, self.kv_lora_rank + self.qk_rope_dim),
                         param_dtype()),
            "kv_norm": self.kv_norm.build(k[2], (self.kv_lora_rank,)),
            "Wkvb": init(k[3], (self.kv_lora_rank,
                                n * (self.qk_nope_dim + self.v_dim)),
                         param_dtype()),
            "Wo": init(k[4], (n * self.v_dim, h), param_dtype())}

    def param_sharding(self, params):
        return jax.tree.map(lambda _: None, params)

    def _in_place(self, t: int) -> bool:
        """Whether a call at ``t`` positions takes the flash kernels: one
        head size a call (the value heads as wide as the key heads), under
        ``_use_flash``'s rule. It then keeps q, k and v as (B, T, n_head *
        width) from the projections to the kernels."""
        return (self.v_dim == self.qk_nope_dim + self.qk_rope_dim
                and self._use_flash(None, 0.0, t))

    def tables(self, t: int):
        """float32 (cos, sin) of positions 0..t-1, ``qk_rope_dim`` wide;
        where the call stays in place (``_in_place``), followed by the pair
        spread over the query heads' lanes (``rotary_tables_in_place``)."""
        with jax.named_scope("zoo_mla.rope"):
            cos, sin = rotary_tables(self.inv_freq, self.rotary_scale, t)
            if self._in_place(t):
                return (cos, sin) + rotary_tables_in_place(
                    cos, sin, self.n_head,
                    self.qk_nope_dim + self.qk_rope_dim, self.qk_nope_dim)
            return cos, sin

    def _key_value_weights(self, wkvb, cd):
        """``Wkvb`` as the two matrices that write k and v in place:
        ``(kv_lora_rank + qk_rope_dim, n_head * qk)``, every head's
        ``k_nope`` columns of ``Wkvb`` over 0/1 rows that set the one
        rotated ``k_pe`` behind them (a product with 1 and sums with 0:
        the concatenation's values exactly, written by the matrix unit
        where a head belongs), and ``(kv_lora_rank, n_head * v_dim)``, its
        value columns. Slices of a weight, a thousandth of the activations
        they save slicing."""
        n, nope, rope = self.n_head, self.qk_nope_dim, self.qk_rope_dim
        w = wkvb.astype(cd).reshape(self.kv_lora_rank, n, nope + self.v_dim)
        w_k = jnp.pad(w[..., :nope], ((0, 0), (0, 0), (0, rope)))
        place = np.zeros((rope, n, nope + rope), np.float32)
        place[np.arange(rope), :, nope + np.arange(rope)] = 1.0
        w_k = jnp.concatenate([w_k, jnp.asarray(place, cd)], axis=0)
        return (w_k.reshape(w_k.shape[0], -1),
                w[..., nope:].reshape(self.kv_lora_rank, -1))

    def call(self, params, x, *, training=False, rng=None):
        tables = None
        if isinstance(x, (list, tuple)):
            x, tables = x
        cd = compute_dtype()
        t, n, nope = x.shape[1], self.n_head, self.qk_nope_dim
        flash = self._in_place(t)
        with jax.named_scope("zoo_mla.q_latent"):
            c_q = self.q_norm.call(params["q_norm"],
                                   _project(params["Wqa"], x, cd))
        with jax.named_scope("zoo_mla.kv_latent"):
            kva = _project(params["Wkva"], x, cd)
            c_kv = self.kv_norm.call(params["kv_norm"],
                                     kva[..., :self.kv_lora_rank])
            k_pe = kva[..., self.kv_lora_rank:]              # (B, T, r)
        cos, sin, *spread = tables if tables is not None else self.tables(t)
        if flash:
            # q, k and v stay (B, T, n * width) from the projections to
            # the kernels: a (B, T, n, width) view is another tiling of
            # the same bytes on a TPU, and every operation on one costs a
            # copy of the tensor
            with jax.named_scope("zoo_mla.rope"):
                k_pe = apply_rotary(k_pe, cos, sin)
            with jax.named_scope("zoo_mla.expand"):
                q = _project(params["Wqb"], c_q, cd)
                w_k, w_v = self._key_value_weights(params["Wkvb"], cd)
                k = _project(w_k, jnp.concatenate([c_kv, k_pe], axis=-1), cd)
                v = _project(w_v, c_kv, cd)
            with jax.named_scope("zoo_mla.rope"):
                q = apply_rotary_in_place(q, *spread, nope + self.qk_rope_dim,
                                          self.qk_rope_dim, nope)
            with jax.named_scope("zoo_mla.attend"):
                from .....ops.pallas import flash_attention
                out = flash_attention(*(_heads(a, n, True)
                                        for a in (q, k, v)), causal=True)
            with jax.named_scope("zoo_mla.out"):
                return _project(params["Wo"], _merged(out, True), cd)
        k_pe = k_pe[:, None]                                 # (B, 1, T, r)
        with jax.named_scope("zoo_mla.expand"):
            q = split_heads(_project(params["Wqb"], c_q, cd), n)
            kv = split_heads(_project(params["Wkvb"], c_kv, cd), n)
        with jax.named_scope("zoo_mla.rope"):
            q_pe = apply_rotary(q[..., nope:], cos, sin)
            k_pe = apply_rotary(k_pe, cos, sin)
        with jax.named_scope("zoo_mla.expand"):
            q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, kv.shape[:-1] + (k_pe.shape[-1],))],
                axis=-1)
            v = kv[..., nope:]
        with jax.named_scope("zoo_mla.attend"):
            out = dot_product_attention(q, k, v, causal=True)
        with jax.named_scope("zoo_mla.out"):
            return _project(params["Wo"], merge_heads(out), cd)


class ShortConvMixer(Layer):
    """The gated short convolution of the LFM2 family, a decoder block's
    mixer that is not attention. With ``a`` (B, T, H):

    * ``[B, C, u] = a Win`` (H -> 3 H, split in that order);
    * ``v = B * u``; ``c[t] = sum_j conv[:, j] * v[t - (kernel - 1) + j]``
      with ``v`` zero before position 0: a causal depthwise convolution,
      one ``kernel``-tap filter a channel (``Conv1d(groups=H,
      padding=kernel - 1)`` cut to the first T outputs);
    * ``y = (C * c) Wout`` (H -> H).

    No activation, no biases, no positions: the layer has no ``tables``
    and ``DecoderStack`` calls it on the hidden states alone. The two
    products accumulate in float32 (``_project``); the gate-conv-gate chain
    between them is ``kernel`` shifted multiply-adds over T: ``v`` in the
    compute dtype, the taps and their sum in float32, the gated result
    rounded to the compute dtype. Parameters ``Win``
    (H, 3 H), ``conv`` (H, kernel; uniform +-kernel^-1/2 at the start,
    ``torch.nn.Conv1d``'s own for this shape) and ``Wout`` (H, H). Device
    time shows under ``zoo_conv.in_proj``, ``zoo_conv.gate`` and
    ``zoo_conv.out_proj``."""

    mixer_kind = "conv"
    layer_scope = False

    def __init__(self, hidden_size: int, kernel: int = 3, **kwargs):
        super().__init__(**kwargs)
        self.hidden_size, self.kernel = hidden_size, kernel

    def build(self, rng, input_shape):
        k = jax.random.split(rng, 3)
        init = get_initializer("glorot_uniform")
        h, bound = self.hidden_size, self.kernel ** -0.5
        return {"Win": init(k[0], (h, 3 * h), param_dtype()),
                "conv": jax.random.uniform(k[1], (h, self.kernel),
                                           param_dtype(), -bound, bound),
                "Wout": init(k[2], (h, h), param_dtype())}

    def param_sharding(self, params):
        return jax.tree.map(lambda _: None, params)

    def call(self, params, x, *, training=False, rng=None):
        cd = compute_dtype()
        t = x.shape[1]
        with jax.named_scope("zoo_conv.in_proj"):
            bcu = _project(params["Win"], x, cd)
        with jax.named_scope("zoo_conv.gate"):
            b, c, u = jnp.split(bcu, 3, axis=-1)
            v = b * u
            taps = params["conv"].astype(jnp.float32)
            conv = taps[:, -1] * v
            for back in range(1, self.kernel):
                # v[t - back], zero before position 0
                shifted = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :t]
                conv = conv + taps[:, -1 - back] * shifted
            y = (c * conv).astype(cd)
        with jax.named_scope("zoo_conv.out_proj"):
            return _project(params["Wout"], y, cd)


class TiedHead(Layer):
    """The logits head of a ``DecoderStack(tied_head=True)``: ``x E^T``
    with ``E`` the stack's token table. It holds no parameter of its own;
    the stack hands it ``{"W": E^T}`` through the containers' dispatch, so
    that the fused cross-entropy can take it over as it takes a ``Dense``
    head (``fused_loss.find_head``)."""

    activation = None
    tied = True
    layer_scope = False     # the stack calls it under ``zoo_loss``

    def __init__(self, output_dim: int, **kwargs):
        super().__init__(**kwargs)
        self.output_dim = output_dim

    def build(self, rng, input_shape):
        return {}

    def call(self, params, x, *, training=False, rng=None):
        return _project(params["W"], x, compute_dtype())


class DecoderBlock(Layer):
    """Pre-norm residual block: ``h = x + Mixer(RMSNorm(x))``;
    ``x' = h + FFN(RMSNorm(h))``. ``attn`` is the block's mixer
    (``DecoderAttention``, ``LatentAttention``: any layer that takes
    ``[x, (cos, sin)]``; ``ShortConvMixer``: any layer from (B, T, H) to
    (B, T, H), called on ``x`` alone where the block is given no tables),
    ``ffn`` its feed-forward layer (``RoutedExperts``,
    ``GatedFeedForward``; any layer from (B, T, H) to (B, T, H)), whose
    state, if it keeps one, is the block's. The two norms and the two adds
    (a block's glue) run under the device scope ``zoo_norm``."""

    layer_scope = False

    def __init__(self, hidden_size: int, attn: Layer, ffn: Layer,
                 epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        self.hidden_size = hidden_size
        self.attn, self.ffn = attn, ffn
        self.ln1 = RMSNorm(epsilon=epsilon)
        self.ln2 = RMSNorm(epsilon=epsilon)

    def build(self, rng, input_shape):
        k = jax.random.split(rng, 4)
        return {"ln1": self.ln1.build(k[0], input_shape),
                "attn": self.attn.build(k[1], input_shape),
                "ln2": self.ln2.build(k[2], input_shape),
                "ffn": self.ffn.build(k[3], input_shape)}

    def initial_state(self, input_shape=None):
        s = self.ffn.initial_state(input_shape)
        return {"ffn": s} if s else {}

    def apply(self, params, state, x, *, training=False, rng=None):
        tables = None
        if isinstance(x, (list, tuple)):
            x, tables = x
        with jax.named_scope("zoo_norm"):
            a = self.ln1.call(params["ln1"], x)
        mixed = self.attn.call(params["attn"],
                               [a, tables] if tables is not None else a,
                               training=training)
        with jax.named_scope("zoo_norm"):
            h = x + mixed
            normed = self.ln2.call(params["ln2"], h)
        f, ns = self.ffn.apply(params["ffn"], (state or {}).get("ffn", {}),
                               normed, training=training, rng=rng)
        with jax.named_scope("zoo_norm"):
            return h + f, ({"ffn": ns} if ns else {})

    def call(self, params, x, *, training=False, rng=None):
        return self.apply(params, {}, x, training=training, rng=rng)[0]


def remat_saved_bytes(record: Optional[Mapping[str, int]] = None
                      ) -> Dict[str, int]:
    """``zoo_remat_saved_bytes`` by ``what`` (``flash_out``, ``flash_lse``:
    the ``FLASH_SAVED`` names without their prefix): what the block
    checkpoints of the rematerialised ``DecoderStack`` training step traced
    last keep on a device beside the blocks' inputs. ``record`` (bytes by
    ``FLASH_SAVED`` name; a name left out is 0) sets it first: the stack
    while it is traced, and the train loop with ``{}`` before it traces a
    step, so that a model without such a stack reads 0 and not the last
    model's."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import FLASH_SAVED
    from .....observability import default_registry
    reg, out = default_registry(), {}
    for name in FLASH_SAVED:
        what = name.removeprefix("zoo_")
        gauge = reg.gauge(  # zoolint: disable=ZL015 the two of FLASH_SAVED
            "zoo_remat_saved_bytes",
            "bytes a device keeps for the backward pass beside the blocks' "
            "inputs in the rematerialised DecoderStack step traced last, "
            "summed over its blocks: the flash kernels' outputs (flash_out) "
            "and row statistics (flash_lse); 0 where the XLA attention op "
            "ran", labels={"what": what})
        if record is not None:
            gauge.set(record.get(name, 0))
        out[what] = int(gauge.value)
    return out


#: the kinds of mixer ``zoo_decoder_blocks{mixer=}`` counts
MIXER_KINDS = ("conv", "full_attention", "sliding_attention", "latent")


def decoder_blocks(census: Optional[Mapping[str, int]] = None
                   ) -> Dict[str, int]:
    """``zoo_decoder_blocks`` by ``mixer`` (``MIXER_KINDS``): the blocks of
    the ``DecoderStack`` built last, by what mixes their tokens. ``census``
    (blocks by kind; a kind left out is 0) sets it first, as the stack does
    where it is built."""
    from .....observability import default_registry
    reg, out = default_registry(), {}
    for kind in MIXER_KINDS:
        gauge = reg.gauge(  # zoolint: disable=ZL015 the four of MIXER_KINDS
            "zoo_decoder_blocks",
            "blocks of the DecoderStack built last, by the kind of their "
            "mixer: a gated short convolution (conv), attention over all "
            "earlier positions (full_attention) or a window of them "
            "(sliding_attention), latent attention (latent)",
            labels={"mixer": kind})
        if census is not None:
            gauge.set(census.get(kind, 0))
        out[kind] = int(gauge.value)
    return out


class DecoderStack(Layer):
    """Token embedding (no position table), one ``DecoderBlock`` per entry
    of ``layer_types`` (``"sliding_attention"``: the block's attention has
    the ``sliding_window``; ``"full_attention"``: none; ``"conv"``: the
    block's mixer is a ``ShortConvMixer`` of ``conv_kernel`` taps, no
    attention), a final RMSNorm. Input int ids (B, T) -> hidden states
    (B, T, H); put a ``Dense(vocab, bias=False)`` behind it for an untied
    head. ``tied_head=True`` ends the stack in the head itself, ``logits =
    RMSNorm(h) E^T`` (B, T, vocab) with ``E`` the token table: one leaf
    takes the embedding's gradient and the head's, ``fit`` holds one table
    and one pair of moments, and the fused cross-entropy takes the head
    over (``fused_head``) as it takes a ``Dense``.

    ``rope_parameters`` maps each layer type to its rotary specification
    (or is one specification for all). ``ffn(i)`` returns block ``i``'s
    feed-forward layer. ``qk_norm`` is ``DecoderAttention``'s. ``attn(i)``,
    where given, returns block ``i``'s mixer (``LatentAttention``: anything
    with ``tables(t)`` and a call on ``[x, (cos, sin)]``; or a layer
    without ``tables``, called on ``x`` alone) in place of the
    ``DecoderAttention`` / ``ShortConvMixer`` that ``n_head``,
    ``n_kv_head``, ``head_dim``, ``rope_parameters``, ``sliding_window``
    and ``conv_kernel`` describe (the attention's are needed only where a
    layer type asks for attention); blocks of one layer type share the
    rotary tables the first of them makes. ``mixers`` counts the blocks by
    ``mixer_kind`` (``zoo_decoder_blocks{mixer=}``).
    ``remat=True`` rematerialises each block in the backward pass
    (``jax.checkpoint``): the step keeps one block's activations at a
    time, and of every block its inputs and, where its attention ran on
    the flash kernels, that call's output and row statistics
    (``flash_attention.FLASH_SAVED``: one tensor of ``B x heads x T x
    head_dim`` in the compute dtype a layer and one float a row), so the
    backward pass recomputes the projections, norms, rotary and the
    feed-forward layer but does not run the flash forward a second time;
    a ``conv`` block holds no such name and keeps its input alone.
    What that keeps is counted while the step is traced, in
    ``zoo_remat_saved_bytes{what=}`` and ``model.last_fit_report
    ["remat_saved_bytes"]``. Device scopes of the stack's own work:
    ``zoo_embed`` (the lookup; the rotary tables stay under their layers'
    ``zoo_attn.rope`` / ``zoo_mla.rope``), ``zoo_norm`` (the final norm),
    ``zoo_loss`` (a tied head's product)."""

    SLIDING, FULL, CONV = "sliding_attention", "full_attention", "conv"
    layer_scope = False

    def __init__(self, vocab: int, layer_types: Sequence[str],
                 hidden_size: int, n_head: Optional[int] = None,
                 n_kv_head: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 ffn: Optional[Callable[[int], Layer]] = None,
                 rope_parameters: Optional[Mapping[str, Any]] = None,
                 sliding_window: Optional[int] = None,
                 epsilon: float = 1e-6, initializer_range: float = 0.02,
                 remat: bool = False,
                 attn: Optional[Callable[[int], Layer]] = None,
                 qk_norm: bool = False, conv_kernel: int = 3,
                 tied_head: bool = False, **kwargs):
        super().__init__(**kwargs)
        self.vocab, self.hidden_size = vocab, hidden_size
        self.layer_types = tuple(layer_types)
        self.initializer_range = initializer_range
        self.remat = remat
        self.blocks = []
        if ffn is None:
            raise ValueError("ffn(i), each block's feed-forward layer, is "
                             "needed")
        for i, kind in enumerate(self.layer_types):
            if kind not in (self.SLIDING, self.FULL, self.CONV):
                raise ValueError(f"layer_types[{i}] = {kind!r}")
        attends = any(kind != self.CONV for kind in self.layer_types)
        if attn is None and attends and None in (n_head, n_kv_head, head_dim,
                                                 rope_parameters):
            raise ValueError("without attn(i), n_head, n_kv_head, head_dim "
                             "and rope_parameters describe the attention")
        for i, kind in enumerate(self.layer_types):
            if attn is not None:
                layer = attn(i)
            elif kind == self.CONV:
                layer = ShortConvMixer(hidden_size, kernel=conv_kernel,
                                       name=f"{self.name}_block{i}_conv")
            else:
                if kind == self.SLIDING and not sliding_window:
                    raise ValueError("sliding_attention layers need "
                                     "sliding_window")
                layer = DecoderAttention(
                    hidden_size, n_head, n_kv_head, head_dim,
                    rotary=rope_parameters.get(kind, rope_parameters),
                    window=sliding_window if kind == self.SLIDING else None,
                    qk_norm=qk_norm, epsilon=epsilon,
                    name=f"{self.name}_block{i}_attn")
            self.blocks.append(DecoderBlock(
                hidden_size, layer, ffn(i), epsilon=epsilon,
                name=f"{self.name}_block{i}"))
        self.norm = RMSNorm(epsilon=epsilon)
        self.head = (TiedHead(vocab, name=f"{self.name}_head")
                     if tied_head else None)
        self.mixers: Dict[str, int] = dict(collections.Counter(
            getattr(blk.attn, "mixer_kind", kind)
            for blk, kind in zip(self.blocks, self.layer_types)))
        decoder_blocks(self.mixers)

    def fused_head(self):
        """``(head, path of the table under the stack's parameters)`` for
        the fused cross-entropy (``fused_loss.find_head``), or None where
        the head is not tied."""
        return None if self.head is None else (self.head, ("wte",))

    def build(self, rng, input_shape):
        keys = jax.random.split(rng, len(self.blocks) + 2)
        h_shape = (input_shape[0], input_shape[1], self.hidden_size)
        p: Dict[str, Any] = {
            "wte": jax.random.normal(keys[0], (self.vocab, self.hidden_size),
                                     param_dtype()) * self.initializer_range,
            "norm": self.norm.build(keys[1], h_shape)}
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk.build(keys[i + 2], h_shape)
        return p

    def initial_state(self, input_shape=None):
        state = {}
        for i, blk in enumerate(self.blocks):
            s = blk.initial_state(input_shape)
            if s:
                state[f"block{i}"] = s
        return state

    def apply(self, params, state, x, *, training=False, rng=None):
        ids = x.astype(jnp.int32)
        with jax.named_scope("zoo_embed"):
            h = jnp.take(params["wte"], ids, axis=0).astype(compute_dtype())
        # one pair of tables per kind of layer that has them, shared by
        # its blocks
        tables = {}
        for blk, kind in zip(self.blocks, self.layer_types):
            if kind not in tables:
                make = getattr(blk.attn, "tables", None)
                tables[kind] = make(ids.shape[1]) if make else None
        from analytics_zoo_tpu.ops.pallas.flash_attention import (
            FLASH_SAVED, saved_bytes_log)
        keep = jax.checkpoint_policies.save_only_these_names(*FLASH_SAVED)
        new_state = {}
        with saved_bytes_log() as kept:
            for i, (blk, kind) in enumerate(zip(self.blocks,
                                                self.layer_types)):
                def run(p, s, h, tab, blk=blk):
                    return blk.apply(p, s, [h, tab], training=training)
                if self.remat:
                    # a block whose mixer is the XLA op or no attention
                    # holds no such name, and its checkpoint keeps the
                    # inputs alone
                    run = jax.checkpoint(run, policy=keep)
                h, ns = run(params[f"block{i}"],
                            (state or {}).get(f"block{i}", {}), h,
                            tables[kind])
                if ns:
                    new_state[f"block{i}"] = ns
        if self.remat and training:
            remat_saved_bytes(kept)
        with jax.named_scope("zoo_norm"):
            h = self.norm.call(params["norm"], h)
        if self.head is not None:
            with jax.named_scope("zoo_loss"):
                h, _ = dispatch_layer(self.head, {"W": params["wte"].T}, {},
                                      h, training=training)
        return h, new_state

    def call(self, params, x, *, training=False, rng=None):
        return self.apply(params, {}, x, training=training, rng=rng)[0]
