"""Graph/layer engine — the TPU-native equivalent of the reference's Keras-1
style API (``pipeline/api/keras/models/Topology.scala``) and its autograd
graph (``pipeline/api/autograd/math.scala``).

Design (idiomatic JAX, not a port):

* A ``Layer`` is a *functional* module: ``build(rng, input_shape) -> params``
  (a pytree) and ``call(params, x) -> y``. Stateful layers (BatchNorm)
  additionally carry a non-trainable ``state`` pytree threaded functionally
  through ``apply`` — no mutation, so everything jits/vmaps/shards cleanly.
* Output shapes are inferred with ``jax.eval_shape`` instead of per-layer
  ``computeOutputShape`` code (the reference implements shape inference by
  hand per layer).
* The functional-API ``Variable`` (operator overloading: ``+ - * / **`` and
  the ``AutoGrad`` op set of ``math.scala:32-365``) and Keras graph nodes are
  one graph system; ``Model(input, output)`` topologically evaluates it.
* ``Sequential`` and ``Model`` are themselves Layers, so they nest, mirroring
  ``KerasNet`` in ``Topology.scala:63``.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ....observability.step_ledger import LAYER_SCOPE

# --------------------------------------------------------------------------
# dtype policy
# --------------------------------------------------------------------------

_compute_dtype = jnp.float32
_param_dtype = jnp.float32
#: who last set the policy: "default" | "direct" (user set_policy) |
#: "context" (init_zoo_context's zoo.compute.dtype). The context only
#: overrides policies IT owns — see init_zoo_context.
_policy_owner = "default"


def set_policy(compute_dtype: Any = jnp.float32, param_dtype: Any = jnp.float32):
    """Set the global mixed-precision policy. ``bfloat16`` compute keeps the
    MXU fed at full rate; params stay float32 for stable updates.

    A direct call takes OWNERSHIP of the policy: later context inits that
    don't name ``zoo.compute.dtype`` leave it alone (see
    ``common.context.init_zoo_context``)."""
    global _compute_dtype, _param_dtype, _policy_owner
    _compute_dtype = jnp.dtype(compute_dtype)
    _param_dtype = jnp.dtype(param_dtype)
    _policy_owner = "direct"


def policy_owner() -> str:
    return _policy_owner


def _set_policy_from_context(compute_dtype: Any):
    """Context-owned policy write (init_zoo_context only)."""
    global _policy_owner
    set_policy(compute_dtype=compute_dtype)
    _policy_owner = "context"


def _reset_policy():
    """Back to the pristine float32 default (reset_zoo_context only)."""
    global _policy_owner
    set_policy()
    _policy_owner = "default"


def compute_dtype():
    return _compute_dtype


def param_dtype():
    return _param_dtype


# --------------------------------------------------------------------------
# naming
# --------------------------------------------------------------------------

_uid_counters: Dict[str, int] = collections.defaultdict(int)


#: True while a layer/model shape-inference probe (``output_shape_for``'s
#: ``eval_shape``) is running — probes use placeholder batch dims, so
#: batch-dependent routing decisions (e.g. the seq-mesh divisibility check)
#: must not warn or raise strict-mode errors off them.
_in_shape_probe = False


def in_shape_probe() -> bool:
    return _in_shape_probe


def unique_name(prefix: str) -> str:
    _uid_counters[prefix] += 1
    return f"{prefix}{_uid_counters[prefix]}"


def reset_uids() -> None:
    _uid_counters.clear()


# --------------------------------------------------------------------------
# initializers (Keras-1 ``init=`` strings, e.g. Dense.scala / NeuralCF.scala)
# --------------------------------------------------------------------------

def get_initializer(name: Union[str, Callable]) -> Callable:
    """Map Keras-1 init names to jax.nn.initializers."""
    if callable(name):
        return name
    from jax.nn import initializers as I

    table = {
        "glorot_uniform": I.glorot_uniform(),
        "glorot_normal": I.glorot_normal(),
        "xavier": I.glorot_uniform(),
        "he_normal": I.he_normal(),
        "he_uniform": I.he_uniform(),
        "lecun_uniform": I.lecun_uniform(),
        "lecun_normal": I.lecun_normal(),
        "uniform": I.uniform(scale=0.05),
        "normal": I.normal(stddev=0.05),
        "zero": I.zeros,
        "zeros": I.zeros,
        "one": I.ones,
        "ones": I.ones,
        "orthogonal": I.orthogonal(),
    }
    if name not in table:
        raise ValueError(f"unknown initializer: {name}")
    return table[name]


# --------------------------------------------------------------------------
# layer-call interception (calibration / quantized execution)
# --------------------------------------------------------------------------

_LAYER_HOOK = contextvars.ContextVar("zoo_layer_hook", default=None)


@contextlib.contextmanager
def intercept_layer_calls(hook):
    """Scope a hook over every container-dispatched layer call.

    ``hook(layer, params, state, x, training, rng)`` returns ``(y, state)``
    to substitute the call, or ``None`` to run the layer normally. Used by
    the inference runtime for int8 activation calibration (record input
    ranges eagerly) and quantized execution (swap in ``quantized_call`` at
    trace time), by the fused LM-head loss (head → identity), by the
    sharded embedding engine (plain ``Embedding`` → row-partitioned
    dedup'd lookup, ``keras/sharded_embed.py``) and by the
    pipeline-parallel step builder (block run → ``gpipe_apply``);
    sub-layers invoked *inside* wrapper layers (TimeDistributed,
    Bidirectional) are not dispatched through containers and stay float.

    Nested scopes CHAIN: the innermost hook is consulted first and a
    ``None`` return falls through to the enclosing one — so the
    fused-loss head intercept composes with the training loop's pipeline
    intercept instead of silently replacing it. Entering a scope with
    ``hook=None`` keeps the historical meaning — interception DISABLED
    for the scope (the int8 runtime's ``qhook if act_scales else None``
    idiom), not a crash and not a chain link."""
    prev = _LAYER_HOOK.get()
    if prev is not None and hook is not None:
        inner = hook

        def hook(layer, params, state, x, training, rng):
            out = inner(layer, params, state, x, training, rng)
            if out is not None:
                return out
            return prev(layer, params, state, x, training, rng)
    token = _LAYER_HOOK.set(hook)
    try:
        yield
    finally:
        _LAYER_HOOK.reset(token)


def dispatch_layer(layer, params, state, x, *, training=False, rng=None):
    """One container-dispatched layer call, a hook's substitute included,
    under the device scope ``zoo_layer.<ClassName>``: the fallback of
    ``observability/step_ledger.py::SCOPES`` for every model a container
    builds. A layer that opens scopes of its own says ``layer_scope =
    False`` and gets none."""
    scope = (jax.named_scope(LAYER_SCOPE + type(layer).__name__)
             if getattr(layer, "layer_scope", True)
             else contextlib.nullcontext())
    with scope:
        hook = _LAYER_HOOK.get()
        if hook is not None:
            out = hook(layer, params, state, x, training, rng)
            if out is not None:
                return out
        return layer.apply(params, state, x, training=training, rng=rng)


# --------------------------------------------------------------------------
# Layer base
# --------------------------------------------------------------------------

class Layer:
    """Base layer.

    Subclasses implement:

    * ``build(self, rng, input_shape) -> params`` — create trainable params.
      ``input_shape`` is a tuple (or list of tuples for multi-input layers)
      *including* a leading batch dim of ``None``.
    * ``call(self, params, x, *, training=False, rng=None) -> y``.

    Stateful layers instead override ``initial_state`` and ``apply``.
    """

    #: ``dispatch_layer`` opens the device scope ``zoo_layer.<ClassName>``
    #: round the call. False for a layer that opens ``zoo_*`` scopes where
    #: its work happens (inside its loops and checkpoints, so that no
    #: ``while`` / ``conditional`` carries one) and for a container
    layer_scope = True

    def __init__(self, name: Optional[str] = None, input_shape: Optional[Tuple] = None):
        # _auto_name marks names eligible for deterministic renaming when the
        # layer joins a container — cross-instance checkpoint/weight files
        # must not depend on process-global uid counters
        self._auto_name = name is None
        self.name = name or unique_name(type(self).__name__.lower() + "_")
        # Keras-1 convention: user-facing input_shape excludes the batch dim
        # (``KerasLayer.inputShape``); internally we carry (None, *dims).
        self._declared_input_shape = (
            (None,) + tuple(input_shape) if input_shape is not None else None
        )

    # ---- to be overridden -------------------------------------------------
    def build(self, rng: jax.Array, input_shape) -> Dict[str, Any]:
        return {}

    def initial_state(self, input_shape) -> Dict[str, Any]:
        return {}

    def call(self, params, x, *, training: bool = False, rng: Optional[jax.Array] = None):
        raise NotImplementedError(type(self).__name__)

    def apply(self, params, state, x, *, training: bool = False,
              rng: Optional[jax.Array] = None):
        """Returns ``(y, new_state)``. Default: stateless passthrough."""
        return self.call(params, x, training=training, rng=rng), state

    def get_config(self) -> Dict[str, Any]:
        return {}

    # ---- tensor-parallel sharding rules (SURVEY §2.4 TP — greenfield) -----
    def param_sharding(self, params):
        """PartitionSpec tree matching this layer's ``params``; ``None``
        leaves mean replicated. Layers whose weights shard over the ``model``
        mesh axis (Dense, Embedding) override this; everything else stays
        replicated — GSPMD propagates activation shardings from here."""
        return jax.tree.map(lambda _: None, params)

    # ---- shape inference --------------------------------------------------
    def output_shape_for(self, params, state, input_shape):
        """Infer output shape via abstract evaluation (no FLOPs)."""
        global _in_shape_probe
        spec = _shapes_to_specs(input_shape)
        rng = jax.random.key(0)
        prev = _in_shape_probe
        _in_shape_probe = True
        try:
            out = jax.eval_shape(
                lambda p, s, x: self.apply(p, s, x, training=False,
                                           rng=rng)[0],
                params, state, spec,
            )
        finally:
            _in_shape_probe = prev
        return jax.tree.map(lambda o: _spec_to_shape(o), out,
                            is_leaf=lambda o: isinstance(o, jax.ShapeDtypeStruct))

    # ---- graph building ---------------------------------------------------
    def __call__(self, x: Union["Variable", Sequence["Variable"]]) -> "Variable":
        """Functional-API call: connect this layer into the graph."""
        if isinstance(x, (list, tuple)):
            parents = [v.node for v in x]
        else:
            parents = [x.node]
        node = Node(self, parents)
        return Variable(node)

    def __repr__(self):
        return f"<{type(self).__name__} {self.name}>"


def _shapes_to_specs(input_shape, dtype=None):
    dtype = dtype or _compute_dtype
    if isinstance(input_shape, list):
        return [jax.ShapeDtypeStruct(_concrete(s), dtype) for s in input_shape]
    return jax.ShapeDtypeStruct(_concrete(input_shape), dtype)


def _concrete(shape):
    return tuple(1 if d is None else d for d in shape)


def _spec_to_shape(spec):
    # restore the symbolic batch dim
    return (None,) + tuple(spec.shape[1:])


# --------------------------------------------------------------------------
# Graph nodes & Variables (autograd)
# --------------------------------------------------------------------------

class Node:
    __slots__ = ("layer", "parents", "name")

    def __init__(self, layer: Layer, parents: List["Node"]):
        self.layer = layer
        self.parents = parents
        self.name = layer.name


class InputLayer(Layer):
    def __init__(self, shape: Tuple, name: Optional[str] = None):
        super().__init__(name=name or unique_name("input_"))
        self.shape = (None,) + tuple(shape)

    def call(self, params, x, *, training=False, rng=None):
        return x


class Lambda(Layer):
    """Arbitrary jnp-function layer — equivalent of the reference's
    ``autograd.Lambda`` (``pipeline/api/autograd/Lambda.scala``). ``fn`` takes
    the input (or list of inputs) and returns an array."""

    def __init__(self, fn: Callable, name: Optional[str] = None):
        super().__init__(name=name or unique_name("lambda_"))
        self.fn = fn

    def call(self, params, x, *, training=False, rng=None):
        if isinstance(x, (list, tuple)):
            return self.fn(*x)
        return self.fn(x)


class Variable:
    """Graph-node handle with operator overloading — parity with
    ``autograd.Variable`` (``autograd/math.scala:365-640``)."""

    def __init__(self, node: Node):
        self.node = node

    @property
    def name(self) -> str:
        return self.node.name

    # -- binary ops ---------------------------------------------------------
    def _binop(self, other, fn, opname):
        if isinstance(other, Variable):
            return Lambda(fn, name=unique_name(opname + "_"))([self, other])
        return Lambda(lambda a: fn(a, other), name=unique_name(opname + "_"))(self)

    def _rbinop(self, other, fn, opname):
        return Lambda(lambda a: fn(other, a), name=unique_name(opname + "_"))(self)

    def __add__(self, o): return self._binop(o, jnp.add, "add")
    def __radd__(self, o): return self._rbinop(o, jnp.add, "add")
    def __sub__(self, o): return self._binop(o, jnp.subtract, "sub")
    def __rsub__(self, o): return self._rbinop(o, jnp.subtract, "sub")
    def __mul__(self, o): return self._binop(o, jnp.multiply, "mul")
    def __rmul__(self, o): return self._rbinop(o, jnp.multiply, "mul")
    def __truediv__(self, o): return self._binop(o, jnp.divide, "div")
    def __rtruediv__(self, o): return self._rbinop(o, jnp.divide, "div")
    def __pow__(self, o): return self._binop(o, jnp.power, "pow")
    def __neg__(self): return Lambda(jnp.negative, name=unique_name("neg_"))(self)

    # -- keras-style slicing (Variable.slice / indexSelect in math.scala) ---
    def __getitem__(self, idx):
        return Lambda(lambda a: a[idx], name=unique_name("slice_"))(self)

    def slice(self, dim: int, start: int, length: int) -> "Variable":
        def f(a):
            sl = [slice(None)] * a.ndim
            sl[dim] = slice(start, start + length)
            return a[tuple(sl)]
        return Lambda(f, name=unique_name("slice_"))(self)

    def index_select(self, dim: int, index: int) -> "Variable":
        return Lambda(lambda a: jnp.take(a, index, axis=dim),
                      name=unique_name("indexselect_"))(self)

    def squeeze(self, dim: int) -> "Variable":
        return Lambda(lambda a: jnp.squeeze(a, axis=dim),
                      name=unique_name("squeeze_"))(self)


def Input(shape: Tuple, name: Optional[str] = None) -> Variable:
    """Create a graph input — ``autograd.Variable(inputShape)`` / keras
    ``Input`` in the reference."""
    layer = InputLayer(shape, name=name)
    node = Node(layer, [])
    return Variable(node)


# --------------------------------------------------------------------------
# Containers
# --------------------------------------------------------------------------

class KerasNet(Layer):
    """Base of ``Sequential``/``Model`` — the counterpart of the reference's
    abstract ``KerasNet`` (``Topology.scala:63-600``). Training methods
    (``compile/fit/evaluate/predict``) are attached in ``training.py`` to keep
    the graph engine free of the optimizer machinery."""

    layer_scope = False     # a nested container's layers bring their own

    def __init__(self, name: Optional[str] = None):
        super().__init__(name=name)
        self._compiled = None  # set by .compile()

    # populated by subclasses
    def build(self, rng, input_shape):
        raise NotImplementedError

    # ---- convenience: materialize params for a given input shape ----------
    def init(self, rng: jax.Array, input_shape=None):
        """Returns ``(params, state)`` for this network."""
        shape = input_shape
        if shape is not None and not isinstance(shape, list):
            if shape and (shape[0] is not None and not isinstance(shape[0], (list, tuple))):
                # user passed shape without batch dim
                shape = (None,) + tuple(shape)
            else:
                shape = tuple(shape)
        params = self.build(rng, shape)
        state = self.initial_state(shape)
        return params, state


class Sequential(KerasNet):
    """Linear stack — parity with ``Sequential`` (``Topology.scala:825-959``)."""

    def __init__(self, layers: Optional[Sequence[Layer]] = None, name: Optional[str] = None):
        super().__init__(name=name or unique_name("sequential_"))
        self.layers: List[Layer] = []
        self._shapes: List[Any] = []  # per-layer input shapes, set at build
        for l in layers or []:
            self.add(l)

    def add(self, layer: Layer) -> "Sequential":
        if getattr(layer, "_auto_name", False):
            # deterministic position-based name: two identically-built models
            # (even in one process) produce identical param keys, so saved
            # weights/checkpoints restore by structure, not by uid counters
            taken = {l.name for l in self.layers}
            cand = f"{type(layer).__name__.lower()}_{len(self.layers)}"
            while cand in taken:  # dodge user-chosen names
                cand += "_"
            layer.name = cand
            layer._auto_name = False  # keep one name if the layer is reused
        self.layers.append(layer)
        return self

    @property
    def input_shape(self):
        for l in self.layers:
            if l._declared_input_shape is not None:
                return l._declared_input_shape
            if isinstance(l, InputLayer):
                return l.shape
        return None

    def build(self, rng, input_shape=None):
        shape = input_shape or self.input_shape
        if shape is None:
            raise ValueError(
                f"{self.name}: first layer needs input_shape=..., or pass one to init()")
        names = [l.name for l in self.layers]
        if len(set(names)) != len(names):
            dupes = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(
                f"{self.name}: duplicate layer names {dupes} — params would "
                f"silently collide; give the layers distinct name=...")
        params: Dict[str, Any] = {}
        self._shapes = []
        keys = jax.random.split(rng, max(len(self.layers), 1))
        for k, layer in zip(keys, self.layers):
            self._shapes.append(shape)
            p = layer.build(k, shape)
            s = layer.initial_state(shape)
            params[layer.name] = p
            shape = layer.output_shape_for(p, s, shape)
        self._built_output_shape = shape
        return params

    def initial_state(self, input_shape=None):
        shape = input_shape or self.input_shape
        state: Dict[str, Any] = {}
        for layer, lshape in zip(self.layers, self._shapes):
            s = layer.initial_state(lshape)
            if s:
                state[layer.name] = s
        return state

    def apply(self, params, state, x, *, training=False, rng=None):
        new_state = dict(state) if state else {}
        h = x
        for i, layer in enumerate(self.layers):
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            lstate = state.get(layer.name, {}) if state else {}
            h, ns = dispatch_layer(layer, params.get(layer.name, {}), lstate,
                                   h, training=training, rng=lrng)
            if ns:
                new_state[layer.name] = ns
        return h, new_state

    def call(self, params, x, *, training=False, rng=None):
        y, _ = self.apply(params, {}, x, training=training, rng=rng)
        return y

    def param_sharding(self, params):
        return {l.name: l.param_sharding(params[l.name])
                for l in self.layers if l.name in params}


class Model(KerasNet):
    """Graph container — parity with ``Model`` (``Topology.scala:602``) and
    the autograd graph. ``Model(input=[vars], output=var)``."""

    def __init__(self, input, output, name: Optional[str] = None):
        super().__init__(name=name or unique_name("model_"))
        self.inputs: List[Variable] = list(input) if isinstance(input, (list, tuple)) else [input]
        self.outputs: List[Variable] = list(output) if isinstance(output, (list, tuple)) else [output]
        self._multi_output = isinstance(output, (list, tuple))
        self._topo = self._toposort()
        # deterministic topo-order names (see Sequential.add): identical
        # graphs get identical param keys regardless of uid-counter state
        taken = {n.layer.name for n in self._topo
                 if not getattr(n.layer, "_auto_name", False)}
        for i, node in enumerate(self._topo):
            if getattr(node.layer, "_auto_name", False):
                cand = f"{type(node.layer).__name__.lower()}_{i}"
                while cand in taken:  # dodge user-chosen names
                    cand += "_"
                node.layer.name = cand
                taken.add(cand)
                node.layer._auto_name = False  # shared layers keep one name
            node.name = node.layer.name

    def _toposort(self) -> List[Node]:
        seen: Dict[int, Node] = {}
        order: List[Node] = []

        def visit(node: Node):
            if id(node) in seen:
                return
            seen[id(node)] = node
            for p in node.parents:
                visit(p)
            order.append(node)

        for v in self.outputs:
            visit(v.node)
        return order

    @property
    def input_shape(self):
        shapes = [v.node.layer.shape for v in self.inputs]
        return shapes if len(shapes) > 1 else shapes[0]

    def build(self, rng, input_shape=None):
        by_name: Dict[str, int] = {}
        for n in self._topo:
            if n.parents:  # param-bearing nodes only
                prev = by_name.setdefault(n.name, id(n.layer))
                if prev != id(n.layer):  # same layer object = weight sharing, OK
                    raise ValueError(
                        f"{self.name}: two different layers named {n.name!r} — "
                        f"params would silently collide; give them distinct "
                        f"name=...")
        shapes = input_shape or self.input_shape
        if not isinstance(shapes, list):
            shapes = [shapes]
        shape_of: Dict[int, Any] = {}
        for v, s in zip(self.inputs, shapes):
            shape_of[id(v.node)] = s

        params: Dict[str, Any] = {}
        self._state_shapes: Dict[str, Any] = {}
        keys = jax.random.split(rng, max(len(self._topo), 1))
        for k, node in zip(keys, self._topo):
            if not node.parents:  # input node
                if id(node) not in shape_of:
                    shape_of[id(node)] = node.layer.shape
                continue
            pshapes = [shape_of[id(p)] for p in node.parents]
            in_shape = pshapes if len(pshapes) > 1 else pshapes[0]
            p = node.layer.build(k, in_shape)
            s = node.layer.initial_state(in_shape)
            params[node.name] = p
            self._state_shapes[node.name] = in_shape
            shape_of[id(node)] = node.layer.output_shape_for(p, s, in_shape)
        self._built_output_shape = [shape_of[id(v.node)] for v in self.outputs]
        return params

    def initial_state(self, input_shape=None):
        if not hasattr(self, "_state_shapes"):
            # build must run first to record shapes; tolerate state-only query
            raise RuntimeError("call build() before initial_state() on Model")
        state: Dict[str, Any] = {}
        for node in self._topo:
            if not node.parents:
                continue
            s = node.layer.initial_state(self._state_shapes[node.name])
            if s:
                state[node.name] = s
        return state

    def apply(self, params, state, x, *, training=False, rng=None):
        xs = x if isinstance(x, (list, tuple)) else [x]
        if len(xs) != len(self.inputs):
            raise ValueError(
                f"{self.name} expects {len(self.inputs)} inputs, got {len(xs)}")
        value_of: Dict[int, Any] = {id(v.node): arr for v, arr in zip(self.inputs, xs)}
        new_state = dict(state) if state else {}
        for i, node in enumerate(self._topo):
            if not node.parents:
                continue
            args = [value_of[id(p)] for p in node.parents]
            arg = args if len(args) > 1 else args[0]
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            lstate = state.get(node.name, {}) if state else {}
            y, ns = dispatch_layer(node.layer, params.get(node.name, {}),
                                   lstate, arg, training=training, rng=lrng)
            if ns:
                new_state[node.name] = ns
            value_of[id(node)] = y
        outs = [value_of[id(v.node)] for v in self.outputs]
        out = outs if self._multi_output else outs[0]
        return out, new_state

    def call(self, params, x, *, training=False, rng=None):
        y, _ = self.apply(params, {}, x, training=training, rng=rng)
        return y

    def new_graph(self, outputs: Sequence[str]) -> "Model":
        """Sub-graph surgery: new Model ending at the named nodes — parity
        with ``GraphNet.newGraph(output)`` (``pipeline/api/net/NetUtils.scala``)."""
        by_name = {n.name: n for n in self._topo}
        outs = [Variable(by_name[o]) for o in outputs]
        return Model(self.inputs, outs if len(outs) > 1 else outs[0])

    def param_sharding(self, params):
        out = {}
        for n in self._topo:
            if n.name in params and n.name not in out:
                out[n.name] = n.layer.param_sharding(params[n.name])
        return out


def install_imported_weights(model: "KerasNet", weights, states=None,
                             source: str = "imported") -> "KerasNet":
    """Shared installer for model importers (caffe/torch/...): init the
    graph, then overwrite named layers' params (shape-checked) and running
    state. ``weights``/``states`` map layer name → leaf dict."""
    model.init_weights()
    for lname, w in weights.items():
        tmpl = model.params.get(lname)
        if tmpl is None:
            raise ValueError(f"{source} weights for unknown layer {lname!r}")
        for k, v in w.items():
            if k not in tmpl:
                raise ValueError(f"{lname}: {source} provides param {k!r}; "
                                 f"layer has {sorted(tmpl)}")
            if np.shape(tmpl[k]) != np.shape(v):
                raise ValueError(f"{lname}.{k}: {source} weight shape "
                                 f"{np.shape(v)} vs graph "
                                 f"{np.shape(tmpl[k])}")
        model.params[lname] = {k: jnp.asarray(v) for k, v in w.items()}  # zoolint: disable=ZL009 one-time load; per-layer shapes differ, nothing to batch
    for lname, s in (states or {}).items():
        model.net_state[lname] = {k: jnp.asarray(v) for k, v in s.items()}  # zoolint: disable=ZL009 one-time load; per-layer shapes differ
    return model
