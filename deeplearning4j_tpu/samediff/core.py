"""SameDiff-equivalent: define-by-graph symbolic autodiff over JAX.

Reference: ``org.nd4j.autodiff.samediff.SameDiff`` / ``SDVariable`` /
``DifferentialFunction`` and the ``InferenceSession``/``TrainingSession``
executors (SURVEY.md §2.2, §3.3).

TPU-first design — deliberately NOT the reference architecture:

- The reference builds a graph of ``DifferentialFunction`` objects and
  executes it **op-by-op** from Java (one JNI crossing per op), deriving
  gradients by a graph-to-graph transform (per-op ``doDiff``).
- Here the graph is a lightweight recipe (ops from a serializable registry),
  *lowered once* to a pure function, and the whole program — forward,
  ``jax.grad`` backward, updater — is a single XLA executable. Gradient
  construction via ``doDiff`` per op collapses into ``jax.grad``.
- Control flow (reference: TF-style Enter/Exit/Switch/Merge frames walked by
  the Java session) is structured instead: ``lax.cond`` / ``lax.while_loop``
  / ``lax.scan`` behind ``sd.cond`` / ``sd.while_loop``, compiler-friendly
  by construction.

Variable kinds mirror the reference exactly (``VariableType``):
VARIABLE (trainable, persisted), CONSTANT (persisted, not trained),
PLACEHOLDER (fed per call), ARRAY (op output, recomputed).
"""

from __future__ import annotations

import collections
import dataclasses
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

# Registry of pure op implementations: op_name -> fn(*arrays, **attrs).
# Every graph node references an entry here, which is what makes graphs
# serializable (serde.py re-links by name on load).
OP_REGISTRY: dict[str, tp.Callable] = {}


def register_op(name: str):
    def deco(fn):
        OP_REGISTRY[name] = fn
        return fn
    return deco


class VariableType:
    VARIABLE = "VARIABLE"
    CONSTANT = "CONSTANT"
    PLACEHOLDER = "PLACEHOLDER"
    ARRAY = "ARRAY"


@dataclasses.dataclass
class VarMeta:
    name: str
    var_type: str
    shape: tuple | None = None
    dtype: str = "float32"
    # producing op name for ARRAY vars; None otherwise
    producer: str | None = None
    output_index: int = 0


@dataclasses.dataclass
class OpNode:
    name: str
    op_name: str
    inputs: tuple
    outputs: tuple
    attrs: dict = dataclasses.field(default_factory=dict)
    # non-serializable callable attrs (control flow bodies); graph with any
    # of these saves config-only UNLESS the callable was traced into a
    # serializable child graph recorded in ``subgraphs`` (same keys)
    fn_attrs: dict = dataclasses.field(default_factory=dict)
    # fn_attr key -> JSON-able child-graph dict (see serde.subgraph_dict)
    subgraphs: dict = dataclasses.field(default_factory=dict)


class SDVariable:
    """Symbolic tensor handle (reference ``SDVariable``). Arithmetic
    operators build graph nodes via the owning ``SameDiff``'s math ops."""

    __array_priority__ = 100  # beat numpy in mixed expressions

    def __init__(self, sd: "SameDiff", name: str):
        self.sd = sd
        self._name = name

    @property
    def name(self) -> str:
        return self._name

    @property
    def var_type(self) -> str:
        return self.sd.variables[self._name].var_type

    @property
    def shape(self):
        return self.sd.variables[self._name].shape

    def rename(self, new_name: str) -> "SDVariable":
        self.sd.rename_variable(self._name, new_name)
        self._name = new_name
        return self

    def eval(self, placeholders=None):
        return self.sd.output(placeholders or {}, self._name)[self._name]

    def get_arr(self):
        """Value of a VARIABLE/CONSTANT (reference ``SDVariable#getArr``)."""
        return self.sd.arrays[self._name]

    def convert_to_variable(self) -> "SDVariable":
        """CONSTANT -> trainable VARIABLE (reference
        ``SDVariable#convertToVariable``) — how imported frozen weights
        become fine-tunable."""
        meta = self.sd.variables[self._name]
        if meta.var_type == VariableType.CONSTANT:
            meta.var_type = VariableType.VARIABLE
            self.sd._fn_cache.clear()
            # the trainable set changed: updater state must re-initialize
            self.sd._updater_state = None
        elif meta.var_type != VariableType.VARIABLE:
            raise ValueError(
                f"{self._name} is {meta.var_type}, not CONSTANT")
        return self

    def convert_to_constant(self) -> "SDVariable":
        """VARIABLE -> frozen CONSTANT (reference
        ``SDVariable#convertToConstant``)."""
        meta = self.sd.variables[self._name]
        if meta.var_type == VariableType.VARIABLE:
            meta.var_type = VariableType.CONSTANT
            self.sd._fn_cache.clear()
            self.sd._updater_state = None
        elif meta.var_type != VariableType.CONSTANT:
            raise ValueError(
                f"{self._name} is {meta.var_type}, not VARIABLE")
        return self

    def set_arr(self, value):
        self.sd.arrays[self._name] = jnp.asarray(value)
        return self

    # ---- operator sugar (delegates to the math namespace) ----
    def _m(self):
        return self.sd.math

    def __add__(self, o):
        return self._m().add(self, o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._m().sub(self, o)

    def __rsub__(self, o):
        return self._m().rsub(self, o)

    def __mul__(self, o):
        return self._m().mul(self, o)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._m().div(self, o)

    def __rtruediv__(self, o):
        return self._m().rdiv(self, o)

    def __pow__(self, o):
        return self._m().pow(self, o)

    def __neg__(self):
        return self._m().neg(self)

    def __matmul__(self, o):
        return self._m().mmul(self, o)

    def __gt__(self, o):
        return self._m().gt(self, o)

    def __lt__(self, o):
        return self._m().lt(self, o)

    def __ge__(self, o):
        return self._m().gte(self, o)

    def __le__(self, o):
        return self._m().lte(self, o)

    def __getitem__(self, idx):
        return self.sd._op("getitem", [self], index=_encode_index(idx))[0]

    # fluent helpers commonly used on reference SDVariable
    def add(self, o, name=None):
        return self._m().add(self, o, name=name)

    def sub(self, o, name=None):
        return self._m().sub(self, o, name=name)

    def mul(self, o, name=None):
        return self._m().mul(self, o, name=name)

    def div(self, o, name=None):
        return self._m().div(self, o, name=name)

    def mmul(self, o, name=None):
        return self._m().mmul(self, o, name=name)

    def sum(self, *dims, keepdims=False, name=None):
        return self._m().sum(self, dims=dims or None, keepdims=keepdims,
                             name=name)

    def mean(self, *dims, keepdims=False, name=None):
        return self._m().mean(self, dims=dims or None, keepdims=keepdims,
                              name=name)

    def std(self, *dims, keepdims=False, name=None):
        return self._m().std(self, dims=dims or None, keepdims=keepdims,
                             name=name)

    def reshape(self, *shape, name=None):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self.sd.reshape(self, shape, name=name)

    def transpose(self, name=None):
        return self.sd.transpose(self, name=name)

    def permute(self, *dims, name=None):
        return self.sd.permute(self, dims, name=name)

    def cast_to(self, dtype, name=None):
        return self.sd.cast(self, dtype, name=name)

    def __repr__(self):
        m = self.sd.variables[self._name]
        return (f"SDVariable(name={self._name!r}, type={m.var_type}, "
                f"shape={m.shape})")


def _encode_index(idx):
    """Encode a python index expression into a JSON-able attr."""
    def enc(i):
        if isinstance(i, slice):
            return {"slice": [i.start, i.stop, i.step]}
        if i is Ellipsis:
            return {"ellipsis": True}
        if i is None:
            return {"newaxis": True}
        return int(i)
    if isinstance(idx, tuple):
        return {"tuple": [enc(i) for i in idx]}
    return enc(idx)


def _decode_index(enc):
    def dec(e):
        if isinstance(e, dict):
            if "slice" in e:
                return slice(*e["slice"])
            if "ellipsis" in e:
                return Ellipsis
            if "newaxis" in e:
                return None
        return int(e)
    if isinstance(enc, dict) and "tuple" in enc:
        return tuple(dec(e) for e in enc["tuple"])
    return dec(enc)


@register_op("getitem")
def _op_getitem(x, *, index):
    return x[_decode_index(index)]


class SameDiff:
    """The graph container + executor (reference ``SameDiff``).

    Build with ``SameDiff.create()``; define variables/placeholders; call
    namespaced op factories (``sd.math``, ``sd.nn``, ``sd.cnn``, ``sd.rnn``,
    ``sd.loss``, ``sd.random``, ``sd.linalg``, ``sd.image``, ``sd.bitwise``);
    run with ``output()``; train with ``fit()`` after ``set_training_config``.
    """

    def __init__(self):
        self.variables: dict[str, VarMeta] = {}
        self.ops: dict[str, OpNode] = {}  # insertion order == topo order
        self.arrays: dict[str, jnp.ndarray] = {}  # VARIABLE/CONSTANT values
        self._name_counter = collections.Counter()
        self.loss_variables: list[str] = []
        self.training_config = None
        self._updater_state = None
        self._iteration_count = 0
        self._epoch_count = 0
        self._listeners = []
        self._fn_cache: dict = {}
        # lazily-built namespaces (import cycle: ops.py imports core)
        self._ns = {}

    # ---------------- namespaces ----------------
    def _namespace(self, key):
        if key not in self._ns:
            from deeplearning4j_tpu.samediff import ops as _ops
            self._ns[key] = _ops.NAMESPACES[key](self)
        return self._ns[key]

    @property
    def math(self):
        return self._namespace("math")

    @property
    def nn(self):
        return self._namespace("nn")

    @property
    def cnn(self):
        return self._namespace("cnn")

    @property
    def rnn(self):
        return self._namespace("rnn")

    @property
    def loss(self):
        return self._namespace("loss")

    @property
    def random(self):
        return self._namespace("random")

    @property
    def linalg(self):
        return self._namespace("linalg")

    @property
    def image(self):
        return self._namespace("image")

    @property
    def bitwise(self):
        return self._namespace("bitwise")

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # ---------------- variable definition ----------------
    def _unique(self, base: str) -> str:
        if base not in self.variables and base not in self.ops:
            return base
        while True:
            self._name_counter[base] += 1
            cand = f"{base}_{self._name_counter[base]}"
            if cand not in self.variables and cand not in self.ops:
                return cand

    def var(self, name=None, shape=None, weight_init=None, dtype="float32",
            value=None, key=None) -> SDVariable:
        """Trainable VARIABLE. Either ``value`` or (``shape`` +
        ``weight_init``) — default init Xavier like the reference."""
        name = self._unique(name or "variable")
        if value is not None:
            arr = jnp.asarray(value, dtype=dtype)
            shape = arr.shape
        else:
            if shape is None:
                raise ValueError("var() needs shape or value")
            arr = _init_array(shape, weight_init, dtype, key)
        self.variables[name] = VarMeta(name, VariableType.VARIABLE,
                                       tuple(shape), str(dtype))
        self.arrays[name] = arr
        return SDVariable(self, name)

    def constant(self, value, name=None, dtype=None) -> SDVariable:
        name = self._unique(name or "constant")
        arr = jnp.asarray(value, dtype=dtype)
        self.variables[name] = VarMeta(name, VariableType.CONSTANT,
                                       tuple(arr.shape), str(arr.dtype))
        self.arrays[name] = arr
        return SDVariable(self, name)

    def placeholder(self, name, shape=None, dtype="float32") -> SDVariable:
        name = self._unique(name)
        self.variables[name] = VarMeta(
            name, VariableType.PLACEHOLDER,
            tuple(shape) if shape is not None else None, str(dtype))
        return SDVariable(self, name)

    def rename_variable(self, old: str, new: str) -> None:
        if new in self.variables:
            raise ValueError(f"variable {new!r} already exists")
        meta = self.variables.pop(old)
        meta.name = new
        self.variables[new] = meta
        if old in self.arrays:
            self.arrays[new] = self.arrays.pop(old)
        for op in self.ops.values():
            op.inputs = tuple(new if i == old else i for i in op.inputs)
            op.outputs = tuple(new if o == old else o for o in op.outputs)
        self.loss_variables = [new if v == old else v
                               for v in self.loss_variables]
        self._fn_cache.clear()

    # ---------------- graph building ----------------
    def _coerce(self, x) -> str:
        """Turn a non-SDVariable operand into a CONSTANT; return var name."""
        if isinstance(x, SDVariable):
            return x.name
        return self.constant(x).name

    def _op(self, op_name, inputs, n_out=1, name=None, fn_attrs=None,
            subgraphs=None, **attrs) -> list[SDVariable]:
        if op_name not in OP_REGISTRY:
            raise KeyError(f"op {op_name!r} not in registry")
        node_name = self._unique(name or op_name)
        in_names = tuple(self._coerce(x) for x in inputs)
        out_names = tuple(
            node_name if i == 0 and n_out == 1 else f"{node_name}:{i}"
            for i in range(n_out))
        for i, o in enumerate(out_names):
            self.variables[o] = VarMeta(o, VariableType.ARRAY,
                                        producer=node_name, output_index=i)
        self.ops[node_name] = OpNode(node_name, op_name, in_names, out_names,
                                     dict(attrs), dict(fn_attrs or {}),
                                     dict(subgraphs or {}))
        self._fn_cache.clear()
        return [SDVariable(self, o) for o in out_names]

    # ---------------- lowering + execution ----------------
    def _ancestor_ops(self, outputs: tp.Sequence[str]) -> list[OpNode]:
        """Demand-driven subgraph: ops reachable backwards from outputs, in
        original (topological) insertion order. Mirrors the reference's
        ``AbstractSession`` dependency subgraph build — but resolved once at
        trace time, not per step."""
        needed_vars = set(outputs)
        needed_ops = set()
        for op in reversed(list(self.ops.values())):
            if any(o in needed_vars for o in op.outputs):
                needed_ops.add(op.name)
                needed_vars.update(op.inputs)
        return [op for op in self.ops.values() if op.name in needed_ops]

    def make_function(self, outputs: tp.Sequence[str]):
        """Lower the subgraph producing ``outputs`` to a pure function
        ``fn(var_arrays: dict, placeholders: dict) -> dict``. The returned
        function is jit-safe; ``output()``/``fit()`` wrap it in ``jax.jit``.
        """
        outputs = tuple(outputs)
        plan = self._ancestor_ops(outputs)

        def fn(var_arrays, placeholders):
            env = dict(var_arrays)
            env.update(placeholders)
            for op in plan:
                impl = OP_REGISTRY[op.op_name]
                try:
                    args = [env[i] for i in op.inputs]
                except KeyError as e:
                    raise KeyError(
                        f"op {op.name!r} input {e} not available — missing "
                        f"placeholder?") from e
                res = impl(*args, **op.attrs, **op.fn_attrs)
                if len(op.outputs) == 1:
                    env[op.outputs[0]] = res
                else:
                    for o, r in zip(op.outputs, res):
                        env[o] = r
            return {o: env[o] for o in outputs}

        return fn

    def graph_signature(self) -> str:
        """Structural content key of the graph (non-ARRAY variables + op
        topology + attrs) for the AOT executable cache: two SameDiff
        instances holding the same program share compiled step/output
        executables; any graph mutation changes the key. Raw-closure
        control-flow bodies key by identity — never shared, and PINNED
        (aot_cache.pin_id) so a dead graph's recycled addresses cannot
        alias a new graph's key while its executables persist."""
        import hashlib

        from deeplearning4j_tpu.optimize import aot_cache as _aot

        h = hashlib.sha1()
        for v in self.variables.values():
            if v.var_type != VariableType.ARRAY:
                h.update(
                    f"{v.var_type}|{v.name}|{v.shape}|{v.dtype}\n".encode())
        for op in self.ops.values():
            h.update(f"{op.name}|{op.op_name}|{op.inputs}|"
                     f"{op.outputs}|".encode())
            try:
                h.update(repr(sorted(op.attrs.items())).encode())
            except Exception:
                h.update(f"id:{_aot.pin_id(op)}".encode())
            for k in sorted(op.fn_attrs):
                sub = op.subgraphs.get(k)
                if sub is not None:
                    h.update(f"{k}:sub:{repr(sub)}".encode())
                else:
                    h.update(
                        f"{k}:fn:{_aot.pin_id(op.fn_attrs[k])}".encode())
            h.update(b"\n")
        h.update(repr(sorted(self.loss_variables)).encode())
        return h.hexdigest()

    def _jitted(self, outputs: tuple):
        if outputs not in self._fn_cache:
            from deeplearning4j_tpu.optimize import aot_cache

            raw = self.make_function(outputs)
            self._fn_cache[outputs] = aot_cache.wrap(
                jax.jit(raw), "sd:" + self.graph_signature(),
                f"output:{outputs}")
        return self._fn_cache[outputs]

    def output(self, placeholders: dict | None, *outputs) -> dict:
        """Run inference (reference ``SameDiff#output``). ``outputs`` may be
        names or SDVariables; returns {name: array}. Whole subgraph runs as
        one jitted XLA program."""
        names = tuple(o.name if isinstance(o, SDVariable) else o
                      for o in outputs)
        if not names:
            raise ValueError("no outputs requested")
        ph = {k: jnp.asarray(v) for k, v in (placeholders or {}).items()}
        fn = self._jitted(names)
        return dict(fn(self.arrays, ph))

    def batch_output(self, placeholders, *outputs):
        return self.output(placeholders, *outputs)

    # convenience mirrors of reference exec API
    def outputs(self) -> list[str]:
        """Terminal ARRAY variables (consumed by no op)."""
        consumed = {i for op in self.ops.values() for i in op.inputs}
        return [v.name for v in self.variables.values()
                if v.var_type == VariableType.ARRAY and v.name not in consumed]

    def inputs(self) -> list[str]:
        return [v.name for v in self.variables.values()
                if v.var_type == VariableType.PLACEHOLDER]

    def trainable_variables(self) -> list[str]:
        return [v.name for v in self.variables.values()
                if v.var_type == VariableType.VARIABLE]

    # ---------------- gradients ----------------
    def calculate_gradients(self, placeholders: dict | None,
                            *wrt) -> dict:
        """d(sum of loss variables)/d(wrt...) — reference
        ``SameDiff#calculateGradients``. The reference builds a second grad
        graph via per-op ``doDiff``; here ``jax.grad`` differentiates the
        lowered program directly."""
        if not self.loss_variables:
            raise ValueError("no loss variables set; call "
                             "set_loss_variables() or use sd.loss.* ops")
        wrt_names = [w.name if isinstance(w, SDVariable) else w for w in wrt]
        if not wrt_names:
            wrt_names = self.trainable_variables()
        ph = {k: jnp.asarray(v) for k, v in (placeholders or {}).items()}
        fn = self.make_function(tuple(self.loss_variables))

        def scalar_loss(wrt_arrays):
            merged = dict(self.arrays)
            merged.update(wrt_arrays)
            outs = fn(merged, ph)
            return sum(jnp.sum(v) for v in outs.values())

        wrt_arrays = {n: self.arrays[n] for n in wrt_names}
        return jax.grad(scalar_loss)(wrt_arrays)

    calculateGradients = calculate_gradients

    def set_loss_variables(self, *vars_):
        self.loss_variables = [v.name if isinstance(v, SDVariable) else v
                               for v in vars_]

    def mark_loss(self, var):
        name = var.name if isinstance(var, SDVariable) else var
        if name not in self.loss_variables:
            self.loss_variables.append(name)

    # ---------------- training ----------------
    def set_training_config(self, cfg) -> None:
        self.training_config = cfg
        self._updater_state = None
        self._fn_cache.pop("__train_step__", None)

    def fit(self, iterator=None, epochs: int = 1, features=None, labels=None):
        from deeplearning4j_tpu.samediff.training import fit as _fit
        return _fit(self, iterator, epochs, features=features, labels=labels)

    def set_listeners(self, *listeners):
        self._listeners = list(listeners)

    # ---------------- structural ops (on sd, like reference) ----------------
    def reshape(self, x, shape, name=None):
        return self._op("reshape", [x], name=name, shape=tuple(shape))[0]

    def transpose(self, x, name=None):
        return self._op("transpose", [x], name=name)[0]

    def permute(self, x, dims, name=None):
        return self._op("permute", [x], name=name, dims=tuple(dims))[0]

    def concat(self, dim, *xs, name=None):
        return self._op("concat", list(xs), name=name, axis=int(dim))[0]

    def stack(self, axis, *xs, name=None):
        return self._op("stack", list(xs), name=name, axis=int(axis))[0]

    def unstack(self, x, axis, num, name=None):
        return self._op("unstack", [x], n_out=num, name=name,
                        axis=int(axis), num=int(num))

    def squeeze(self, x, axis, name=None):
        return self._op("squeeze", [x], name=name, axis=int(axis))[0]

    def expand_dims(self, x, axis, name=None):
        return self._op("expand_dims", [x], name=name, axis=int(axis))[0]

    def tile(self, x, reps, name=None):
        return self._op("tile", [x], name=name, reps=tuple(reps))[0]

    def cast(self, x, dtype, name=None):
        return self._op("cast", [x], name=name, dtype=str(dtype))[0]

    def slice(self, x, begin, size, name=None):
        return self._op("slice_op", [x], name=name, begin=tuple(begin),
                        size=tuple(size))[0]

    def gather(self, x, indices, axis=0, name=None):
        return self._op("gather", [x, indices], name=name, axis=int(axis))[0]

    def one_hot(self, indices, depth, name=None):
        return self._op("one_hot", [indices], name=name, depth=int(depth))[0]

    # scatter family (reference SDBaseOps scatter*: rows of `ref` selected
    # by `indices` (axis 0) combined with `updates`; duplicates accumulate)
    def scatter_update(self, ref, indices, updates, name=None):
        return self._op("scatter.update", [ref, indices, updates],
                        name=name)[0]

    def scatter_add(self, ref, indices, updates, name=None):
        return self._op("scatter.add", [ref, indices, updates], name=name)[0]

    def scatter_sub(self, ref, indices, updates, name=None):
        return self._op("scatter.sub", [ref, indices, updates], name=name)[0]

    def scatter_mul(self, ref, indices, updates, name=None):
        return self._op("scatter.mul", [ref, indices, updates], name=name)[0]

    def scatter_div(self, ref, indices, updates, name=None):
        return self._op("scatter.div", [ref, indices, updates], name=name)[0]

    def scatter_max(self, ref, indices, updates, name=None):
        return self._op("scatter.max", [ref, indices, updates], name=name)[0]

    def scatter_min(self, ref, indices, updates, name=None):
        return self._op("scatter.min", [ref, indices, updates], name=name)[0]

    def gather_nd(self, x, indices, name=None):
        return self._op("gather_nd", [x, indices], name=name)[0]

    # scatter-nd family (reference scatter_nd / scatter_nd_add /
    # scatter_nd_sub / scatter_nd_update: index TUPLES in the trailing
    # dim select elements; scatter_nd builds from zeros, duplicates sum)
    def scatter_nd(self, indices, updates, shape, name=None):
        return self._op("scatter.nd", [indices, updates], name=name,
                        shape=tuple(int(s) for s in shape))[0]

    def scatter_nd_add(self, ref, indices, updates, name=None):
        return self._op("scatter.ndAdd", [ref, indices, updates],
                        name=name)[0]

    def scatter_nd_sub(self, ref, indices, updates, name=None):
        return self._op("scatter.ndSub", [ref, indices, updates],
                        name=name)[0]

    def scatter_nd_update(self, ref, indices, updates, name=None):
        return self._op("scatter.ndUpdate", [ref, indices, updates],
                        name=name)[0]

    def split_v(self, x, sizes, axis=0, name=None):
        """Unequal-size split (reference split_v); `split` stays the
        equal-parts form."""
        return tuple(self._op("split_v", [x], n_out=len(sizes), name=name,
                              sizes=tuple(int(s) for s in sizes),
                              axis=int(axis)))

    # segment family (reference SDBaseOps segment* / unsortedSegment*: the
    # jax impls don't require sorted ids, so both surfaces share one op.
    # DEVIATION: num_segments is always required — XLA needs static output
    # shapes, so the sorted variants cannot infer it from the ids at run
    # time the way the reference kernels do)
    def _segment(self, kind, data, ids, num_segments, name):
        return self._op(f"segment.{kind}", [data, ids], name=name,
                        num_segments=int(num_segments))[0]

    def segment_sum(self, data, ids, num_segments, name=None):
        return self._segment("sum", data, ids, num_segments, name)

    def segment_mean(self, data, ids, num_segments, name=None):
        return self._segment("mean", data, ids, num_segments, name)

    def segment_max(self, data, ids, num_segments, name=None):
        return self._segment("max", data, ids, num_segments, name)

    def segment_min(self, data, ids, num_segments, name=None):
        return self._segment("min", data, ids, num_segments, name)

    def segment_prod(self, data, ids, num_segments, name=None):
        return self._segment("prod", data, ids, num_segments, name)

    unsorted_segment_sum = segment_sum
    unsorted_segment_mean = segment_mean
    unsorted_segment_max = segment_max
    unsorted_segment_min = segment_min
    unsorted_segment_prod = segment_prod

    def sequence_mask(self, lengths, maxlen, dtype="float32", name=None):
        return self._op("sequence_mask", [lengths], name=name,
                        maxlen=int(maxlen), dtype=str(dtype))[0]

    def shape_of(self, x, name=None):
        return self._op("shape_of", [x], name=name)[0]

    def zeros_like(self, x, name=None):
        return self._op("zeros_like", [x], name=name)[0]

    def ones_like(self, x, name=None):
        return self._op("ones_like", [x], name=name)[0]

    def eye(self, n, name=None):
        return self.constant(jnp.eye(n), name=name)

    def linspace(self, start, stop, num, name=None):
        return self.constant(jnp.linspace(start, stop, num), name=name)

    def range(self, start, stop, step=1, name=None, dtype="int32"):
        return self.constant(jnp.arange(start, stop, step, dtype=dtype),
                             name=name)

    # ---------------- control flow (structured, lax-backed) ----------------
    def _try_trace(self, fn, n_args):
        """Trace ``fn`` symbolically into a fresh child SameDiff by calling
        it on placeholder SDVariables. Returns (child, out_names,
        serializable) when the callable stayed inside SDVariable ops
        (``serializable`` is False if a NESTED control-flow body inside it
        used raw jax — executable, but save() must reject it), or None when
        ``fn`` itself used raw jax/numpy (still executable via the raw
        closure, just never saveable)."""
        child = SameDiff()
        args = [child.placeholder(f"arg{i}") for i in range(n_args)]
        before_ops = set(self.ops)
        before_vars = set(self.variables)
        polluted = False
        try:
            out = fn(*args)
        except (TypeError, AttributeError):
            # raw jax/numpy applied to an SDVariable placeholder fails with
            # one of these (incl. float(v) coercions — TypeError); the set
            # stays NARROW on purpose: a ValueError from a genuine user bug
            # must propagate here, at the cond/while/scan call site, not be
            # silently routed to the raw-closure path to resurface at a
            # distant jit trace. NOTE: the probe CALLS the body once at
            # graph build — side effects run here too (see cond docstring).
            out = None
        finally:
            # a callable mixing parent-graph variables creates stray nodes
            # in the PARENT during the probe — always roll those back
            # (including when a user bug propagates out of the probe)
            if (set(self.ops) != before_ops
                    or set(self.variables) != before_vars):
                polluted = True
                for k in set(self.ops) - before_ops:
                    del self.ops[k]
                for k in set(self.variables) - before_vars:
                    del self.variables[k]
                    self.arrays.pop(k, None)
                self._fn_cache.clear()
        if polluted or out is None:
            return None
        outs = list(out) if isinstance(out, (list, tuple)) else [out]
        if not all(isinstance(o, SDVariable) and o.sd is child
                   for o in outs):
            return None
        serializable = True
        for op in child.ops.values():
            if any(i not in child.variables for i in op.inputs):
                return None  # referenced a variable outside the child graph
            if set(op.fn_attrs) - set(op.subgraphs):
                # nested control flow with an untraceable body: the child
                # graph runs fine but would serialize without the inner
                # callables — mark the whole subgraph unsaveable
                serializable = False
        return child, [o.name for o in outs], serializable

    def cond(self, pred, true_fn, false_fn, operands, name=None,
             n_out: int = 1):
        """Structured conditional — replaces the reference's Switch/Merge
        frame machinery with ``lax.cond`` (compiler-friendly; both branches
        traced once). ``true_fn``/``false_fn`` map arrays -> array (or a
        tuple of ``n_out`` arrays — both branches must agree). Returns one
        SDVariable, or a tuple of ``n_out`` of them. When the callables
        stay inside SDVariable ops the graph remains serializable
        (save/load round-trips the branches).

        BUILD-TIME PROBE CONTRACT (also for while_loop/scan): each body is
        CALLED once on symbolic placeholders at graph build to decide
        serializability — side effects in the body run at build time, and
        bodies needing concrete values (``float(v)``, data-dependent
        Python branching) fall back to the raw-closure (unsaveable) path."""
        from deeplearning4j_tpu.samediff import serde as _serde

        n = len(operands)
        single = n_out == 1
        traced_t = self._try_trace(true_fn, n)
        traced_f = self._try_trace(false_fn, n)
        fn_attrs = {"true_fn": true_fn, "false_fn": false_fn}
        subgraphs = {}
        if traced_t is not None and traced_f is not None:
            (ct, ot, st), (cf, of, sf) = traced_t, traced_f
            if len(ot) != n_out or len(of) != n_out:
                raise ValueError(
                    f"cond branches returned {len(ot)}/{len(of)} outputs, "
                    f"expected n_out={n_out}")
            fn_attrs = {"true_fn": subgraph_callable(ct, ot, single=single),
                        "false_fn": subgraph_callable(cf, of, single=single)}
            if st and sf:
                subgraphs = {
                    "true_fn": _serde.subgraph_dict(ct, ot, single=single),
                    "false_fn": _serde.subgraph_dict(cf, of, single=single)}
        outs = self._op("cond", [pred] + list(operands), n_out=n_out,
                        name=name, fn_attrs=fn_attrs, subgraphs=subgraphs)
        return outs[0] if single else tuple(outs)

    def while_loop(self, cond_fn, body_fn, operands, name=None,
                   max_iterations: Optional[int] = None):
        """Structured while — replaces Enter/Exit/NextIteration frames with
        ``lax.while_loop``. ``operands`` is the loop carry (list of vars);
        returns the final carry as a tuple of SDVariables. Serializable
        when the callables stay inside SDVariable ops.

        ``max_iterations``: an upper trip-count bound. When given, the
        loop lowers to a masked ``lax.scan`` of exactly that length —
        results match the unbounded form whenever the loop exits within
        the bound, and the loop becomes REVERSE-DIFFERENTIABLE (training
        can backprop through it; raw ``lax.while_loop`` has no transpose
        rule — the reference's TrainingSession backprops through its loop
        frames, and this is the TPU-native path to the same capability)."""
        from deeplearning4j_tpu.samediff import serde as _serde

        n = len(operands)
        traced_c = self._try_trace(cond_fn, n)
        traced_b = self._try_trace(body_fn, n)
        fn_attrs = {"cond_fn": cond_fn, "body_fn": body_fn}
        subgraphs = {}
        if traced_c is not None and traced_b is not None:
            (cc, oc, sc), (cb, ob, sb) = traced_c, traced_b
            fn_attrs = {"cond_fn": subgraph_callable(cc, oc, single=True),
                        "body_fn": subgraph_callable(cb, ob, single=False)}
            if sc and sb:
                subgraphs = {
                    "cond_fn": _serde.subgraph_dict(cc, oc, single=True),
                    "body_fn": _serde.subgraph_dict(cb, ob, single=False)}
        return self._op("while_loop", list(operands), n_out=n, name=name,
                        fn_attrs=fn_attrs, subgraphs=subgraphs,
                        max_iterations=(None if max_iterations is None
                                        else int(max_iterations)))

    def scan(self, body_fn, init, xs, name=None):
        """``lax.scan`` over leading axis of ``xs``; body maps
        (carry, x) -> (carry, y). Returns (final_carry, ys). Serializable
        when ``body_fn`` stays inside SDVariable ops."""
        from deeplearning4j_tpu.samediff import serde as _serde

        traced = self._try_trace(body_fn, 2)
        fn_attrs = {"body_fn": body_fn}
        subgraphs = {}
        if traced is not None:
            child, outs, ser = traced
            if len(outs) == 2:
                fn_attrs = {"body_fn": subgraph_callable(child, outs,
                                                         single=False)}
                if ser:
                    subgraphs = {"body_fn": _serde.subgraph_dict(
                        child, outs, single=False)}
        return self._op("scan_op", [init, xs], n_out=2, name=name,
                        fn_attrs=fn_attrs, subgraphs=subgraphs)

    # ---------------- persistence ----------------
    def save(self, path, save_updater_state: bool = True):
        from deeplearning4j_tpu.samediff.serde import save as _save
        _save(self, path, save_updater_state)

    @staticmethod
    def load(path):
        from deeplearning4j_tpu.samediff.serde import load as _load
        return _load(path)

    def summary(self) -> str:
        lines = [f"SameDiff: {len(self.variables)} variables, "
                 f"{len(self.ops)} ops"]
        for v in self.variables.values():
            if v.var_type != VariableType.ARRAY:
                lines.append(f"  {v.var_type:<12} {v.name:<24} "
                             f"shape={v.shape}")
        for op in self.ops.values():
            lines.append(f"  OP {op.op_name:<18} {op.name:<24} "
                         f"{op.inputs} -> {op.outputs}")
        return "\n".join(lines)


def subgraph_callable(child: "SameDiff", out_names: list, single: bool):
    """Turn a traced child graph into a plain ``f(*arrays) -> array/tuple``
    suitable for ``lax.cond/while_loop/scan`` bodies."""
    fn = child.make_function(tuple(out_names))
    arg_names = [v.name for v in child.variables.values()
                 if v.var_type == VariableType.PLACEHOLDER]

    def call(*xs):
        res = fn(child.arrays, dict(zip(arg_names, xs)))
        outs = [res[o] for o in out_names]
        return outs[0] if single else tuple(outs)

    return call


def _init_array(shape, weight_init, dtype, key):
    """Init a VARIABLE. Accepts a conf.weights WeightInit or None (Xavier,
    the reference default for SDVariable trainables)."""
    shape = tuple(int(s) for s in shape)
    if key is None:
        key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
    if weight_init is None:
        if len(shape) >= 2:
            fan_in, fan_out = shape[-2], shape[-1]
            std = float(np.sqrt(2.0 / (fan_in + fan_out)))
            return std * jax.random.normal(key, shape, dtype=dtype)
        return jnp.zeros(shape, dtype=dtype)
    if callable(getattr(weight_init, "init", None)):
        fan_in = shape[0] if len(shape) > 1 else 1
        fan_out = shape[-1]
        return weight_init.init(key, shape, fan_in, fan_out).astype(dtype)
    raise TypeError(f"bad weight_init {weight_init!r}")


# ---- structural op impls (registered) ----

@register_op("identity")
def _op_identity(x):
    return x


@register_op("reshape_onnx")
def _op_reshape_onnx(x, *, shape):
    """ONNX Reshape semantics: 0 copies the input dim, -1 infers."""
    shape = tuple(x.shape[i] if s == 0 else s for i, s in enumerate(shape))
    return x.reshape(shape)


@register_op("unsqueeze_onnx")
def _op_unsqueeze_onnx(x, *, axes):
    """ONNX Unsqueeze: axes are relative to the OUTPUT rank."""
    out_rank = x.ndim + len(axes)
    for a in sorted(a % out_rank for a in axes):
        x = jnp.expand_dims(x, a)
    return x


@register_op("softmax_flattened")
def _op_softmax_flattened(x, *, axis):
    """ONNX opset<13 Softmax: coerce to 2D at ``axis``, softmax the flat
    tail, restore shape."""
    import numpy as _np

    lead = int(_np.prod(x.shape[:axis], dtype=_np.int64)) if axis else 1
    flat = x.reshape(lead, -1)
    return jax.nn.softmax(flat, axis=-1).reshape(x.shape)


@register_op("flatten2d")
def _op_flatten2d(x):
    """[b, ...] -> [b, prod(...)] (ONNX Flatten / Keras Flatten)."""
    return x.reshape(x.shape[0], -1)


@register_op("reshape")
def _op_reshape(x, *, shape):
    return jnp.reshape(x, shape)


@register_op("transpose")
def _op_transpose(x):
    return jnp.transpose(x)


@register_op("permute")
def _op_permute(x, *, dims):
    return jnp.transpose(x, dims)


@register_op("concat")
def _op_concat(*xs, axis):
    return jnp.concatenate(xs, axis=axis)


@register_op("stack")
def _op_stack(*xs, axis):
    return jnp.stack(xs, axis=axis)


@register_op("unstack")
def _op_unstack(x, *, axis, num):
    parts = jnp.split(x, num, axis=axis)
    return tuple(jnp.squeeze(p, axis=axis) for p in parts)


@register_op("split")
def _op_split(x, *, axis, num=None, sizes=None):
    """Even split (``num``) or ragged split (``sizes``, TF SplitV). A
    single ``-1`` size is inferred from the input dim (TF semantics);
    shapes are concrete at trace time."""
    if sizes is not None:
        sizes = [int(s) for s in sizes]
        if sizes.count(-1) > 1:
            raise ValueError("split: at most one size may be -1")
        if -1 in sizes:
            known = sum(s for s in sizes if s >= 0)
            sizes[sizes.index(-1)] = int(x.shape[axis]) - known
        cuts = list(np.cumsum(sizes[:-1]))
        return tuple(jnp.split(x, cuts, axis=axis))
    return tuple(jnp.split(x, num, axis=axis))


@register_op("select_tf")
def _op_select_tf(cond, a, b):
    """TF ``Select`` (v1): a rank-1 condition of length B against rank-N
    operands selects whole leading-dim rows (unlike where's trailing
    broadcast)."""
    c = cond.astype(bool)
    if c.ndim == 1 and a.ndim > 1:
        c = c.reshape((-1,) + (1,) * (a.ndim - 1))
    return jnp.where(c, a, b)


@register_op("strided_slice")
def _op_strided_slice(x, *, begin, end, strides, begin_mask=0, end_mask=0,
                      ellipsis_mask=0, new_axis_mask=0, shrink_axis_mask=0):
    """TF StridedSlice semantics for STATIC begin/end/strides, with the
    common masks (begin/end/shrink). Cite: reference StridedSlice import in
    TFGraphMapper per-op mappings."""
    if ellipsis_mask or new_axis_mask:
        raise NotImplementedError(
            "strided_slice: ellipsis_mask/new_axis_mask not supported")
    idx = []
    for i in range(len(begin)):
        if shrink_axis_mask & (1 << i):
            idx.append(int(begin[i]))
            continue
        b = None if (begin_mask & (1 << i)) else int(begin[i])
        e = None if (end_mask & (1 << i)) else int(end[i])
        idx.append(slice(b, e, int(strides[i])))
    return x[tuple(idx)]


@register_op("squeeze")
def _op_squeeze(x, *, axis):
    return jnp.squeeze(x, axis=axis)


@register_op("expand_dims")
def _op_expand_dims(x, *, axis):
    return jnp.expand_dims(x, axis=axis)


@register_op("tile")
def _op_tile(x, *, reps):
    return jnp.tile(x, reps)


@register_op("cast")
def _op_cast(x, *, dtype):
    return x.astype(dtype)


@register_op("slice_op")
def _op_slice(x, *, begin, size):
    return jax.lax.dynamic_slice(x, begin, size)


@register_op("gather")
def _op_gather(x, indices, *, axis):
    return jnp.take(x, indices.astype(jnp.int32), axis=axis)


@register_op("one_hot")
def _op_one_hot(indices, *, depth, axis=-1, dtype="float32"):
    r = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=dtype)
    return jnp.moveaxis(r, -1, axis) if axis != -1 else r


@register_op("shape_of")
def _op_shape_of(x):
    return jnp.asarray(x.shape, dtype=jnp.int32)


@register_op("zeros_like")
def _op_zeros_like(x):
    return jnp.zeros_like(x)


@register_op("ones_like")
def _op_ones_like(x):
    return jnp.ones_like(x)


@register_op("cond")
def _op_cond(pred, *operands, true_fn, false_fn):
    return jax.lax.cond(pred.astype(bool).reshape(()), true_fn, false_fn,
                        *operands)


@register_op("while_loop")
def _op_while_loop(*operands, cond_fn, body_fn, max_iterations=None):
    def as_carry(r):
        # a single-carry body may return a bare array; tuple(r) would
        # wrongly iterate its elements
        return tuple(r) if isinstance(r, (tuple, list)) else (r,)

    if max_iterations is None:
        return jax.lax.while_loop(
            lambda c: cond_fn(*c).astype(bool).reshape(()),
            lambda c: as_carry(body_fn(*c)), tuple(operands))

    # bounded form: a scan over max_iterations steps — identical results
    # whenever the loop exits within the bound, and REVERSE-DIFFERENTIABLE
    # (lax.while_loop has no transpose rule; scan does). The step is a
    # lax.cond, NOT a jnp.where over an always-evaluated body: once the
    # condition goes false the body never runs, so a body that would be
    # undefined past exit (divide-by-zero at the boundary, say) neither
    # poisons the forward nor turns the where-transpose into 0*inf NaNs.
    def step(c):
        new = as_carry(body_fn(*c))
        if len(new) != len(c):
            raise ValueError(
                f"while_loop body returned {len(new)} outputs for a "
                f"{len(c)}-element carry (the unbounded lowering rejects "
                "this too)")
        return new

    def body(c, _):
        pred = cond_fn(*c).astype(bool).reshape(())
        return jax.lax.cond(pred, step, lambda c: c, c), None

    out, _ = jax.lax.scan(body, tuple(operands), None,
                          length=int(max_iterations))
    return out


@register_op("scan_op")
def _op_scan(init, xs, *, body_fn):
    return jax.lax.scan(body_fn, init, xs)
