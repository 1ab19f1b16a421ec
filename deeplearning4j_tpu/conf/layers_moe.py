"""Mixture-of-Experts layer for the conf DSL (beyond the reference —
DL4J has no MoE, SURVEY.md §2.3 lists expert parallelism absent; this
makes GShard-style MoE a first-class layer that lowers through
MultiLayerNetwork/ComputationGraph and trains data+expert-parallel under
``ParallelWrapper(expert_parallel=True)`` with no hand-written
shard_map).

The math lives in ``parallel/expert.py::moe_apply`` (shared with the raw
shard_map entrypoints, so the layer and the library demos cannot
diverge): top-k routing with renormalized gates, per-expert capacity
with residual pass-through for dropped tokens, and — when the expert
weights arrive sharded (``e_loc < n_experts`` under the wrapper's
shard_map) — an ``all_to_all`` token exchange over the active mesh axis.

The GShard load-balance auxiliary loss reaches the training objective
through the reserved state key :data:`AUX_LOSS_KEY`: the layer writes
its (already ``aux_weight``-scaled) aux into the state it returns, and
both network ``_loss`` implementations add every such entry to the
score. In eval/``output()`` the state entry is ignored.

:class:`RoutedExpertsLayer` is the DROPLESS layer serving runs
(``nn.decoding`` refuses :class:`MoELayer`, whose capacity couples the
rows of a batch): sigmoid scores, a top-k choice, no capacity, a grouped
matrix product over the token slots sorted by expert (on a TPU a Pallas
kernel, ``ops.routed_experts``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import serde
from deeplearning4j_tpu.conf import inputs as it
from deeplearning4j_tpu.conf.layers import BaseLayer
from deeplearning4j_tpu.conf.layers_hybrid import _dot, _matrix, _wdtype, swiglu

#: Reserved state key: layers put auxiliary (train-time) loss terms here;
#: MultiLayerNetwork/ComputationGraph ``_loss`` sums them into the score.
AUX_LOSS_KEY = "__aux_loss__"
ROUTED_ROWS_MAX = 4096      # tokens a RoutedExpertsLayer routes at a time
# Up to ``most`` slots (token x chosen expert) an expert held, a
# RoutedExpertsLayer's rows are FEW: the time is the reading of the
# matrices. A program lowered for a TPU then reads the touched experts'
# matrices where they lie, in one Pallas kernel
# (``ops.routed_experts.touched_experts_ffn``). Every other platform puts
# every token through every expert held from ``fewest`` slots on (below,
# most experts are chosen by nobody) and groups the slots by expert
# elsewhere; beyond ``most`` every platform groups them: every expert
# would be ``held / top_k`` times the matrix unit's work. Read on the v5e
# at hidden 2048, 128 experts of 1024, top 8 (tools/chip/moe_crossover.py;
# PERF.md section 6, PR 33 and PR 36), ms a layer:
#   rows  slots  touched | every expert  grouped  the kernel
#      8    0.5       51 |        2.290    1.046       0.868
#     16    1         81 |        2.289    1.780       1.371
#     32    2        106 |        2.293    2.768       1.787
#     64    4        127 |        2.298    5.057       2.139
#    128    8        128 |        2.306    5.171       2.170
#    256   16        128 |        2.904    5.331       2.226
#   1024   64        128 |       10.65     6.50    not measured
# The kernel is bound by its copies (754 GB/s at 32 rows; its products
# alone take 0.42 ms there and 2.15 at 256 rows, as long as the copies),
# so one path serves the whole range on the TPU.
EVERY_EXPERT_SLOTS = (2, 24)


def sum_aux_losses(new_state, dtype):
    """Total of every layer's reserved aux-loss entry (train-time only —
    callers gate on ``train``; shared by MultiLayerNetwork and
    ComputationGraph ``_loss`` so the contract cannot diverge)."""
    total = 0.0
    for s in new_state.values():
        if isinstance(s, dict) and AUX_LOSS_KEY in s:
            total = total + s[AUX_LOSS_KEY].astype(dtype)
    return total


@serde.register
@dataclasses.dataclass
class MoELayer(BaseLayer):
    """GShard-style MoE FFN block: router -> top-k dispatch (capacity C)
    -> per-expert relu FFN -> gated combine, residual around the whole
    block (output size == input size).

    ``capacity_factor`` sizes C = ceil(top_k * tokens / n_experts * cf)
    per shard. Under ``ParallelWrapper(expert_parallel=True)`` the
    ``w1/b1/w2/b2`` leaves shard over the mesh's data axis (experts ride
    the same axis as the batch, the GShard layout — see
    ``param_shard_axes``); standalone, all experts run locally."""

    scope_class = "moe"

    n_experts: int = 4
    d_hidden: int = 0          # 0 -> 4 * d_model
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 1e-2
    has_bias: bool = True
    residual: bool = True
    """False: emit only the expert-combine output (the surrounding graph
    wires its own residual — the zoo transformer's explicit add vertex);
    True: the layer is the full residual block."""


    def _dims(self, input_type):
        if isinstance(input_type, it.Recurrent):
            return input_type.size
        if isinstance(input_type, it.FeedForward):
            return input_type.size
        raise ValueError(
            f"MoELayer needs recurrent/feed-forward input, got {input_type}")

    def output_type(self, input_type):
        self._dims(input_type)
        return input_type  # residual block: shape-preserving

    def init(self, key, input_type, dtype=jnp.float32):
        import jax

        d = self._dims(input_type)
        h = self.d_hidden or 4 * d
        e = self.n_experts
        k1, k2, k3 = jax.random.split(key, 3)
        s1, s2 = 1.0 / math.sqrt(d), 1.0 / math.sqrt(h)
        p = {
            "router": (s1 * jax.random.normal(k1, (d, e))).astype(dtype),
            "w1": (s1 * jax.random.normal(k2, (e, d, h))).astype(dtype),
            "w2": (s2 * jax.random.normal(k3, (e, h, d))).astype(dtype),
        }
        if self.has_bias:
            p["b1"] = jnp.zeros((e, h), dtype)
            p["b2"] = jnp.zeros((e, d), dtype)
        return p

    def init_state(self, input_type, dtype=jnp.float32):
        return {AUX_LOSS_KEY: jnp.zeros((), dtype)}

    def param_order(self):
        return (["router", "w1", "w2", "b1", "b2"] if self.has_bias
                else ["router", "w1", "w2"])

    def regularized_param_keys(self):
        return ["w1", "w2"]

    def param_shard_axes(self):
        """Leaves whose LEADING axis shards over the expert mesh axis
        (consumed by ParallelWrapper's expert-parallel spec builder)."""
        keys = ["w1", "w2"] + (["b1", "b2"] if self.has_bias else [])
        return {k: "expert" for k in keys}

    def forward(self, params, state, x, train=False, rng=None):
        from deeplearning4j_tpu.parallel import expert as expert_mod

        x = self._dropout_input(x, train, rng)
        shape = x.shape
        d = shape[-1]
        x2 = x.reshape(-1, d)
        t = x2.shape[0]
        e_loc = params["w1"].shape[0]
        axis = None
        if e_loc != self.n_experts:
            axis = expert_mod.current_expert_axis()
            if axis is None:
                raise RuntimeError(
                    f"MoELayer: expert weights arrived sharded "
                    f"({e_loc}/{self.n_experts}) outside an "
                    "active_expert_axis context — run through "
                    "ParallelWrapper(expert_parallel=True)")
        capacity = max(1, math.ceil(
            self.top_k * t / self.n_experts * self.capacity_factor))
        y2, aux = expert_mod.moe_apply(
            params["router"], params["w1"], params["w2"], x2,
            self.n_experts, capacity, top_k=self.top_k, axis_name=axis,
            b1=params.get("b1"), b2=params.get("b2"),
            residual=self.residual)
        new_state = {AUX_LOSS_KEY: (self.aux_weight * aux).astype(
            state[AUX_LOSS_KEY].dtype)} if train else state
        y = self.activation.apply(y2).reshape(shape)
        return y, new_state


@serde.register
@dataclasses.dataclass
class RoutedExpertsLayer(BaseLayer):
    """A dropless routed feed-forward beside a shared expert:

    ``s = sigmoid(Wr u)`` over ``n_experts`` in float32; the ``top_k``
    largest of ``s + b`` are chosen (the bias ``b`` enters the choice
    only); ``w_e = route_scale * s_e / (sum of the chosen s + route_eps)``
    (``route_norm``; else ``route_scale * s_e``); the output is
    ``Shared(u) + sum_e w_e Expert_e(u)``, every expert ``Wd (silu(Wg u)
    * Wu u)``. No capacity: every chosen (token, expert) slot is
    computed, so a token's result does not depend on the rest of the
    batch beyond rounding.

    ``experts_held = (first, count)`` names the experts whose matrices
    this layer holds (``count`` 0: all). It routes over all ``n_experts``
    and computes its own experts' part of the sum; what the others would
    add is another holder's. The shared expert is computed by every
    holder alike.

    One sum, three products by the number of tokens and the platform
    (``EVERY_EXPERT_SLOTS``): a prompt's thousands of tokens go grouped,
    their live ``tokens * top_k`` slots sorted by expert (an expert nobody
    chose is never read), in a program lowered for a TPU through
    ``ops.routed_experts.grouped_experts_ffn`` (one Pallas kernel, a tile
    of rows of one expert a grid step) and elsewhere through
    ``jax.lax.ragged_dot`` (whose gradient the kernel's is); a decode
    step's rows go, in a program lowered for a TPU, through
    ``ops.routed_experts.touched_experts_ffn`` (one Pallas kernel over the
    matrices of the experts the rows chose, read where they lie), and
    elsewhere through every expert held with the weight zero where it was
    not chosen. Tokens that are not live (idle rows,
    prompt padding) are routed nowhere and weigh nothing. More than
    ``ROUTED_ROWS_MAX`` tokens go through in slices.

    ``forward_live`` is what ``nn.decoding`` calls (the interface of a
    layer without state, ``conf/layers_hybrid.py``); its counts, one
    scalar a call: ``moe_routed_slots`` (live token x chosen expert held
    here), ``moe_experts_touched`` (experts held with at least one slot),
    ``moe_expert_layer_steps`` (1 where any token was live),
    ``moe_max_load`` (the fullest expert's slots), ``moe_experts_read``
    (experts whose matrices the product streamed: the touched ones by the
    kernel and the grouped product, all those held by the batched one)."""

    scope_class = "moe"

    n_out: int = 0
    n_experts: int = 8
    n_hidden: int = 0
    top_k: int = 2
    n_shared_hidden: int = 0        # 0: no shared expert
    route_norm: bool = True
    route_scale: float = 1.0
    route_eps: float = 1e-20        # added to the chosen scores' sum
    experts_held: tuple = (0, 0)    # (first, count); count 0: all
    out_scale: float = 1.0
    weight_dtype: str = ""
    swiglu_limit: float = 0.0       # every SwiGLU clamped (layers_hybrid.swiglu)

    uses_mask = True
    live_counters = ("moe_routed_slots", "moe_experts_touched",
                     "moe_expert_layer_steps", "moe_max_load",
                     "moe_experts_read")

    def _held(self):
        first, count = self.experts_held
        return int(first), int(count) or self.n_experts - int(first)

    def output_type(self, input_type):
        if isinstance(input_type, it.Recurrent):
            return it.Recurrent(size=self.n_out,
                                timesteps=input_type.timesteps)
        return it.FeedForward(size=self.n_out)

    def init(self, key, input_type, dtype=jnp.float32):
        d = input_type.size
        wd = _wdtype(self.weight_dtype, dtype)
        h, held = self.n_hidden, self._held()[1]
        ks = jax.random.split(key, 7)
        p = {"Wr": _matrix(self, ks[0], (d, self.n_experts), jnp.float32),
             "b": jnp.zeros((self.n_experts,), jnp.float32),
             "Wg": self.weight_init.init(ks[1], (held, d, h), d, h, wd,
                                         self.distribution),
             "Wu": self.weight_init.init(ks[2], (held, d, h), d, h, wd,
                                         self.distribution),
             "Wd": self.weight_init.init(ks[3], (held, h, self.n_out), h,
                                         self.n_out, wd, self.distribution)}
        if self.n_shared_hidden:
            s = self.n_shared_hidden
            p.update(Sg=_matrix(self, ks[4], (d, s), wd),
                     Su=_matrix(self, ks[5], (d, s), wd),
                     Sd=_matrix(self, ks[6], (s, self.n_out), wd))
        return p

    def param_order(self):
        shared = ["Sg", "Su", "Sd"] if self.n_shared_hidden else []
        return ["Wr", "b", "Wg", "Wu", "Wd"] + shared

    def regularized_param_keys(self):
        return [k for k in self.param_order() if k not in ("Wr", "b")]

    def route(self, params, u):
        """``(experts [n, top_k] int32, weights [n, top_k] float32)`` of
        tokens ``u: [n, d]``: float32 at the highest precision, because
        the choice is discrete and a choice made from rounded scores
        differs from the exact one at every near-tie."""
        s = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), params["Wr"],
                                   precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(s + params["b"], self.top_k)
        w = jnp.take_along_axis(s, experts, axis=1)
        if self.route_norm:
            w = w / (jnp.sum(w, axis=1, keepdims=True) + self.route_eps)
        return experts.astype(jnp.int32), w * self.route_scale

    def _every_expert(self, params, x, w):
        """The same sum for a decode step's rows off the TPU
        (``EVERY_EXPERT_SLOTS``), and the kernel's reference: every token
        through every expert held, ``w: [n, held]`` zero where the expert
        was not chosen."""
        f32 = jnp.float32
        hidden = swiglu(jnp.einsum("nd,edh->enh", x, params["Wg"],
                                   preferred_element_type=f32),
                        lambda: jnp.einsum("nd,edh->enh", x, params["Wu"],
                                           preferred_element_type=f32),
                        self.swiglu_limit)
        ys = jnp.einsum("enh,ehd->end", hidden.astype(x.dtype), params["Wd"],
                        preferred_element_type=f32)
        return jnp.einsum("end,ne->nd", ys, w)

    def _held_slots(self, params, u, live):
        """The slots of tokens ``u: [n, d]`` (``live: [n]`` bool):
        ``(experts, mine, w [n, top_k], chosen [n, top_k, held], sizes
        [held])``: a slot's expert, whether it is a live token's and held
        here, its weight; the slot's expert among those held here as a
        mask, and the slots each of them got."""
        first, held = self._held()
        experts, w = self.route(params, u)
        mine = (live[:, None] & (experts >= first)
                & (experts < first + held))
        chosen = mine[:, :, None] & (
            (experts - first)[:, :, None] == jnp.arange(held))
        return (experts, mine, w, chosen,
                jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32))

    @staticmethod
    def _by_row(w, chosen):
        """``[n, held]``: a row's weight for each expert held, zero where
        it did not choose it."""
        return jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)

    def _experts(self, params, x, experts, mine, w, chosen, sizes):
        """``(y [n, n_out], read)``: the routed sum of ``x: [n, d]`` over
        the slots :meth:`_held_slots` gives, and how many experts'
        matrices the product streamed. Which product is a matter of ``n *
        top_k / held`` alone, and of the platform the program is lowered
        for. Each is the scope ``moe.experts``, but a prompt's kernel on a
        TPU: that is ``moe.grouped`` and the sort and the gathers around it
        the layer's own."""
        from deeplearning4j_tpu.ops import routed_experts

        first, held = self._held()
        slots = experts.size
        fewest, most = EVERY_EXPERT_SLOTS

        def n_touched():
            return jnp.sum(sizes > 0, dtype=jnp.int32)

        def grouped_by(product, **kw):
            """The grouped product: each of the ``n * top_k`` slots with its
            expert (``held``: not this holder's; it sorts behind every
            group) and weight; ``ragged_dot``, or the kernel over tiles of
            one expert (a program lowered for a TPU)."""
            return product(x, params["Wg"], params["Wu"], params["Wd"],
                           jnp.where(mine, experts - first, held).reshape(-1),
                           w.reshape(-1), sizes, self.top_k,
                           self.swiglu_limit, **kw)

        def every():
            with jax.named_scope("moe.experts"):
                return (self._every_expert(params, x,
                                           self._by_row(w, chosen)),
                        jnp.int32(held))

        def grouped():
            with jax.named_scope("moe.experts"):
                return (grouped_by(routed_experts.grouped_experts_ragged),
                        n_touched())

        def touched():
            with jax.named_scope("moe.experts"):
                return (routed_experts.touched_experts_ffn(
                    x, params["Wg"], params["Wu"], params["Wd"],
                    self._by_row(w, chosen), sizes, interpret=False,
                    **({"limit": self.swiglu_limit} if self.swiglu_limit
                       else {})),
                    n_touched())

        def tiled():
            return (grouped_by(routed_experts.grouped_experts_ffn,
                               interpret=False), n_touched())

        applies = routed_experts.touched_experts_applies(
            params["Wg"].shape, params["Wd"].shape[-1])
        # slots an expert expects, whatever share of the experts is held
        # here: a holder of 16 of 256 gets a sixteenth of the slots
        if slots > most * self.n_experts:
            if not applies:
                return grouped()
            return jax.lax.platform_dependent(tpu=tiled, default=grouped)
        plain = every if slots >= fewest * self.n_experts else grouped
        if not applies:
            return plain()
        return jax.lax.platform_dependent(tpu=touched, default=plain)

    def _slice(self, params, u, live):
        """``u: [n, d]`` float32, ``live: [n]`` bool -> ``(y [n, n_out],
        sizes [held], read)``: ``sizes`` the slots each expert held here
        got, ``read`` the experts whose matrices the product streamed."""
        with jax.named_scope("moe.route"):
            *slots, sizes = self._held_slots(params, u, live)
        with jax.named_scope("moe.experts"):
            x = u.astype(params["Wg"].dtype)
        y, read = self._experts(params, x, *slots, sizes)
        if self.n_shared_hidden:
            with jax.named_scope("moe.shared"):
                y = y + _dot(swiglu(_dot(u, params["Sg"]),
                                    lambda: _dot(u, params["Su"]),
                                    self.swiglu_limit), params["Sd"])
        return y * self.out_scale, sizes, read

    def forward_live(self, params, x, live):
        """``x: [..., d]``, ``live: x.shape[:-1]`` (true where the token
        is a real one) -> ``(y [..., n_out] float32, counts)``."""
        flat = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        live = jnp.asarray(live).reshape(-1) > 0
        n = flat.shape[0]
        if n > ROUTED_ROWS_MAX and n % ROUTED_ROWS_MAX == 0:
            m = n // ROUTED_ROWS_MAX
            y, sizes, read = jax.lax.map(
                lambda a: self._slice(params, *a),
                (flat.reshape(m, ROUTED_ROWS_MAX, -1),
                 live.reshape(m, ROUTED_ROWS_MAX)))
            sizes, read = jnp.sum(sizes, axis=0), jnp.sum(read)
        else:
            y, sizes, read = self._slice(params, flat, live)
        counts = {"moe_routed_slots": jnp.sum(sizes),
                  "moe_experts_touched": jnp.sum(sizes > 0, dtype=jnp.int32),
                  "moe_expert_layer_steps": jnp.any(live).astype(jnp.int32),
                  "moe_max_load": jnp.max(sizes),
                  "moe_experts_read": read}
        return y.reshape(x.shape[:-1] + (-1,)), counts

    def forward(self, params, state, x, train=False, rng=None, mask=None):
        x = self._dropout_input(x, train, rng)
        live = (jnp.ones(x.shape[:-1], bool) if mask is None
                else jnp.asarray(mask) > 0)
        return self.forward_live(params, x, live)[0], state
