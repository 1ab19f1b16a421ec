"""Expert parallelism (MoE) over a mesh ``expert`` axis (beyond the
reference: DL4J has no EP — SURVEY.md §2.3 lists it absent; on TPU the
token exchange is ONE ``all_to_all`` over ICI each way, compiled into the
program with everything else).

Design (Mesh-TensorFlow/GShard-style, TPU-first):

- E experts, one (or E/devices) per mesh shard; tokens arrive sharded
  over the same axis (each shard owns T/E tokens — the data dimension
  rides the expert axis, the standard GShard layout).
- Top-1 router with capacity C per (source shard, expert): dispatch is
  an einsum against a [T, E, C] one-hot tensor (differentiable; dropped
  tokens — beyond capacity — pass through the residual untouched).
- ``all_to_all`` sends each source shard's per-expert buffers to the
  owning expert shard, the expert FFN runs on [E*C, d] (one big MXU
  matmul), and the reverse ``all_to_all`` + combine-einsum scatters
  results back, scaled by the router probability (so the router gets
  gradients through the prob factor, exactly GShard's estimator).
- An auxiliary load-balance loss (mean gate prob x mean assignment per
  expert, scaled by E^2) keeps routing from collapsing.

``moe_spmd_fn`` returns the jitted sharded layer; ``moe_train_step``
wires loss + SGD with expert weights staying shard-local and router
weights replicated (their gradient all-reduces with ``pmean``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as mesh_mod

from deeplearning4j_tpu.parallel.mesh import EXPERT_AXIS  # noqa: F401 — reserved in round 1


# --- active expert-axis context (set by ParallelWrapper's expert-parallel
# step around its shard_map body at TRACE time; read by MoELayer.forward
# to name the all_to_all axis when its expert weights arrive sharded) ---
import contextlib as _contextlib

_ACTIVE_EXPERT_AXIS: list = [None]


@_contextlib.contextmanager
def active_expert_axis(name: str):
    _ACTIVE_EXPERT_AXIS.append(name)
    try:
        yield
    finally:
        _ACTIVE_EXPERT_AXIS.pop()


def current_expert_axis():
    return _ACTIVE_EXPERT_AXIS[-1]


def moe_init(key, d_model: int, d_hidden: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    """One logical copy: router [d, E] (replicated) + per-expert FFN
    weights with a leading [E] axis (shard ``P('expert')``)."""
    import numpy as np

    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / np.sqrt(d_model)
    s2 = 1.0 / np.sqrt(d_hidden)
    return {
        "router": (s1 * jax.random.normal(k1, (d_model, n_experts))
                   ).astype(dtype),
        "w1": (s1 * jax.random.normal(k2, (n_experts, d_model, d_hidden))
               ).astype(dtype),
        "w2": (s2 * jax.random.normal(k3, (n_experts, d_hidden, d_model))
               ).astype(dtype),
    }


def shard_moe_params(params: dict, mesh: Mesh) -> dict:
    return {
        "router": jax.device_put(params["router"],
                                 NamedSharding(mesh, P())),
        "w1": jax.device_put(params["w1"],
                             NamedSharding(mesh, P(EXPERT_AXIS))),
        "w2": jax.device_put(params["w2"],
                             NamedSharding(mesh, P(EXPERT_AXIS))),
    }


def moe_apply(router, w1, w2, x, n_experts: int, capacity: int,
              top_k: int = 1, axis_name: str | None = EXPERT_AXIS,
              b1=None, b2=None, residual: bool = True):
    """The MoE layer math, shared by the raw shard_map entrypoints below
    AND the conf-DSL ``MoELayer`` (``conf/layers_moe.py``).

    ``x`` [t, d] tokens (this shard's, when ``axis_name`` is bound);
    ``w1`` [e_loc, d, h] / ``w2`` [e_loc, h, d] the LOCAL experts
    (e_loc == n_experts when running unsharded); ``router`` [d, E]
    replicated. ``top_k`` in {1, 2}: top-1 is Switch-style (combine gate
    = the RAW router probability, keeping the router differentiable
    through the task loss); GShard top-2 routes each token to its two
    best experts with gates renormalized over the pair; capacity
    is counted per (source shard, expert) with the rank-0 choice queued
    before rank-1 (GShard's ordering). ``axis_name=None`` (or e_loc ==
    n_experts) skips the all_to_all — single-shard execution, used by CPU
    tests and the conf layer's unsharded path. Returns (x + y, aux)."""
    t, d = x.shape
    e_loc = w1.shape[0]
    logits = x @ router                                # [t, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k assignment matrix + per-(token, expert) gate weights,
    # renormalized over the chosen experts (GShard combine weights)
    kidx = jax.lax.top_k(probs, top_k)[1]              # [t, k]
    hots = jax.nn.one_hot(kidx, n_experts, dtype=x.dtype)  # [t, k, E]
    gates = jnp.take_along_axis(probs, kidx, axis=-1)  # [t, k]
    if top_k > 1:
        # GShard top-2+: gates renormalized over the chosen pair
        gates = gates / jnp.maximum(
            jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # top_k == 1 keeps the RAW router probability as the combine gate
    # (Switch-Transformer): renormalizing would pin the gate at 1.0 and
    # cut the router's task-loss gradient through the combine path,
    # leaving it trainable only via the aux loss.

    # capacity queue: rank-0 choices first, then rank-1 (stable order)
    flat = hots.transpose(1, 0, 2).reshape(top_k * t, n_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = pos_flat.reshape(top_k, t, n_experts).transpose(1, 0, 2)
    keep = pos < capacity                              # [t, k, E]
    # dispatch[t, e, c]: token t occupies slot c of expert e (0/1; a
    # token dropped by capacity keeps its residual only)
    dispatch = jnp.einsum("tke,tkc->tec", hots * keep, jax.nn.one_hot(
        jnp.sum(pos * hots, axis=-1).astype(jnp.int32), capacity,
        dtype=x.dtype))
    # combine[t, e, c] = dispatch * gate of that (t, e) pair
    gate_te = jnp.einsum("tke,tk->te", hots * keep, gates)
    combine = dispatch * gate_te[:, :, None]

    send = jnp.einsum("td,tec->ecd", x, dispatch)      # [E, C, d]
    n_shards = n_experts // e_loc
    if n_shards > 1:
        if axis_name is None:
            raise ValueError(
                f"w1 holds {e_loc}/{n_experts} experts but no mesh axis "
                "was given for the all_to_all exchange")
        # rows grouped by DEST expert -> after all_to_all the leading
        # axis is the SOURCE shard, all buffers for MY experts
        send = send.reshape(n_shards, e_loc * capacity, d)
        recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
        # [n_shards, e_loc*C, d] -> [e_loc, n_shards*C, d]
        recv = recv.reshape(n_shards, e_loc, capacity, d).transpose(
            1, 0, 2, 3).reshape(e_loc, n_shards * capacity, d)
    else:
        recv = send

    h = jnp.einsum("ecd,edh->ech", recv, w1)
    if b1 is not None:
        h = h + b1[:, None, :]
    h = jnp.maximum(h, 0.0)
    out = jnp.einsum("ech,ehd->ecd", h, w2)
    if b2 is not None:
        out = out + b2[:, None, :]

    if n_shards > 1:
        out = out.reshape(e_loc, n_shards, capacity, d).transpose(
            1, 0, 2, 3).reshape(n_shards, e_loc * capacity, d)
        back = jax.lax.all_to_all(out, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(n_experts, capacity, d)
    else:
        back = out
    # combine, scaled by the router gate (raw top-1 prob for k=1,
    # pair-renormalized for k>=2) — the router's task-loss gradient path
    y = jnp.einsum("ecd,tec->td", back, combine)

    # load-balance aux (GShard): E * sum_e mean(prob_e) * mean(top-1
    # assignment_e) — the rank-0 assignment only, per the paper
    assign = jnp.mean(hots[:, 0], axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(assign * prob_mean)
    return (x + y if residual else y), aux


def _moe_local(params, x, n_experts: int, capacity: int, top_k: int = 1):
    return moe_apply(params["router"], params["w1"], params["w2"], x,
                     n_experts, capacity, top_k=top_k)


def moe_spmd_fn(n_experts: int, capacity: int, mesh: Mesh,
                top_k: int = 1):
    """-> jitted ``(params, x) -> (y, aux)``: x [T, d] sharded over
    ``expert`` (T % n_shards == 0), params via ``shard_moe_params``."""
    def spmd(params, x):
        p = {"router": params["router"],
             "w1": params["w1"], "w2": params["w2"]}
        y, aux = _moe_local(p, x, n_experts, capacity, top_k=top_k)
        return y, jax.lax.pmean(aux, EXPERT_AXIS)

    sharded = mesh_mod.shard_map(
        spmd, mesh,
        in_specs=({"router": P(), "w1": P(EXPERT_AXIS),
                   "w2": P(EXPERT_AXIS)}, P(EXPERT_AXIS)),
        out_specs=(P(EXPERT_AXIS), P()))
    return jax.jit(sharded)


def moe_train_step(n_experts: int, capacity: int, mesh: Mesh,
                   lr: float = 0.05, aux_weight: float = 1e-2,
                   top_k: int = 1):
    """-> jitted ``(params, x, target) -> (params, loss)``: MSE + aux
    load-balance loss; expert-weight grads stay shard-local, the
    replicated router's grad is ``pmean``-reduced.

    Why pmean and not psum (round-3 advisor follow-up, settled
    empirically — see test_moe_train_step_gradients_match_single_device):
    differentiating the ``pmean``-reduced loss inside the shard_map body
    ALREADY cross-shard-accumulates the router cotangent — the AD
    transpose of the psum collective inside pmean performs the reduction
    — so ``g["router"]`` arrives as the full logical gradient, identical
    on every shard (verified elementwise against the 1-device mesh).
    ``pmean`` over identical replicas is an identity. The test pins one
    full train step against the 1-device mesh elementwise, so any
    regression in either direction is caught."""
    def spmd(params, x, target):
        def loss_fn(p):
            y, aux = _moe_local(p, x, n_experts, capacity, top_k=top_k)
            mse = jnp.mean((y - target) ** 2)
            return jax.lax.pmean(mse, EXPERT_AXIS) \
                + aux_weight * jax.lax.pmean(aux, EXPERT_AXIS)

        loss, g = jax.value_and_grad(loss_fn)(params)
        g = dict(g)
        g["router"] = jax.lax.pmean(g["router"], EXPERT_AXIS)
        new = {k: params[k] - lr * g[k] for k in params}
        return new, loss

    sharded = mesh_mod.shard_map(
        spmd, mesh,
        in_specs=({"router": P(), "w1": P(EXPERT_AXIS),
                   "w2": P(EXPERT_AXIS)}, P(EXPERT_AXIS), P(EXPERT_AXIS)),
        out_specs=({"router": P(), "w1": P(EXPERT_AXIS),
                    "w2": P(EXPERT_AXIS)}, P()))
    return jax.jit(sharded, donate_argnums=(0,))


# Test oracle: run moe_spmd_fn over a ONE-device ``expert`` mesh (the
# all_to_all degenerates to identity, every expert is local) and compare
# against the sharded mesh on the same tokens. Capacity is per (source
# shard, expert), so exact equivalence needs capacity large enough that
# no token drops — the drop semantics get their own single-shard test.
