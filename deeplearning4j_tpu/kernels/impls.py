"""Tiling-parameterized Pallas kernel implementations.

These are the Mosaic-side bodies behind the kernel registry
(``kernels.registry``): each takes its tiling as an explicit
``(bm, bn, bk)`` triple so the per-shape autotuner (``kernels.tuner``)
can sweep the grid/block space instead of baking one hand-picked layout
(the ``ops/conv_fused`` experiment hard-codes 512/128/128 — the exact
"compiler-generated schedules leave tuning on the table" gap
arXiv:2207.00257 measures for high-level-construct transpilation).

Both kernels follow the ``ops/conv_fused`` discipline:

- forward is the Pallas pass (MXU matmul with a fused epilogue),
  ``interpret=True`` off-TPU so the CPU container executes the SAME
  kernel body through the Pallas interpreter (the backend-parity
  oracle);
- backward is a ``jax.custom_vjp`` built from plain XLA ops that
  recompute exactly what the stock path would have produced, so
  gradients track the ``jax.lax`` reference implementation and the
  kernel path stays drop-in for train steps (donation included —
  nothing here blocks input/output aliasing, pinned by the PRG201
  audit over kernel-bearing executables).

Tiling validity: a candidate ``(bm, bn, bk)`` is clamped per-dimension
to the problem size (``ebm = min(bm, m)`` ...) and is legal when every
clamped block divides its dimension exactly and, for a TPU-keyed
envelope, passes Mosaic's block-shape rule (:func:`mosaic_block_ok`) —
the registry's envelope check; shapes with no legal candidate fall back
to stock XLA.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def effective_tiling(m: int, k: int, n: int,
                     tiling: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Clamp a candidate tiling to the problem size."""
    bm, bn, bk = tiling
    return min(int(bm), m), min(int(bn), n), min(int(bk), k)


def tiling_valid(m: int, k: int, n: int,
                 tiling: Tuple[int, int, int]) -> bool:
    """True when every clamped block divides its dimension exactly (the
    grid covers the problem with no ragged tail)."""
    ebm, ebn, ebk = effective_tiling(m, k, n, tiling)
    return (ebm > 0 and ebn > 0 and ebk > 0
            and m % ebm == 0 and n % ebn == 0 and k % ebk == 0)


def mosaic_block_ok(m: int, k: int, n: int,
                    tiling: Tuple[int, int, int]) -> bool:
    """Mosaic's block-shape rule for the ``[bm, bk] x [bk, bn]`` blocks
    these kernels declare: the last two dims of a block are multiples of
    (8, 128) or span the whole array dim. The interpreter takes any
    divisor; the TPU lowering refuses the rest by name."""
    ebm, ebn, ebk = effective_tiling(m, k, n, tiling)
    return ((ebm % 8 == 0 or ebm == m)
            and (ebn % 128 == 0 or ebn == n)
            and (ebk % 128 == 0 or ebk == k))


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# --------------------------------------------------------------------------
# matmul + bias + elementwise activation (dense / 1x1-conv forward)
# --------------------------------------------------------------------------

def _mm_bias_act_kernel(x_ref, w_ref, b_ref, y_ref, acc, *, nk, act_fn):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _():
        z = acc[...] + b_ref[...].astype(jnp.float32)
        y_ref[...] = act_fn(z).astype(y_ref.dtype)


def _mm_bias_act_impl(x2, w2, b, act, tiling, interpret):
    m, k = x2.shape
    n = w2.shape[-1]
    ebm, ebn, ebk = effective_tiling(m, k, n, tiling)
    assert tiling_valid(m, k, n, tiling), (m, k, n, tiling)
    nbm, nbn, nbk = m // ebm, n // ebn, k // ebk
    return pl.pallas_call(
        functools.partial(_mm_bias_act_kernel, nk=nbk, act_fn=act.apply),
        grid=(nbm, nbn, nbk),
        in_specs=[pl.BlockSpec((ebm, ebk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((ebk, ebn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((1, ebn), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((ebm, ebn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x2.dtype),
        scratch_shapes=[pltpu.VMEM((ebm, ebn), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(x2, w2, b.reshape(1, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def matmul_bias_act(x2, w2, b, act, tiling, interpret):
    """``act(x2 @ w2 + b)`` as ONE tiled Pallas pass: the bias add and
    the elementwise activation run in the MXU epilogue (last K block)
    instead of as separate XLA passes over the output.

    x2: [M, K]; w2: [K, N]; b: [N]; ``act`` an elementwise
    ``conf.activations.Activation``; ``tiling`` a ``(bm, bn, bk)``
    candidate valid per :func:`tiling_valid`. Backward is plain XLA
    recomputing the pre-activation exactly as the stock dense forward
    would, so gradients match the reference path.
    """
    return _mm_bias_act_impl(x2, w2, b, act, tiling, interpret)


def _mm_bias_act_fwd(x2, w2, b, act, tiling, interpret):
    y = _mm_bias_act_impl(x2, w2, b, act, tiling, interpret)
    return y, (x2, w2, b)


def _mm_bias_act_bwd(act, tiling, interpret, res, g):
    x2, w2, b = res
    # recompute the pre-activation with the SAME ops the stock forward
    # uses (x @ W + b), then pull the cotangent through the activation —
    # the gradient is the reference path's gradient, not a kernel-shaped
    # approximation of it
    z = x2 @ w2 + b
    _, act_vjp = jax.vjp(act.apply, z)
    (dz,) = act_vjp(g.astype(z.dtype))
    dx = (dz @ w2.T).astype(x2.dtype)
    dw = (x2.T @ dz).astype(w2.dtype)
    db = jnp.sum(dz.astype(jnp.float32), axis=0).astype(b.dtype)
    return dx, dw, db


matmul_bias_act.defvjp(_mm_bias_act_fwd, _mm_bias_act_bwd)


# --------------------------------------------------------------------------
# matmul + per-channel sum / sum-of-squares (fused conv+BN statistics)
# --------------------------------------------------------------------------

def _mm_stats_kernel(x_ref, w_ref, y_ref, s_ref, q_ref, acc, *, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _():
        # statistics over the OUTPUT-dtype y — matches the unfused path,
        # which rounds y to the storage dtype before the mean/var read
        # (same formulation as ops/conv_fused)
        yb = acc[...].astype(y_ref.dtype)
        y_ref[...] = yb
        y32 = yb.astype(jnp.float32)
        s_ref[...] = jnp.sum(y32, axis=0).reshape(s_ref.shape)
        q_ref[...] = jnp.sum(y32 * y32, axis=0).reshape(q_ref.shape)


def _mm_stats_impl(x2, w2, tiling, interpret):
    m, k = x2.shape
    n = w2.shape[-1]
    ebm, ebn, ebk = effective_tiling(m, k, n, tiling)
    assert tiling_valid(m, k, n, tiling), (m, k, n, tiling)
    nbm, nbn, nbk = m // ebm, n // ebn, k // ebk
    y, ssum, sq = pl.pallas_call(
        functools.partial(_mm_stats_kernel, nk=nbk),
        grid=(nbm, nbn, nbk),
        in_specs=[pl.BlockSpec((ebm, ebk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((ebk, ebn), lambda i, j, kk: (kk, j))],
        out_specs=[pl.BlockSpec((ebm, ebn), lambda i, j, kk: (i, j)),
                   pl.BlockSpec((1, 1, ebn), lambda i, j, kk: (i, 0, j)),
                   pl.BlockSpec((1, 1, ebn), lambda i, j, kk: (i, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x2.dtype),
            jax.ShapeDtypeStruct((nbm, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((nbm, 1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ebm, ebn), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(x2, w2)
    # reduce the per-row-block partials (tiny [nbm, N] arrays) in XLA
    return y, jnp.sum(ssum[:, 0], axis=0), jnp.sum(sq[:, 0], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_stats(x2, w2, tiling, interpret):
    """``y = x2 @ w2`` plus per-output-channel ``sum(y)`` / ``sum(y*y)``
    (f32) in ONE output pass — the fused conv+BN statistics class
    (``ops/conv_fused``) with the tiling exposed to the autotuner.

    Returns ``(y [M, N] in x2.dtype, s [N] f32, q [N] f32)``.
    """
    return _mm_stats_impl(x2, w2, tiling, interpret)


def _mm_stats_fwd(x2, w2, tiling, interpret):
    y, s, q = _mm_stats_impl(x2, w2, tiling, interpret)
    return (y, s, q), (x2, w2, y)


def _mm_stats_bwd(tiling, interpret, res, cts):
    # identical math to ops/conv_fused._bwd: d(sum y)/dy = 1,
    # d(sum y^2)/dy = 2y — one combined cotangent, two MXU matmuls
    x2, w2, y = res
    gy, gs, gq = cts
    g = (gy.astype(jnp.float32) + gs[None, :]
         + 2.0 * y.astype(jnp.float32) * gq[None, :]).astype(x2.dtype)
    dx = jax.lax.dot(g, w2.T, preferred_element_type=jnp.float32)
    dw = jax.lax.dot(x2.T, g, preferred_element_type=jnp.float32)
    return dx.astype(x2.dtype), dw.astype(w2.dtype)


matmul_stats.defvjp(_mm_stats_fwd, _mm_stats_bwd)


# --------------------------------------------------------------------------
# int8 matmul + f32 scale/bias + activation (quantized dense / 1x1-conv)
# --------------------------------------------------------------------------

def _mm_bias_act_q8_kernel(x_ref, w_ref, s_ref, b_ref, y_ref, acc, *, nk,
                           act_fn):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    # int8 x int8 -> int32: the MXU's native int8 path (the interpreter
    # runs the same accumulate in int32 on CPU)
    acc[...] += jax.lax.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(kk == nk - 1)
    def _():
        # dequant-free epilogue: the per-output-channel scale already
        # carries the folded activation scales, the effective bias carries
        # the zero-point correction (see conf.layers_quant)
        z = (acc[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
             + b_ref[...].astype(jnp.float32))
        y_ref[...] = act_fn(z).astype(y_ref.dtype)


def matmul_bias_act_int8(xq, wq, scale, b, act, tiling, interpret):
    """``act(int32_dot(xq, wq) * scale + b)`` as ONE tiled Pallas pass —
    the quantized-serving variant of :func:`matmul_bias_act`.

    xq: [M, K] int8 (already quantized in-graph); wq: [K, N] int8;
    scale/b: [N] f32 (effective scale/bias from
    ``nn.inference_opt.quantize_for_inference``). Forward-only: quantized
    layers never train, so there is no custom VJP — differentiating
    through this is a programming error and fails loudly in JAX.
    """
    m, k = xq.shape
    n = wq.shape[-1]
    ebm, ebn, ebk = effective_tiling(m, k, n, tiling)
    assert tiling_valid(m, k, n, tiling), (m, k, n, tiling)
    nbm, nbn, nbk = m // ebm, n // ebn, k // ebk
    return pl.pallas_call(
        functools.partial(_mm_bias_act_q8_kernel, nk=nbk, act_fn=act.apply),
        grid=(nbm, nbn, nbk),
        in_specs=[pl.BlockSpec((ebm, ebk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((ebk, ebn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((1, ebn), lambda i, j, kk: (0, j)),
                  pl.BlockSpec((1, ebn), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((ebm, ebn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ebm, ebn), jnp.int32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(xq, wq, scale.reshape(1, n), b.reshape(1, n))
