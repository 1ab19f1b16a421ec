"""Fused 1x1-conv (matmul) + batch-norm statistics in one output pass.

Reference: libnd4j's cuDNN platform helpers fuse conv+BN+activation per
op pair (``platform/cudnn/batchnorm.cu`` per SURVEY.md §2.1); here the
TPU-shaped equivalent targets the schedule XLA actually emits for a
train-mode 1x1-conv+BN: write y, read y for mean/var, read y to
normalize — three passes over the activation. The Pallas kernel below
computes the matmul AND the per-channel sum / sum-of-squares partials in
the SAME output pass (the epilogue of the K-loop), so the statistics
read disappears; the normalize+activation pass stays in XLA where it
fuses with whatever follows.

Numerics note: the per-channel sums are taken over the OUTPUT-dtype
(bf16-rounded) y, exactly like the unfused path's
``jnp.mean(y.astype(f32))``; variance is the one-pass E[y^2]-E[y]^2 form
in f32 — at batch-norm's 1e5+ elements-per-channel scale the one/two
pass difference is ~1e-6 relative (pinned by tests/test_zoo.py).

Backward: custom VJP. With y = x @ w, s_c = sum_m y[m,c],
q_c = sum_m y[m,c]^2, the cotangent into y is
g_total = gy + gs[None, :] + 2*y*gq[None, :], and dx = g_total @ w.T,
dw = x.T @ g_total — two plain MXU matmuls (XLA), no extra passes vs
the unfused backward (which also reads y for the BN-stats grad).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_BM_CANDIDATES = (512, 256, 128)
_BN = 128
_BK = 128


def _tpu_compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def pick_block_m(m: int) -> Optional[int]:
    """Largest supported row-block size dividing ``m`` (None = shapes not
    blockable -> caller uses the plain XLA path)."""
    for bm in _BM_CANDIDATES:
        if m % bm == 0:
            return bm
    return None


def fusable(m: int, cin: int, cout: int) -> bool:
    """True when the grid covers these shapes exactly — row count
    divisible by a supported block, channel counts either below the
    128-lane block or a multiple of it. False -> callers
    (FusedConvBN1x1) take the plain XLA path."""
    return (pick_block_m(m) is not None
            and (cin <= _BK or cin % _BK == 0)
            and (cout <= _BN or cout % _BN == 0))


def _kernel(x_ref, w_ref, y_ref, s_ref, q_ref, acc, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        # statistics over the OUTPUT-dtype y (matches the unfused path,
        # which rounds y to bf16 before jnp.mean/var reads it back)
        yb = acc[...].astype(y_ref.dtype)
        y_ref[...] = yb
        y32 = yb.astype(jnp.float32)
        s_ref[...] = jnp.sum(y32, axis=0).reshape(s_ref.shape)
        q_ref[...] = jnp.sum(y32 * y32, axis=0).reshape(q_ref.shape)


def _fwd_impl(x2, w2, interpret):
    m, cin = x2.shape
    cout = w2.shape[-1]
    bm = pick_block_m(m)
    assert bm is not None, (m, cin, cout)
    bn = min(_BN, cout)
    bk = min(_BK, cin)
    nbm, nbn, nbk = m // bm, cout // bn, cin // bk
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    y, ssum, sq = pl.pallas_call(
        functools.partial(_kernel, nk=nbk),
        grid=(nbm, nbn, nbk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((1, 1, bn), lambda i, j, k: (i, 0, j)),
                   pl.BlockSpec((1, 1, bn), lambda i, j, k: (i, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct((m, cout), x2.dtype),
            jax.ShapeDtypeStruct((nbm, 1, cout), jnp.float32),
            jax.ShapeDtypeStruct((nbm, 1, cout), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_tpu_compiler_params(interpret),
        interpret=interpret,
    )(x2, w2)
    # reduce the per-row-block partials (tiny [nbm, C] arrays)
    s = jnp.sum(ssum[:, 0], axis=0)
    q = jnp.sum(sq[:, 0], axis=0)
    return y, s, q


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul_with_stats(x2, w2, interpret=False):
    """``y = x2 @ w2`` plus per-output-channel ``sum(y)`` / ``sum(y*y)``
    (f32), all produced in ONE pass over y by a Pallas kernel.

    x2: [M, Cin]; w2: [Cin, Cout] -> (y [M, Cout] in x2.dtype,
    s [Cout] f32, q [Cout] f32). Shapes must satisfy :func:`fusable`.
    """
    return _fwd_impl(x2, w2, interpret)


def _fwd(x2, w2, interpret):
    y, s, q = _fwd_impl(x2, w2, interpret)
    return (y, s, q), (x2, w2, y)


def _bwd(interpret, res, cts):
    x2, w2, y = res
    gy, gs, gq = cts
    # d(sum y)/dy = 1; d(sum y^2)/dy = 2y — fold into one cotangent,
    # f32 for the accumulation then back to the compute dtype for the MXU
    g = (gy.astype(jnp.float32) + gs[None, :]
         + 2.0 * y.astype(jnp.float32) * gq[None, :]).astype(x2.dtype)
    dx = jax.lax.dot(g, w2.T, preferred_element_type=jnp.float32)
    dw = jax.lax.dot(x2.T, g, preferred_element_type=jnp.float32)
    return dx.astype(x2.dtype), dw.astype(w2.dtype)


matmul_with_stats.defvjp(_fwd, _bwd)


def bn_fold_scale_shift(gamma, beta, mean, var, eps):
    """Inference-time BN folding constants (the libnd4j cuDNN-helper
    fusion, applied statically): eval-mode batch norm is the per-channel
    affine ``y*scale + shift`` with

        scale = gamma / sqrt(var + eps)
        shift = beta - mean * scale

    so a preceding linear op (conv/dense, identity activation) absorbs it
    exactly: ``W' = W * scale`` (scale over the output-channel axis),
    ``b' = b * scale + shift``. Computed in f32 regardless of the serving
    dtype — the fold happens once at engine construction, and rsqrt in
    bf16 would bake a permanent ~1e-2 error into the weights. ``gamma``/
    ``beta`` None = locked gamma/beta (1/0)."""
    var32 = jnp.asarray(var, jnp.float32)
    mean32 = jnp.asarray(mean, jnp.float32)
    scale = jax.lax.rsqrt(var32 + jnp.float32(eps))
    if gamma is not None:
        scale = scale * jnp.asarray(gamma, jnp.float32)
    shift = -mean32 * scale
    if beta is not None:
        shift = shift + jnp.asarray(beta, jnp.float32)
    return scale, shift


def conv1x1_bn_stats(x, w, stride: Tuple[int, int] = (1, 1),
                     interpret: Optional[bool] = None):
    """1x1 convolution (NHWC, HWIO weights [1, 1, Cin, Cout]) returning
    ``(y, sum, sumsq)`` with the statistics fused into the conv's output
    pass. A strided 1x1 conv is an exact spatial subsample first (both
    VALID and SAME sample positions 0, s, 2s, ...).

    ``interpret=None`` auto-enables the Pallas interpreter off-TPU so CPU
    CI exercises the same kernel (SURVEY.md §4 backend-parity oracle).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sh, sw = stride
    if (sh, sw) != (1, 1):
        x = x[:, ::sh, ::sw, :]
    b, h, wd, cin = x.shape
    cout = w.shape[-1]
    m = b * h * wd
    y2, s, q = matmul_with_stats(x.reshape(m, cin), w.reshape(cin, cout),
                                 interpret)
    return y2.reshape(b, h, wd, cout), s, q
