"""Gradient-check harness — the central correctness oracle.

Reference: ``org.deeplearning4j.gradientcheck.GradientCheckUtil`` (the
backbone of the reference's test strategy, SURVEY.md §4): central-difference
numerical gradients vs backprop in double precision, exact per-parameter
comparison with relative-error thresholds.

Here the analytic side is ``jax.grad`` through the whole jitted loss; the
harness runs in f64 on CPU (``jax.enable_x64``), mirroring the reference's
double-precision-only protocol; TPU runs the same models in f32/bf16 with
tolerance tiers.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from deeplearning4j_tpu.util import params as params_util


@dataclasses.dataclass
class GradCheckResult:
    n_params: int
    n_checked: int
    n_failed: int
    max_rel_error: float
    mean_rel_error: float
    failures: list  # (flat_index, analytic, numeric, rel_error)

    @property
    def passed(self) -> bool:
        return self.n_failed == 0


def _central_diff_check(f_jit, flat0: np.ndarray, analytic: np.ndarray,
                        idx: np.ndarray, reshape, epsilon: float,
                        max_rel_error: float,
                        abs_error_threshold: float) -> GradCheckResult:
    """Shared perturb/eval/compare harness. ``reshape`` maps a flat vector
    back to the shape ``f_jit`` expects; rel_err = |a-n| / (|a|+|n|)
    (reference GradientCheckUtil convention).

    The perturbations are evaluated VMAPPED in chunks — one compiled call
    per chunk of up/down pairs instead of two dispatches + a host sync per
    sampled parameter (the per-parameter loop made the f64 oracle the
    dominant cost of the whole tier-1 suite). Same evaluations, same f64
    math, identical results."""
    import jax
    import jax.numpy as jnp

    fv = jax.jit(jax.vmap(lambda v: f_jit(reshape(v))))
    chunk = 256
    numeric = np.empty(len(idx), np.float64)
    for start in range(0, len(idx), chunk):
        ii = np.asarray(idx[start:start + chunk])
        pert = np.zeros((len(ii), flat0.size), flat0.dtype)
        pert[np.arange(len(ii)), ii] = epsilon
        base = flat0[None, :]
        up = np.asarray(fv(jnp.asarray(base + pert)), np.float64)
        dn = np.asarray(fv(jnp.asarray(base - pert)), np.float64)
        numeric[start:start + len(ii)] = (up - dn) / (2.0 * epsilon)

    a = np.asarray(analytic, np.float64)[np.asarray(idx)]
    denom = np.abs(a) + np.abs(numeric)
    rel = np.where(denom > 0, np.abs(a - numeric) / np.maximum(denom, 1e-300),
                   0.0)
    bad = (rel > max_rel_error) & (np.abs(a - numeric) > abs_error_threshold)
    failures = [(int(i), float(av), float(nv), float(rv))
                for i, av, nv, rv in zip(np.asarray(idx)[bad], a[bad],
                                         numeric[bad], rel[bad])]
    return GradCheckResult(
        n_params=int(flat0.size),
        n_checked=len(idx),
        n_failed=len(failures),
        max_rel_error=float(np.max(rel)) if len(rel) else 0.0,
        mean_rel_error=float(np.mean(rel)) if len(rel) else 0.0,
        failures=failures[:20],
    )


def _check_net_params_gradient(conf64, net, loss_args, epsilon,
                               max_rel_error, abs_error_threshold, n_samples,
                               seed) -> GradCheckResult:
    """Shared scaffolding for the MultiLayerNetwork / ComputationGraph
    checks: flatten params, jit loss-of-flat-vector, analytic ``jax.grad``,
    optional parameter subsampling, central-difference compare."""
    import jax
    import jax.numpy as jnp

    like = net.params

    def loss_from_flat(flat):
        p = params_util.unflatten_params(conf64, flat, like)
        loss, _ = net._loss(p, net.state, *loss_args, rng=None, train=True)
        return loss

    flat0 = np.asarray(params_util.flatten_params(conf64, net.params))
    loss_jit = jax.jit(loss_from_flat)
    analytic = np.asarray(
        jax.jit(jax.grad(loss_from_flat))(jnp.asarray(flat0)))

    n = flat0.size
    if n_samples is not None and n_samples < n:
        rng = np.random.default_rng(seed)
        idx = np.sort(rng.choice(n, size=n_samples, replace=False))
    else:
        idx = np.arange(n)

    return _central_diff_check(loss_jit, flat0, analytic, idx,
                               reshape=lambda v: v, epsilon=epsilon,
                               max_rel_error=max_rel_error,
                               abs_error_threshold=abs_error_threshold)


def gradient_check(conf, ds, epsilon: float = 1e-6,
                   max_rel_error: float = 1e-5,
                   abs_error_threshold: float = 1e-9,
                   n_samples: Optional[int] = None,
                   seed: int = 0) -> GradCheckResult:
    """Check d(loss)/d(params) of a MultiLayerConfiguration against central
    differences (reference ``GradientCheckUtil#checkGradients``).

    ``n_samples``: check a random subset of parameters (None = all).
    """
    import jax

    with jax.enable_x64(True):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf64 = dataclasses.replace(conf, dtype="float64")
        net = MultiLayerNetwork(conf64).init()

        features = jnp.asarray(np.asarray(ds.features), jnp.float64)
        labels = jnp.asarray(np.asarray(ds.labels), jnp.float64)
        fmask = (jnp.asarray(np.asarray(ds.features_mask), jnp.float64)
                 if ds.features_mask is not None else None)
        lmask = (jnp.asarray(np.asarray(ds.labels_mask), jnp.float64)
                 if ds.labels_mask is not None
                 else jnp.ones((features.shape[0],), jnp.float64))

        return _check_net_params_gradient(
            conf64, net, (features, labels, fmask, lmask), epsilon,
            max_rel_error, abs_error_threshold, n_samples, seed)


def check_layer_input_gradient(layer, input_type, x, epsilon: float = 1e-6,
                               max_rel_error: float = 1e-5,
                               abs_error_threshold: float = 1e-9,
                               seed: int = 0) -> GradCheckResult:
    """Op-level validation (reference ``OpValidation``/``TestCase``):
    d(sum(layer(x)))/dx vs central differences, f64."""
    import jax

    with jax.enable_x64(True):
        import jax.numpy as jnp

        key = jax.random.PRNGKey(seed)
        params = layer.init(key, input_type, jnp.float64)
        state = layer.init_state(input_type, jnp.float64)
        x = jnp.asarray(np.asarray(x), jnp.float64)

        def f(xx):
            y, _ = layer.forward(params, state, xx, train=False, rng=None)
            return jnp.sum(y)

        analytic = np.asarray(jax.jit(jax.grad(f))(x)).ravel()
        f_jit = jax.jit(f)
        x_np = np.asarray(x)
        flat0 = x_np.ravel()
        return _central_diff_check(
            f_jit, flat0, analytic, np.arange(flat0.size),
            reshape=lambda v: v.reshape(x_np.shape), epsilon=epsilon,
            max_rel_error=max_rel_error,
            abs_error_threshold=abs_error_threshold)


def gradient_check_graph(conf, mds, epsilon: float = 1e-6,
                         max_rel_error: float = 1e-5,
                         abs_error_threshold: float = 1e-9,
                         n_samples: Optional[int] = None,
                         seed: int = 0) -> GradCheckResult:
    """Gradient check for a ComputationGraphConfiguration against central
    differences (reference ``GradientCheckUtil#checkGradients(GraphConfig)``
    overload; same f64 protocol as :func:`gradient_check`)."""
    import jax

    with jax.enable_x64(True):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.graph import ComputationGraph, _as_multi

        conf64 = dataclasses.replace(conf, dtype="float64")
        net = ComputationGraph(conf64).init()
        mds = _as_multi(mds)
        features = tuple(jnp.asarray(np.asarray(f), jnp.float64)
                         for f in mds.features)
        labels = tuple(jnp.asarray(np.asarray(l), jnp.float64)
                       for l in mds.labels)
        fmasks = tuple(
            jnp.asarray(np.asarray(m), jnp.float64) if m is not None else None
            for m in (mds.features_masks if mds.features_masks is not None
                      else (None,) * len(features)))
        if mds.labels_masks is not None:
            lmasks = tuple(
                jnp.asarray(np.asarray(m), jnp.float64) if m is not None
                else jnp.ones((labels[i].shape[0],), jnp.float64)
                for i, m in enumerate(mds.labels_masks))
        else:
            lmasks = tuple(jnp.ones((l.shape[0],), jnp.float64)
                           for l in labels)

        return _check_net_params_gradient(
            conf64, net, (features, labels, fmasks, lmasks), epsilon,
            max_rel_error, abs_error_threshold, n_samples, seed)
