"""Op validation harness.

Reference: ``org.nd4j.autodiff.validation.OpValidation`` + ``TestCase`` —
per-op forward value checks, gradient checks, and COVERAGE ACCOUNTING
(the reference fails CI when an op has no validation). Here:

- :class:`TestCase`: expected outputs + gradient checking for one op node.
- :func:`validate`: runs a TestCase (forward compare + f64 central
  differences vs the lowered graph's ``jax.grad``).
- :func:`coverage_report`: which registered ops have been validated in this
  process — tests assert a floor so newly added ops must bring a TestCase.
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.samediff import ops as _ops  # noqa: F401  — importing
# populates OP_REGISTRY (namespaces are otherwise lazy; a validate() call
# before any namespace use must still see the full registry)
from deeplearning4j_tpu.samediff.core import OP_REGISTRY, SameDiff

_VALIDATED: set[str] = set()


class TestCase:
    """One op validation case (reference ``TestCase``)."""

    __test__ = False  # not a pytest class, despite the (parity) name

    def __init__(self, sd: SameDiff, inputs: dict, expected: dict,
                 grad_wrt: list | None = None, epsilon: float = 1e-6,
                 max_rel_error: float = 1e-4):
        self.sd = sd
        # float inputs promote to f64 (the reference's double-precision
        # gradient-check protocol); integer/bool inputs keep their dtype
        # (bitwise/scatter-index operands must stay integral)
        self.inputs = {
            k: (np.asarray(v, np.float64)
                if np.issubdtype(np.asarray(v).dtype, np.floating)
                else np.asarray(v))
            for k, v in inputs.items()}
        self.expected = {k: np.asarray(v) for k, v in expected.items()}
        # grad_wrt=[] means "forward-only" (bool/int outputs, non-smooth
        # ops); None defaults to every FLOAT input (integral operands —
        # indices, segment ids — are not differentiable)
        self.grad_wrt = (
            [k for k, v in self.inputs.items()
             if np.issubdtype(v.dtype, np.floating)]
            if grad_wrt is None else list(grad_wrt))
        self.epsilon = float(epsilon)
        self.max_rel_error = float(max_rel_error)


def validate(case: TestCase) -> None:
    """Forward compare + central-difference gradient check in f64
    (``jax.enable_x64``, mirroring the reference's double-precision-only
    gradient checks); records coverage for every op node in the case's
    graph."""
    import jax

    with jax.enable_x64(True):
        _validate_x64(case)


def _validate_x64(case: TestCase) -> None:
    sd = case.sd
    out_names = tuple(case.expected)

    outs = sd.output(case.inputs, *out_names)
    for name, want in case.expected.items():
        np.testing.assert_allclose(
            np.asarray(outs[name], np.float64), want, rtol=1e-5, atol=1e-6,
            err_msg=f"forward mismatch for output {name!r}")

    if not case.grad_wrt:
        for node in sd.ops.values():
            _VALIDATED.add(node.op_name)
        return

    # gradient of sum(outputs) wrt each requested placeholder
    import jax
    import jax.numpy as jnp

    fn = sd.make_function(out_names)

    def scalar(ph_vals):
        res = fn(dict(sd.arrays), {
            k: (jnp.asarray(v, jnp.float64)
                if np.issubdtype(jnp.asarray(v).dtype, np.floating)
                else jnp.asarray(v))
            for k, v in ph_vals.items()})
        return sum(jnp.sum(v) for v in res.values())

    # differentiate ONLY the requested (float) placeholders — int/bool
    # operands (indices, segment ids, masks) ride along as constants
    fixed = {k: v for k, v in case.inputs.items() if k not in case.grad_wrt}
    analytic = jax.grad(lambda pv: scalar({**fixed, **pv}))(
        {k: jnp.asarray(v) for k, v in case.inputs.items()
         if k in case.grad_wrt})
    for k in case.grad_wrt:
        a = np.asarray(analytic[k], np.float64).ravel()
        x0 = np.asarray(case.inputs[k])
        flat0 = x0.ravel()
        n = flat0.size

        # VMAPPED central differences in chunks: one compiled call per
        # chunk of up/down evaluations instead of two EAGER whole-graph
        # executions per element (the per-element loop dominated the
        # tier-1 op-validation wall time). Tiny inputs (n <= 8) keep the
        # eager loop — a jit+vmap compile costs more than 16 eager evals
        # of a small graph. Same evaluations, same math, either way.
        numeric = np.empty(n, np.float64)
        if n <= 8:
            work = flat0.copy()
            for idx in range(n):
                orig = work[idx]
                work[idx] = orig + case.epsilon
                up = float(scalar({**case.inputs,
                                   k: work.reshape(x0.shape)}))
                work[idx] = orig - case.epsilon
                dn = float(scalar({**case.inputs,
                                   k: work.reshape(x0.shape)}))
                work[idx] = orig
                numeric[idx] = (up - dn) / (2 * case.epsilon)
        else:
            def scalar_k(xk_flat, _k=k, _shape=x0.shape):
                return scalar({**case.inputs, _k: xk_flat.reshape(_shape)})

            fv = jax.jit(jax.vmap(scalar_k))
            chunk = 256
            for start in range(0, n, chunk):
                ii = np.arange(start, min(start + chunk, n))
                pert = np.zeros((len(ii), n), x0.dtype)
                pert[np.arange(len(ii)), ii] = case.epsilon
                up = np.asarray(fv(jnp.asarray(flat0[None] + pert)),
                                np.float64)
                dn = np.asarray(fv(jnp.asarray(flat0[None] - pert)),
                                np.float64)
                numeric[ii] = (up - dn) / (2 * case.epsilon)

        for idx in range(n):
            # central differences bottom out around eps_machine/epsilon —
            # treat both-tiny as matching zero
            if abs(numeric[idx]) < 1e-7 and abs(a[idx]) < 1e-7:
                continue
            denom = max(abs(numeric[idx]), abs(a[idx]), 1e-8)
            rel = abs(numeric[idx] - a[idx]) / denom
            assert rel < case.max_rel_error, (
                f"gradient mismatch for {k}[{idx}]: "
                f"numeric={numeric[idx]:.3e} "
                f"analytic={a[idx]:.3e} rel={rel:.3e}")

    for node in sd.ops.values():
        _VALIDATED.add(node.op_name)


def coverage_report() -> dict:
    """{'validated': n, 'registered': m, 'missing': [...]} for this
    process (reference: OpValidation's coverage accounting)."""
    registered = set(OP_REGISTRY)
    return {
        "validated": len(_VALIDATED & registered),
        "registered": len(registered),
        "missing": sorted(registered - _VALIDATED),
    }
