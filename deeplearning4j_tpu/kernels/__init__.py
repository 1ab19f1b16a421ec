"""Pallas kernel subsystem: registry, per-shape autotuner, routing.

ROADMAP item 5: training is conv-compute-bound (BASELINE.md,
bench_conv_matrix.json — sync + ingest < 0.1%), so raw speed now only
comes from better kernels than the ones XLA emits. This package makes
the hand-kernel path SYSTEMATIC instead of ad hoc (the PyGraph
compiler-integration argument, arXiv:2503.19779):

- ``registry``: named Pallas kernels (fused conv+BN statistics — the
  round-2 ``ops/conv_fused`` experiment — and a tiled
  matmul+bias+activation), each with a declared shape/dtype envelope,
  a tiling parameter space, and the ``jax.lax`` reference it must
  match;
- ``tuner``: the per-(shape, dtype, backend) autotuner and the
  digest-verified on-disk tuning cache (temp+rename; corruption is a
  named refusal + stock-XLA fallback);
- ``routing``: the forward-pass dispatch behind ``conf.use_kernels``
  (default OFF — bit-identical to no subsystem at all); ``backend()``
  picks real Mosaic lowering on a TPU backend and the Pallas
  interpreter everywhere else, so CPU containers validate the same
  kernel bodies end to end.

Selection is keyed into ``optimize/aot_cache`` via
``cache_tag(conf)``'s ``kern:<id>:<digest>`` tokens: a retuned kernel
is a NEW executable, an untuned shape is stock XLA, and the program
linter's PRG207 audits every token against this registry.

See docs/kernels.md.
"""

from __future__ import annotations

import os

from deeplearning4j_tpu.kernels import impls as impls  # noqa: F401
from deeplearning4j_tpu.kernels import registry as registry  # noqa: F401
from deeplearning4j_tpu.kernels import routing as routing  # noqa: F401
from deeplearning4j_tpu.kernels import tuner as tuner  # noqa: F401
from deeplearning4j_tpu.kernels.registry import (  # noqa: F401
    AttentionEnvelope,
    Kernel,
    KernelRegistry,
    MatmulEnvelope,
    REGISTRY,
    Selection,
)
from deeplearning4j_tpu.kernels.routing import (  # noqa: F401
    autotune_decoder,
    autotune_model,
    backend,
    decoder_envelopes,
    maybe_flash_attention,
    maybe_forward,
    maybe_vertex_forward,
    plan_envelopes,
)
from deeplearning4j_tpu.kernels.tuner import (  # noqa: F401
    AutotuneResult,
    TUNING,
    TuningCache,
    TuningCacheCorruptError,
    autotune,
    set_tuning_cache,
)


def tuning_digest(kernel_id: str) -> str:
    """The registry's current 8-hex tuning digest for one kernel (what
    the ``kern:<id>:<digest>`` key tokens carry)."""
    return REGISTRY.tuning_digest(kernel_id)


def cache_tag(conf=None) -> str:
    """The step-key token string for a model conf: empty unless
    ``conf.use_kernels`` (so every pre-subsystem key is unchanged),
    else one ``:kern:<id>:<digest>`` token per registered kernel.
    Cheap per call — digests are memoized against the tuning-cache
    epoch — so fit loops re-check it every dispatch and rebuild their
    step on a retune."""
    if conf is not None and not getattr(conf, "use_kernels", False):
        return ""
    return REGISTRY.cache_tag()


# opt-in persistent cache via environment (bound lazily so importing
# the package never touches the filesystem unless asked)
_ENV_CACHE = "DL4J_TPU_KERNEL_CACHE"
if os.environ.get(_ENV_CACHE):
    try:
        set_tuning_cache(os.environ[_ENV_CACHE])
    except TuningCacheCorruptError:
        # refused: the named error already detached the file; selection
        # runs on stock XLA until a fresh cache is bound
        pass
