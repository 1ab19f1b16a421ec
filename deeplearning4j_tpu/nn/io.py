"""Host↔device batch placement shared by MultiLayerNetwork and
ComputationGraph.

uint8 FEATURE batches keep their dtype across the host→device link (4x less
traffic than float32) and are dequantized to ``[0, 1]`` floats inside the
compiled program (the ``ImagePreProcessingScaler`` math moved on-device). Labels and
masks always land as the network dtype — only inputs get the quantized
transfer. Arrays that are already ``jax.Array`` (an
``AsyncDataSetIterator(device_put=True)`` or ``ParallelInference`` placed
them, possibly with a committed sharding) pass through without a host
round-trip, but still get a device-side cast if their dtype disagrees.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np


def as_device(a, dtype, feature: bool = False):
    """Place ``a`` on device. ``feature=True`` preserves uint8 (dequantized
    later inside the jit by :func:`dequant`); everything else is cast to
    ``dtype``."""
    if isinstance(a, jax.Array):
        if feature and a.dtype == jnp.uint8:
            return a
        return a if a.dtype == jnp.dtype(dtype) else a.astype(dtype)
    a = np.asarray(a)
    if feature and a.dtype == np.uint8:
        return jax.device_put(a)
    if a.dtype != np.dtype(dtype):
        a = np.asarray(a, dtype)
    # device_put streams the host buffer directly (jnp.asarray can take a
    # much slower conversion path for large arrays)
    return jax.device_put(a)


def dequant(x, dtype, scale: bool = True):
    """In-jit conversion of uint8 features: image-shaped inputs scale to
    [0, 1] (``scale=True``); integer-valued inputs (e.g. embedding token
    ids) just cast, preserving their values."""
    if x.dtype == jnp.uint8:
        x = x.astype(dtype)
        return x * (1.0 / 255.0) if scale else x
    return x


def cast_floats(tree, dtype):
    """Cast every floating-point leaf of a pytree to ``dtype`` (the
    mixed-precision compute cast: f32 master params -> bf16 compute
    copies inside the jitted step; its transpose under ``jax.grad``
    up-casts gradients back to the master dtype for free). Non-float
    leaves (int token ids, uint8 images) pass through untouched."""
    dt = jnp.dtype(dtype)
    return jax.tree_util.tree_map(
        lambda x: x.astype(dt)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dt else x,
        tree)


def image_input(input_type) -> bool:
    """Whether a network InputType is image-shaped (uint8 batches then mean
    pixels, dequantized to [0,1]); non-image uint8 (token ids) only cast."""
    from deeplearning4j_tpu.conf import inputs as it

    return isinstance(input_type, (it.Convolutional, it.ConvolutionalFlat))


def warm_dtype_variants(input_types, base_dtype, quantization=None):
    """THE source of truth for the client-visible input-dtype variant sets
    a serving engine must pre-compile per padding bucket
    (``InferenceEngine.warmup`` delegates here; keep any new variant in
    this one derivation).

    Per input: image-typed inputs reach the device as either the float
    base dtype or raw uint8 (the quantized-feature path of
    :func:`as_device` — a DIFFERENT aval, hence a different executable),
    so both are covered; everything else serves the base dtype only.
    ``quantization`` (the conf's ``QuantizationSpec``) adds no variant:
    int8 quantization happens in-graph behind the same f32/uint8 client
    avals, keyed by the artifact's ``q:<scheme>:<digest8>`` token — the
    quantized executables are warmed through this same product, just
    under their own keys. Returns the cross-product list of per-input
    dtype tuples.
    """
    import itertools

    import numpy as np

    base = np.dtype(base_dtype)
    per_input = []
    for t in input_types:
        if t is not None and image_input(t):
            per_input.append((base, np.dtype(np.uint8)))
        else:
            per_input.append((base,))
    return list(itertools.product(*per_input))


# bounded dispatch depth for async fit loops: a host read of a device
# value is a sync that drains the queue, so the pipeline is deep enough to
# queue a whole small epoch; transfer-heavy loops can lower it via env to
# avoid queueing device memory for many in-flight batches
DISPATCH_DEPTH = int(os.environ.get("DL4J_TPU_DISPATCH_DEPTH", "12"))


def step_scalars(itc, base_key):
    """In-jit derivation of the per-step scalars from the device iteration
    counter: (float iteration for LR schedules, folded rng key). ONE
    definition so MultiLayerNetwork and ComputationGraph stay in RNG/LR
    lockstep."""
    it = itc.astype(jnp.float32)
    rng = jax.random.fold_in(base_key, itc + 1_000_003)
    return it, rng


def drain(pending, force: bool = False):
    """Block on queued step results when the pipeline is full (or at epoch
    end with ``force``); returns the (possibly emptied) list. The block is
    an intentional device wait, so the telemetry host-gap clock pauses
    around it (device time must never read as host dispatch gap). It
    waits for ALL of them: the device's queue is empty when it returns.
    The ``drain`` span says how many steps it blocked on."""
    if pending and (force or len(pending) >= DISPATCH_DEPTH):
        from deeplearning4j_tpu.telemetry import spans

        with spans.span("drain", sync=True, steps=len(pending)):
            spans.host_gap_pause()
            try:
                jax.block_until_ready(pending)
            finally:
                spans.host_gap_resume()
        pending.clear()
    return pending


def timed_batches(batches):
    """``batches``, with each ``next()`` of its iterator under a
    ``fit.next_batch`` span: the time a fit loop waited for its input."""
    from deeplearning4j_tpu.telemetry import spans

    it = iter(batches)
    while True:
        with spans.span("fit.next_batch"):
            try:
                ds = next(it)
            except StopIteration:
                return
        yield ds


class LazyScoreMixin:
    """``score_value`` backed by a device scalar, converted to float only
    when read (both network classes share the async-fit contract)."""

    _score_dev = None
    _score_cache = None

    @property
    def score_value(self) -> float:
        if self._score_cache is None and self._score_dev is not None:
            self._score_cache = float(self._score_dev)
        return (self._score_cache if self._score_cache is not None
                else float("nan"))

    @score_value.setter
    def score_value(self, v):
        self._score_dev = None
        self._score_cache = None if v is None else float(v)

    # --- health-layer rollback hooks ---------------------------------------
    # (telemetry.health ROLLBACK policy; wrappers holding device-resident
    # training trees override these with their own capture/restore)

    def _health_snapshot(self):
        from deeplearning4j_tpu.optimize import checkpoint

        return checkpoint.snapshot_training_state(self)

    def _health_restore(self, snap):
        from deeplearning4j_tpu.optimize import checkpoint

        checkpoint.restore_training_state(self, snap)

    # --- device-resident step counters -------------------------------------
    # Every eager host-side op (jnp.asarray, fold_in, jnp.ones) is a
    # dispatch of its own, as costly to launch as the whole compiled
    # step. The iteration counter therefore LIVES on device: the jitted
    # step increments and returns it (donated), and the host only
    # re-materializes it if user code rewrote ``self.iteration`` between
    # steps.

    _it_dev = None
    _it_mirror = -1
    _ep_dev = None
    _ep_mirror = -1

    def device_iteration(self):
        if self._it_dev is None or self._it_mirror != self.iteration:
            self._it_dev = jnp.asarray(self.iteration, jnp.int32)
            self._it_mirror = self.iteration
        return self._it_dev

    def advance_device_iteration(self, new_dev):
        """Record the step-returned counter. Call AFTER ``self.iteration``
        was incremented so the mirror matches."""
        self._it_dev = new_dev
        self._it_mirror = self.iteration

    def device_epoch(self):
        if self._ep_dev is None or self._ep_mirror != self.epoch:
            self._ep_dev = jnp.asarray(float(self.epoch), jnp.float32)
            self._ep_mirror = self.epoch
        return self._ep_dev


def propagate_mask(mask, y, layer_or_vertex):
    """Thread a [batch, time] feature mask past one layer/vertex whose
    OUTPUT is ``y`` (reference ``feedForwardMaskArray`` semantics, decided
    from traced shapes so unknown conf timesteps work): same-T sequence
    output keeps the mask; a time-RESIZING layer exposing ``resize_mask``
    (strided Conv1D, 1D pooling/crop/upsample/pad — max-pool semantics)
    transforms it; losing the sequence shape (pooling over time,
    LastTimeStep, flatten) or resizing without a resizer terminates it."""
    if mask is None:
        return None
    if getattr(y, "ndim", 0) != 3:
        return None
    if y.shape[1] == mask.shape[1]:
        return mask
    layer = layer_or_vertex
    while layer is not None:
        rm = getattr(layer, "resize_mask", None)
        if rm is not None:
            resized = rm(mask)
            return resized if resized.shape[1] == y.shape[1] else None
        layer = getattr(layer, "layer", None)
    return None


def contains_go_backwards(layer) -> bool:
    """Walks wrapper ``.layer`` chains for the Keras go_backwards flag
    (shared by MultiLayerNetwork and ComputationGraph: such layers get
    PER-SEGMENT RESET under tBPTT and refuse rnn_time_step streaming)."""
    while layer is not None:
        if getattr(layer, "go_backwards", False):
            return True
        layer = getattr(layer, "layer", None)
    return False


def check_streaming_safe(layer, label: str):
    """Shared ``rnn_time_step`` guard: reject layers whose per-segment
    streaming would silently diverge from the full-sequence forward —
    Bidirectional / go_backwards (need the whole sequence) and carry-less
    time-mixing layers (``streaming_safe() is False``: windowed convs/
    pools/crops/pads, full-sequence attention). Walks wrapper ``.layer``
    chains."""
    def contains_bidirectional(l):
        if type(l).__name__ == "Bidirectional":
            return True
        inner = getattr(l, "layer", None)
        return inner is not None and contains_bidirectional(inner)

    if contains_bidirectional(layer):
        raise RuntimeError(
            f"rnn_time_step is unsupported for Bidirectional layers "
            f"({label}, including wrapped ones): the backward pass needs "
            "the full sequence (reference throws "
            "UnsupportedOperationException here)")
    inner = layer
    while inner is not None:
        if getattr(inner, "go_backwards", False):
            raise RuntimeError(
                f"rnn_time_step is unsupported for go_backwards RNNs "
                f"({label}): reversed processing needs the full sequence")
        safe = getattr(inner, "streaming_safe", None)
        if safe is not None and not safe():
            raise RuntimeError(
                f"rnn_time_step is unsupported for {label} "
                f"({type(inner).__name__}): it mixes/resizes the time "
                "axis without recurrent state, so per-segment streaming "
                "would silently diverge from the full forward at call "
                "boundaries")
        inner = getattr(inner, "layer", None)
