"""MultiLayerNetwork — sequential model runtime.

Reference: ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork`` (~4k LoC):
``fit`` / ``output`` / ``score`` / ``evaluate``, flat params vector,
listeners, updater application via ``MultiLayerUpdater``.

TPU-native inversion (SURVEY.md §3.1): the reference's hot loop —
per-layer ``activate``/``backpropGradient`` calls each crossing JNI per op —
becomes ONE ``jax.jit``-compiled XLA program:
``train_step(params, state, opt_state, batch) -> (params', state',
opt_state', loss)``. Forward, backward (``jax.grad``), gradient
normalization, regularization and updater all fuse into a single
device executable; the Python loop only feeds batches.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.conf.multilayer import MultiLayerConfiguration
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import io as nn_io
from deeplearning4j_tpu.datasets.iterators import (
    ArrayDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.optimize import aot_cache, solver
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.util import params as params_util


def _as_iterator(data, labels=None, batch_size: Optional[int] = None):
    if isinstance(data, DataSetIterator):
        return data
    if isinstance(data, DataSet):
        return ListDataSetIterator([data])
    if labels is not None:
        return ArrayDataSetIterator(data, labels,
                                    batch_size or np.asarray(data).shape[0],
                                    drop_last=False)
    raise TypeError(f"cannot build DataSetIterator from {type(data)}")


def _wrap_fused(iterator, fused_steps, conf):
    """``fit(fused_steps=K)`` plumbing shared by both model types: wrap
    the fit iterator in a K-stacking ``DeviceRingIterator`` (no-op for
    K<=1 or an already-K-stacking ring, so composed/pre-wrapped inputs
    never double-stack). tBPTT configs refuse — a tBPTT batch already
    trains as one compiled segment scan owning the time axis."""
    k = int(fused_steps or 0)
    if k <= 1:
        return iterator
    from deeplearning4j_tpu.conf.multilayer import BackpropType

    if conf.backprop_type is BackpropType.TRUNCATED_BPTT:
        raise ValueError(
            "fused_steps composes with STANDARD backprop only: a tBPTT "
            "batch already trains as one compiled segment scan")
    from deeplearning4j_tpu.datasets.prefetch import DeviceRingIterator

    if getattr(iterator, "stack_batches", 0) == k:
        return iterator
    return DeviceRingIterator(iterator, stack_batches=k)


def _is_go_backwards_layer(layer) -> bool:
    """go_backwards layers get PER-SEGMENT RESET under tBPTT (their
    reversed scan's carry would come from the FUTURE segment) — same
    contract as ComputationGraph (nn/graph.py _is_go_backwards); single-
    segment training is exactly standard BPTT, pinned in tests."""
    return nn_io.contains_go_backwards(layer)


class MultiLayerNetwork(nn_io.LazyScoreMixin):
    """Sequential network (reference ``MultiLayerNetwork``)."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params: Optional[Dict[str, dict]] = None
        self.state: Dict[str, dict] = {}
        self.opt_state: Dict[str, dict] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[TrainingListener] = []
        self.last_batch_size: Optional[int] = None
        self._score_dev = None
        self._score_cache: Optional[float] = float("nan")
        self._train_step = None
        self._tbptt_scan = None
        self._fused_scan = None
        self._output_fn = None
        self._score_fn = None
        self._rnn_step_fn = None
        self._rnn_carries = None
        self._dtype = jnp.dtype(conf.dtype)
        # mixed precision: forward/backward in compute_dtype (bf16), params/
        # opt-state/BN-stats/loss in dtype (f32 masters) — see the conf field
        self._cdtype = (jnp.dtype(conf.compute_dtype)
                        if getattr(conf, "compute_dtype", None) else None)
        self._base_key = jax.random.PRNGKey(conf.seed)

    # --- lifecycle ---------------------------------------------------------
    def init(self) -> "MultiLayerNetwork":
        """Initialize params/state/updater-state (reference ``#init``)."""
        key = self._base_key
        types = self.conf.input_types()
        self.params, self.state, self.opt_state = {}, {}, {}
        for i, (layer, itype) in enumerate(zip(self.conf.layers, types)):
            p = layer.init(jax.random.fold_in(key, i), itype, self._dtype)
            if p:
                self.params[str(i)] = p
            s = layer.init_state(itype, self._dtype)
            if s:
                self.state[str(i)] = s
        for k, lp in self.params.items():
            upd = self._updater_for(int(k))
            self.opt_state[k] = {pk: upd.init_state(pv) for pk, pv in lp.items()}
        return self

    def set_listeners(self, *listeners: TrainingListener):
        self.listeners = list(listeners)
        return self

    def _updater_for(self, layer_idx: int):
        layer = self.conf.layers[layer_idx]
        return getattr(layer, "updater", None) or self.conf.updater

    def _graph_key(self) -> str:
        """AOT-cache graph signature (optimize.aot_cache): content-keyed on
        the conf when its repr is deterministic, so clones and fresh
        instances of the same network reuse compiled step executables."""
        if getattr(self, "_graph_key_cache", None) is None:
            self._graph_key_cache = "mln:" + aot_cache.graph_signature(
                self.conf, fallback=self)
        return self._graph_key_cache

    def _ktag(self) -> str:
        """Kernel-registry step-key tokens (``kernels.cache_tag``):
        empty unless ``conf.use_kernels`` — every pre-subsystem key is
        unchanged — else ``:kern:<id>:<digest>`` per kernel, so a
        RETUNED kernel re-keys (and re-traces) the step instead of
        silently dispatching the stale layout."""
        if not getattr(self.conf, "use_kernels", False):
            return ""
        from deeplearning4j_tpu import kernels

        return kernels.cache_tag(self.conf)

    def _qtag(self) -> str:
        """Quantization step-key token: empty unless the conf carries a
        ``QuantizationSpec`` (default-off is bitwise inert — every
        pre-quantization key is unchanged), else ``:q:<scheme>:<digest8>``
        so a RECALIBRATION mints a new executable instead of silently
        serving stale scales, and PRG208 can audit every quantized
        executable against the live calibration records."""
        q = getattr(self.conf, "quantization", None)
        if q is None:
            return ""
        return f":q:{q.scheme}:{q.digest[:8]}"

    # --- functional core ---------------------------------------------------
    def _forward(self, params, state, x, train: bool, rng, fmask=None,
                 upto: int = None, carries=None):
        """Pure forward pass over layers [0, upto). Returns (x, new_state,
        new_carries). ``fmask``: per-timestep features mask [batch, time],
        given only to mask-consuming layers (RNNs, wrappers) and RESIZED
        through time-resizing layers (reference ``feedForwardMaskArray``
        through the stack, round 3 — decided from TRACED shapes, so
        variable-length configs with unknown conf timesteps resize too):
        output stays [B, T, ..] with the mask's T -> keep; T changed and
        the layer exposes ``resize_mask`` (strided Conv1D / 1D pooling /
        crop / upsample / pad, max-pool semantics) -> resize; sequence
        shape lost or no resizer -> the mask terminates. ``carries``:
        {layer_idx: carry} recurrent state threaded across tBPTT segments /
        ``rnn_time_step`` calls; None = start every RNN from zeros."""
        n = len(self.conf.layers) if upto is None else upto
        new_state, new_carries = {}, {}
        remat = bool(getattr(self.conf, "gradient_checkpointing", False))
        use_k = bool(getattr(self.conf, "use_kernels", False))
        if use_k:
            from deeplearning4j_tpu import kernels as _kernels
        for i in range(n):
            layer = self.conf.layers[i]
            p = params.get(str(i), {})
            s = state.get(str(i), {})
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            kw = {"mask": fmask} if getattr(layer, "uses_mask", False) else {}
            if carries is not None and getattr(layer, "has_carry", False) \
                    and not _is_go_backwards_layer(layer):
                c = carries.get(str(i))
                if c is None:
                    c = layer.zero_carry(x.shape[0], x.dtype)
                x, c2 = layer.forward_with_carry(p, c, x, train=train,
                                                 rng=lrng, **kw)
                new_carries[str(i)] = c2
                if str(i) in state:
                    new_state[str(i)] = s
            else:
                # kernel-registry routing (conf.use_kernels): a TUNED
                # Pallas kernel covering this layer's concrete shapes
                # replaces the stock forward; None = stock XLA unchanged
                routed = (_kernels.maybe_forward(
                    layer, p, s, x, train=train, rng=lrng, **kw)
                    if use_k else None)
                if routed is not None:
                    x, s2 = routed
                elif remat and layer.has_params():
                    def fwd(p, s, x, _layer=layer, _rng=lrng, _kw=kw):
                        return _layer.forward(p, s, x, train=train,
                                              rng=_rng, **_kw)

                    x, s2 = jax.checkpoint(fwd)(p, s, x)
                else:
                    x, s2 = layer.forward(p, s, x, train=train, rng=lrng,
                                          **kw)
                if str(i) in state:
                    new_state[str(i)] = s2
            fmask = nn_io.propagate_mask(fmask, x, layer)
        return x, new_state, new_carries

    def _output_layer(self):
        last = self.conf.layers[-1]
        if not hasattr(last, "score"):
            raise TypeError(
                f"last layer {type(last).__name__} is not an output layer "
                "(reference: fit() requires an IOutputLayer)")
        return last

    def _dequant(self, x):
        return nn_io.dequant(x, self._cdtype or self._dtype,
                             scale=nn_io.image_input(self.conf.input_type))

    def _fwd_cast(self, params, x, fmask, full: bool = False):
        """Mixed-precision cast for one forward pass: params/input/mask to
        the compute dtype. ``full=True`` = the pass runs THROUGH the output
        layer — its params stay f32 masters so logits land in the storage
        dtype (promotion does the upcast). No-op without a policy."""
        if self._cdtype is None:
            return params, x, fmask
        cast = nn_io.cast_floats(params, self._cdtype)
        if full:
            last = str(len(self.conf.layers) - 1)
            if last in params:
                cast[last] = params[last]
        x, fmask = nn_io.cast_floats((x, fmask), self._cdtype)
        return cast, x, fmask

    def _loss(self, params, state, features, labels, fmask, lmask, rng,
              train=True, carries=None):
        features = self._dequant(features)
        out_layer = self._output_layer()
        last = len(self.conf.layers) - 1
        fwd_params, features, fmask = self._fwd_cast(params, features, fmask)
        if self._cdtype is not None and carries is not None:
            carries = nn_io.cast_floats(carries, self._cdtype)
        x, new_state, new_carries = self._forward(
            fwd_params, state, features, train=train, rng=rng, fmask=fmask,
            upto=last, carries=carries)
        # output-layer activation + loss in the storage dtype on the f32
        # master params: log-softmax over many classes is exactly where
        # bf16 loses bits that show up in gradients
        x = x.astype(self._dtype)
        loss = out_layer.score(params.get(str(last), {}), x, labels, lmask)
        loss = loss + solver.regularization_score(self.conf.layers, params)
        if train:  # eval must not pick up the stale training aux
            from deeplearning4j_tpu.conf.layers_moe import sum_aux_losses

            loss = loss + sum_aux_losses(new_state, self._dtype)
        return loss, (new_state, new_carries)

    def train_step_fn(self, guards: str = ""):
        """The raw (unjitted) pure train step — exposed so parallel wrappers
        can jit it under a Mesh with explicit shardings (stage-7 path).

        ``guards`` (``telemetry.health.graph_mode()``): ``"observe"``
        appends the packed health guard vector to the step outputs;
        ``"skip"`` additionally applies the in-graph SKIP_STEP select
        (an anomalous step's params/state/opt/carries revert to their
        inputs). ``""`` compiles the unguarded step."""
        from deeplearning4j_tpu.telemetry import health

        layers = self.conf.layers

        def step(params, state, opt_state, features, labels, fmask, lmask,
                 it, ep, rng, carries=None):
            def loss_fn(p):
                return self._loss(p, state, features, labels, fmask, lmask,
                                  rng, carries=carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = {}, {}
            for k in params:
                layer = layers[int(k)]
                upd = self._updater_for(int(k))
                lr = upd.current_lr(it, ep)
                g = solver.normalize_layer_gradients(layer, grads[k])
                new_params[k], new_opt[k] = solver.apply_updater_to_layer(
                    layer, upd, params[k], g, opt_state[k], lr, it, ep)
            if carries is not None:
                # tBPTT: the next segment resumes from this segment's
                # final RNN state, detached (gradients do not flow across
                # segments — reference BackpropType.TruncatedBPTT)
                new_carries = jax.lax.stop_gradient(new_carries)
            if guards:
                vec = health.guard_vector(loss, grads, params=params,
                                          new_params=new_params)
                if guards == "skip":
                    if carries is None:
                        (new_params, new_state, new_opt) = health.apply_skip(
                            vec, (new_params, new_state, new_opt),
                            (params, state, opt_state))
                    else:
                        (new_params, new_state, new_opt,
                         new_carries) = health.apply_skip(
                            vec,
                            (new_params, new_state, new_opt, new_carries),
                            (params, state, opt_state, carries))
                if carries is None:
                    return new_params, new_state, new_opt, loss, vec
                return (new_params, new_state, new_opt, loss, new_carries,
                        vec)
            if carries is None:
                return new_params, new_state, new_opt, loss
            return new_params, new_state, new_opt, loss, new_carries

        return step

    def grad_fn(self):
        """Backward only, updater NOT applied: (params, state, features,
        labels, fmask, lmask, rng) -> (loss, new_state, grads). The split
        point where ParallelWrapper interposes gradient exchange (reference
        ``EncodingHandler#encodeUpdates`` hook, SURVEY.md §3.4). With
        ``carries`` (a tBPTT segment) the return gains detached
        ``new_carries``."""

        def gfn(params, state, features, labels, fmask, lmask, rng,
                carries=None):
            def loss_fn(p):
                return self._loss(p, state, features, labels, fmask, lmask,
                                  rng, carries=carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if carries is None:
                return loss, new_state, grads
            return loss, new_state, grads, jax.lax.stop_gradient(new_carries)

        return gfn

    def apply_updates_fn(self):
        """Updater half of the step: (params, opt_state, grads, it, ep) ->
        (new_params, new_opt_state). Gradient normalization + regularization
        + per-layer updater (reference ``MultiLayerUpdater#update``)."""
        layers = self.conf.layers

        def afn(params, opt_state, grads, it, ep):
            new_params, new_opt = {}, {}
            for k in params:
                layer = layers[int(k)]
                upd = self._updater_for(int(k))
                lr = upd.current_lr(it, ep)
                g = solver.normalize_layer_gradients(layer, grads[k])
                new_params[k], new_opt[k] = solver.apply_updater_to_layer(
                    layer, upd, params[k], g, opt_state[k], lr, it, ep)
            return new_params, new_opt

        return afn

    def _build_train_step(self):
        from deeplearning4j_tpu.telemetry import health

        mode = health.graph_mode()
        raw = self.train_step_fn(guards=mode)
        dtype = self._dtype

        # all per-step scalar work (iteration, epoch, rng fold, default
        # mask) happens INSIDE the jit: the only host-side cost per step is
        # the batch transfer + one dispatch (see nn_io device counters)
        def step(params, state, opt_state, features, labels, fmask, lmask,
                 itc, ep, base_key):
            it, rng = nn_io.step_scalars(itc, base_key)
            if lmask is None:
                lmask = jnp.ones((features.shape[0],), dtype)
            out = raw(params, state, opt_state, features, labels, fmask,
                      lmask, it, ep, rng)
            new_p, new_s, new_o, loss = out[:4]
            if mode:
                return new_p, new_s, new_o, loss, itc + 1, out[4]
            return new_p, new_s, new_o, loss, itc + 1

        self._train_step_mode = mode
        self._train_step_ktag = self._ktag()
        self._guard_keys = health.bucket_keys(self.params or {})
        return aot_cache.wrap(
            jax.jit(step, donate_argnums=(0, 1, 2, 7)),
            self._graph_key(),
            f"train_step:d012+itc{health.cache_tag()}"
            f"{self._train_step_ktag}{self._qtag()}")

    def _build_output_fn(self):
        def out(params, state, x, fmask):
            params, x, fmask = self._fwd_cast(params, self._dequant(x),
                                              fmask, full=True)
            y, _, _ = self._forward(params, state, x,
                                    train=False, rng=None, fmask=fmask)
            return y.astype(self._dtype)

        self._output_ktag = self._ktag()
        return aot_cache.wrap(jax.jit(out), self._graph_key(),
                              f"output{self._output_ktag}{self._qtag()}")

    def _build_rnn_step_fn(self):
        def out(params, state, carries, x, fmask):
            params, x, fmask = self._fwd_cast(params, self._dequant(x),
                                              fmask, full=True)
            if self._cdtype is not None:
                carries = nn_io.cast_floats(carries, self._cdtype)
            y, _, new_carries = self._forward(
                params, state, x, train=False, rng=None,
                fmask=fmask, carries=carries)
            return y.astype(self._dtype), new_carries

        return jax.jit(out)

    def _build_score_fn(self):
        def score(params, state, features, labels, fmask, lmask):
            # eval mode: BN uses running stats, dropout off — matches the
            # reference's score() running feed-forward in inference mode
            loss, _ = self._loss(params, state, features, labels, fmask,
                                 lmask, rng=None, train=False)
            return loss

        self._score_ktag = self._ktag()
        return aot_cache.wrap(jax.jit(score), self._graph_key(),
                              f"score{self._score_ktag}{self._qtag()}")

    # --- training ----------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1,
            batch_size: Optional[int] = None,
            fused_steps: Optional[int] = None):
        """Train (reference ``MultiLayerNetwork#fit`` overloads: iterator,
        DataSet, or (features, labels) arrays).

        ``fused_steps=K`` (round 11): fuse K optimization steps into ONE
        compiled dispatch — the iterator is wrapped in a K-stacking
        ``DeviceRingIterator`` (one ``device_put`` per super-step,
        consumed stacks donated) and each stack trains through the
        ``lax.scan`` fused runner. Bit-identical to K=1 on the same
        batch stream; listeners still see K per-step losses. Composes
        with STANDARD backprop only (tBPTT already scans segments)."""
        from deeplearning4j_tpu.telemetry import flightrec

        if self.params is None:
            self.init()
        iterator = _as_iterator(data, labels, batch_size)
        iterator = _wrap_fused(iterator, fused_steps, self.conf)
        telemetry.host_gap_reset()
        try:
            with telemetry.span("fit", epochs=epochs), \
                    flightrec.flight_recorder(model=self):
                for _ in range(epochs):
                    for lst in self.listeners:
                        lst.on_epoch_start(self, self.epoch)
                    pending = []
                    for ds in nn_io.timed_batches(iterator):
                        pending.append(self._fit_batch_async(ds))
                        nn_io.drain(pending)
                    nn_io.drain(pending, force=True)
                    iterator.reset()
                    for lst in self.listeners:
                        lst.on_epoch_end(self, self.epoch)
                    self.epoch += 1
        finally:
            telemetry.host_gap_stop()
        return self

    def _batch_arrays(self, ds: DataSet, lazy_lmask: bool = False,
                      write_back: bool = False):
        """``lazy_lmask``: a missing labels mask stays None (the jitted
        train step builds the all-ones default on device — an eager
        ``jnp.ones`` here would cost a dispatch round-trip per step).
        ``write_back``: store staged device arrays back into ``ds`` so a
        DataSet reused across epochs transfers once (reference
        ``DataSet#migrate``, applied by the fit path only — score/eval
        leave the caller's arrays untouched; call ``ds.migrate()`` there)."""
        features = nn_io.as_device(ds.features, self._dtype, feature=True)
        labels = nn_io.as_device(ds.labels, self._dtype)
        fmask = (nn_io.as_device(ds.features_mask, self._dtype)
                 if ds.features_mask is not None else None)
        if ds.labels_mask is not None:
            lmask = nn_io.as_device(ds.labels_mask, self._dtype)
        elif lazy_lmask:
            lmask = None
        else:
            lmask = jnp.ones((features.shape[0],), self._dtype)
        if write_back:
            ds.features = features
            ds.labels = labels
            if fmask is not None:
                ds.features_mask = fmask
            if ds.labels_mask is not None:
                ds.labels_mask = lmask
        return features, labels, fmask, lmask

    def _fit_batch_async(self, ds: DataSet):
        """One step WITHOUT forcing a host sync: the loss stays a device
        scalar (``score_value`` converts lazily); listeners receive the
        device scalar and only sync when they actually read it (e.g.
        ScoreIterationListener every N prints)."""
        if self.params is None:
            self.init()
        k = int(getattr(ds, "fused_stack", 0) or 0)
        if k > 1:
            return self._fit_fused(ds, k)
        from deeplearning4j_tpu.conf.multilayer import BackpropType

        tbptt = (self.conf.backprop_type is BackpropType.TRUNCATED_BPTT
                 and np.ndim(ds.features) == 3)
        from deeplearning4j_tpu.resilience import faults

        if tbptt:
            # one normalization path shared with ParallelWrapper
            with telemetry.span(telemetry.PHASE_INGEST):
                args = self.tbptt_batch_arrays(ds)
            # same once-per-optimization-step injection site as the
            # standard branch below — tBPTT steps are killable too
            args = (faults.fault_point("train.step", args[0]),
                    ) + tuple(args[1:])
            return self._fit_tbptt(*args)
        with telemetry.span(telemetry.PHASE_INGEST):
            features, labels, fmask, lmask = self._batch_arrays(
                ds, lazy_lmask=True, write_back=True)
        from deeplearning4j_tpu.telemetry import health

        # injection site (raise = preemption/crash, corrupt = poisoned
        # batch feeding the health guards); host-side, outside the jit
        features = faults.fault_point("train.step", features)

        mode = health.graph_mode()
        if self._train_step is None \
                or getattr(self, "_train_step_mode", "") != mode \
                or getattr(self, "_train_step_ktag", "") != self._ktag():
            self._train_step = self._build_train_step()
        gvec = None
        with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
            telemetry.host_gap_close()
            out = self._train_step(
                self.params, self.state, self.opt_state, features, labels,
                fmask, lmask, self.device_iteration(), self.device_epoch(),
                self._base_key)
            (self.params, self.state, self.opt_state, loss,
             new_itc) = out[:5]
            if mode:
                gvec = out[5]
            _sp.set_result(loss)
        with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
            # single device: the step has no collective — once the loss is
            # ready the updated params are too, so this span records ~0
            # (the same convention bench_resnet_profile.py --phases uses)
            _sp.set_result(self.params)
        # the host gap opens AFTER the result-bearing spans exit: under
        # enable(sync=True) they block on the device result, so the gap
        # measures pure host dispatch-loop work with no device overlap
        telemetry.host_gap_open()
        telemetry.record_step("multilayer", int(features.shape[0]))
        self.last_batch_size = int(features.shape[0])
        self._score_dev = loss
        self._score_cache = None
        # increment BEFORE firing listeners: at listener time
        # model.iteration is uniformly "next iteration to run" (tBPTT
        # already works this way), while the arg stays the just-finished
        # iteration's index
        cur = self.iteration
        self.iteration += 1
        self.advance_device_iteration(new_itc)
        if mode:
            health.observe_step(
                self, "multilayer", cur, self.epoch, loss, gvec,
                self._guard_keys, batch=(features, labels),
                rng_seed=int(getattr(self.conf, "seed", 0) or 0))
        if self.listeners:
            with telemetry.span("listeners"):
                for lst in self.listeners:
                    lst.iteration_done(self, cur, self.epoch, loss)
        return loss

    def fit_batch(self, ds: DataSet) -> float:
        """One optimization step on one minibatch, synced (tBPTT: one step
        per segment, reference ``MultiLayerNetwork#doTruncatedBPTT``)."""
        try:
            return float(self._fit_batch_async(ds))
        finally:
            # a standalone step is not a dispatch loop: idle time until
            # the caller's next step must not record as host gap
            telemetry.host_gap_stop()

    def _fit_fused(self, ds: DataSet, k: int):
        """K fused optimization steps from one [K, B, ...] stacked batch
        (``DeviceRingIterator(stack_batches=K)`` built it): one compiled
        ``lax.scan`` dispatch, params/state/opt/iteration donated across
        the K-step boundary, K keyed into the AOT cache so K=1 and K=4
        executables never collide. Listeners fire K times with the
        scan's per-step losses; health guards ride the scan with
        WARN/SKIP staying sync-free and ROLLBACK/HALT resolving at
        super-step granularity."""
        from deeplearning4j_tpu.conf.multilayer import BackpropType
        from deeplearning4j_tpu.resilience import faults
        from deeplearning4j_tpu.telemetry import health

        if self.conf.backprop_type is BackpropType.TRUNCATED_BPTT:
            raise ValueError(
                "fused_steps composes with STANDARD backprop only: a "
                "tBPTT batch already trains as one compiled segment scan")
        with telemetry.span(telemetry.PHASE_INGEST):
            features, labels, fmask, lmask = self._batch_arrays(
                ds, lazy_lmask=True, write_back=True)
        # same once-per-dispatch injection site as the standard branch
        # (raise = preemption mid-super-step; corrupt poisons the stack)
        features = faults.fault_point("train.step", features)
        mode = health.graph_mode()
        ktag = self._ktag()
        if self._fused_scan is None:
            self._fused_scan = {}
        if (k, mode, ktag) not in self._fused_scan:
            # K joins the cache key: a K=1 and a K=4 executable must
            # never collide even though their graph keys match
            self._fused_scan[k, mode, ktag] = aot_cache.wrap(
                jax.jit(self.fused_scan_fn(k, guards=mode),
                        donate_argnums=(0, 1, 2, 7)),
                self._graph_key(),
                f"fused_scan:{k}:d0127{health.cache_tag()}{ktag}")
        gvecs = None
        with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
            telemetry.host_gap_close(k)
            out = self._fused_scan[k, mode, ktag](
                self.params, self.state, self.opt_state, features, labels,
                fmask, lmask, self.device_iteration(), self.device_epoch(),
                self._base_key)
            (self.params, self.state, self.opt_state, new_itc,
             losses) = out[:5]
            if mode:
                gvecs = out[5]
            _sp.set_result(losses)
        with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
            _sp.set_result(self.params)  # single device: ~0 (see above)
        telemetry.host_gap_open()  # post-span: sync mode excludes device
        telemetry.record_step(
            "multilayer", int(features.shape[0]) * int(features.shape[1]),
            steps=k)
        # per-STEP batch size: examples/sec listeners multiply by the
        # per-iteration rate, which counts K iterations per dispatch
        self.last_batch_size = int(features.shape[1])
        self._score_dev = losses[-1]
        self._score_cache = None
        cur = self.iteration
        self.iteration += k
        self.advance_device_iteration(new_itc)
        if mode:
            self._guard_keys = health.bucket_keys(self.params)
            health.observe_fused(
                self, "multilayer", cur, self.epoch, losses, gvecs,
                self._guard_keys, k, batch=(features, labels),
                rng_seed=int(getattr(self.conf, "seed", 0) or 0))
        if self.listeners:
            # K per-step losses from the scan's ys — each a lazy device
            # slice, so listeners that never read a score never sync
            with telemetry.span("listeners"):
                for j in range(k):
                    loss_j = losses[j]
                    for lst in self.listeners:
                        lst.iteration_done(self, cur + j, self.epoch,
                                           loss_j)
        return losses[-1]  # device scalar: the async fit pipeline queues it

    def _tbptt_prepad(self, ds: DataSet) -> DataSet:
        """Variable-length host batches (fresh numpy per batch, NLP
        streams): pad T to a multiple of tbptt_fwd_length in NUMPY (free)
        so the scan jit's cache key quantizes to the segment count instead
        of retracing for every distinct T. Padded steps get zero masks.
        Device-resident / non-multiple recurring batches pass through —
        they compile once per distinct T anyway. Returns a NEW DataSet
        (the caller's arrays are never mutated)."""
        f = ds.features
        if not isinstance(f, np.ndarray) or f.ndim != 3:
            return ds
        seg = int(self.conf.tbptt_fwd_length)
        t = f.shape[1]
        pad = (-t) % seg
        if pad == 0:
            return ds
        # reuse the padded copy across epochs: write_back migrates ITS
        # arrays to device on the first fit, so a reused DataSet still
        # transfers once. Keyed on the IDENTITY of every array the pad
        # consumed — replacing labels/masks invalidates the cache.
        # (In-place writes into the same numpy buffer are not detectable;
        # replace the array to retrain on new data.)
        key = (f, ds.labels, ds.features_mask, ds.labels_mask, seg,
               int(self.conf.tbptt_back_length or seg))
        cached = getattr(ds, "_tbptt_padded", None)
        if cached is not None and len(cached[0]) == len(key) and all(
                a is b for a, b in zip(cached[0], key)):
            return cached[1]
        n = f.shape[0]
        back = min(int(self.conf.tbptt_back_length or seg), seg)
        # back < fwd: insert the padding BEFORE the tail segment's real
        # steps (left-align them) so they land inside the gradient window,
        # not the no-grad state-advance head — masked steps pass RNN state
        # through unchanged, so this is exactly the reference's
        # shorter-tail-slice semantics. back == fwd keeps the plain right
        # pad (window covers the whole segment either way).
        split = t - (t % seg) if back < seg else t

        def pad_t(a, fill=0.0):
            a = np.asarray(a)
            z = np.full((n, pad) + a.shape[2:], fill, a.dtype)
            return np.concatenate([a[:, :split], z, a[:, split:]], axis=1)

        fmask = pad_t(ds.features_mask if ds.features_mask is not None
                      else np.ones((n, t), self._dtype))
        lm = ds.labels_mask
        if lm is not None and np.ndim(lm) == 1:   # per-example -> per-step
            lm = np.asarray(lm)[:, None] * np.ones((n, t), self._dtype)
        lmask = pad_t(lm if lm is not None
                      else np.ones((n, t), self._dtype))
        labels = (pad_t(ds.labels) if np.ndim(ds.labels) == 3
                  else ds.labels)
        padded = DataSet(pad_t(f), labels, features_mask=fmask,
                         labels_mask=lmask)
        try:
            ds._tbptt_padded = (key, padded)
        except AttributeError:
            pass  # exotic immutable containers just re-pad
        return padded

    def tbptt_scan_fn(self, seg: int, back: Optional[int] = None,
                      guards: str = ""):
        """The raw (unjitted) whole-batch tBPTT runner: segments the time
        axis INSIDE the trace and scans the per-segment train step with
        detached carries — ``(params, state, opt, features, labels, fmask,
        lmask, itc, ep, base_key) -> (params, state, opt, new_itc,
        mean_loss)``. Exposed (like ``train_step_fn``) so ParallelWrapper
        can jit it over a mesh with the batch axis sharded — the same
        compiled segment chain, SPMD-partitioned.

        ``back < seg`` (reference ``tbptt_back_length < fwd_length``): the
        first ``seg - back`` steps of each segment only advance the RNN
        state in inference mode — no gradient flows through them (they run
        outside the train step's loss closure) — and the parameter update
        trains on the trailing ``back`` window. Still ONE compiled scan.

        ``guards``: with a health mode set the per-segment guard vectors
        (``telemetry.health``) aggregate elementwise-max across the scan
        and the run returns an extra trailing vector; ``"skip"`` reverts
        each anomalous SEGMENT's update inside the scan body."""
        raw = self.train_step_fn(guards=guards)
        segments, zero_carries, advance, _ = self.tbptt_scan_parts(seg,
                                                                   back)

        def run(params, state, opt, features, labels, fmask, lmask,
                itc, ep, base_key):
            from deeplearning4j_tpu.telemetry import health

            segs = tuple(segments(a)
                         for a in (features, labels, fmask, lmask))
            carries = zero_carries(features)

            def body(carry, xs):
                params, state, opt, carries, itc = carry
                f_s, l_s, fm_s, lm_s = xs
                f_s, l_s, fm_s, lm_s, carries = advance(
                    params, state, carries, f_s, l_s, fm_s, lm_s)
                it, rng = nn_io.step_scalars(itc, base_key)
                out = raw(params, state, opt, f_s, l_s, fm_s, lm_s, it,
                          ep, rng, carries)
                if guards:
                    params, state, opt, loss, carries, vec = out
                    return (params, state, opt, carries, itc + 1), (loss,
                                                                    vec)
                params, state, opt, loss, carries = out
                return (params, state, opt, carries, itc + 1), loss

            (params, state, opt, carries, itc), ys = jax.lax.scan(
                body, (params, state, opt, carries, itc), segs)
            if guards:
                losses, vecs = ys
                return (params, state, opt, itc, jnp.mean(losses),
                        health.combine(vecs))
            return params, state, opt, itc, jnp.mean(ys)

        return run

    def tbptt_scan_parts(self, seg: int, back: Optional[int] = None):
        """Shared tBPTT scan plumbing — ``(segments, zero_carries, advance,
        cut)`` — used by :meth:`tbptt_scan_fn` and ParallelWrapper's
        compressed-gradient scan:

        - ``segments(arr)``: [B, T, ...] -> [n_seg, B, seg, ...] in-trace
          (tail zero-padded; with ``back < seg`` the tail pad goes BEFORE
          its real steps so they stay inside the gradient window).
        - ``zero_carries(features)``: per-layer zero RNN carries, vma-
          anchored to the batch so the scan carry is shard_map-legal.
        - ``advance(params, state, carries, f, l, fm, lm)``: consume the
          segment's no-grad head (``cut`` steps, inference mode) and
          return the trimmed gradient window + advanced carries."""
        back = seg if back is None else min(int(back), seg)
        cut = seg - back
        last = len(self.conf.layers) - 1
        cdt = self._cdtype or self._dtype

        def segments(arr):
            # INSIDE the jit: shapes are static under trace, so the
            # segmentation costs zero extra dispatches. n_seg derives
            # from the traced shape (NOT closed over: a different T
            # retraces with its own count).
            arr = jnp.asarray(arr)
            t = arr.shape[1]
            ns = -(-t // seg)
            pad = ns * seg - t
            if pad and cut:
                z = jnp.zeros(arr.shape[:1] + (pad,) + arr.shape[2:],
                              arr.dtype)
                arr = jnp.concatenate(
                    [arr[:, :t - (t % seg)], z, arr[:, t - (t % seg):]],
                    axis=1)
            else:
                arr = _pad_time(arr, ns * seg)
            shaped = arr.reshape(arr.shape[0], ns, seg,
                                 *arr.shape[2:])
            return jnp.moveaxis(shaped, 1, 0)

        def zero_carries(features):
            # anchor the zero carries to the features: under shard_map the
            # batch is varied over the mesh axis, and a bare jnp.zeros is
            # not — lax.scan then rejects the carry (vma mismatch). The
            # +0*sum() is free under jit and a no-op outside shard_map.
            anchor = jnp.sum(features[:1, :1]) * 0
            carries = {str(i): layer.zero_carry(features.shape[0], cdt)
                       for i, layer in enumerate(self.conf.layers)
                       if getattr(layer, "has_carry", False)
                       and not _is_go_backwards_layer(layer)}
            return jax.tree_util.tree_map(
                lambda z: z + anchor.astype(z.dtype), carries)

        def advance(params, state, carries, f_s, l_s, fm_s, lm_s):
            if cut:
                # state-advance over the head of the segment: the params
                # used here are scan-carry constants with respect to the
                # train step's loss argument, so no gradient reaches
                # these timesteps — reference truncates the backward
                # pass at back_length
                fwd_p, f_c, fm_c = self._fwd_cast(
                    params, self._dequant(f_s[:, :cut]), fm_s[:, :cut])
                _, _, carries = self._forward(
                    fwd_p, state, f_c, train=False, rng=None,
                    fmask=fm_c, upto=last, carries=carries)
                f_s, l_s, fm_s, lm_s = (a[:, cut:] for a in
                                        (f_s, l_s, fm_s, lm_s))
            return f_s, l_s, fm_s, lm_s, carries

        return segments, zero_carries, advance, cut

    def fused_scan_fn(self, k: int, guards: str = ""):
        """The raw (unjitted) K-step fused runner (round 11, ROADMAP open
        item 5): ``lax.scan`` the standard train step over a
        device-resident stack of K batches — ``(params, state, opt,
        features[K,B,...], labels[K,...], fmask[K,...]|None,
        lmask[K,...]|None, itc, ep, base_key) -> (params, state, opt,
        new_itc, losses[K][, vecs[K,G]])`` — so K optimization steps cost
        ONE host dispatch. The scan body is exactly the single-step
        ``train_step_fn`` fed the same in-jit per-step scalars
        (``nn_io.step_scalars`` on the carried iteration counter), so a
        K-step fused run is bit-identical to K standard steps on the
        same batch stream; the tBPTT segment scan is the template
        (``tbptt_scan_fn``), with batches instead of segments as the
        scanned axis and no carries.

        ``guards``: with a health mode the per-step guard vectors ride
        the scan's ys and the run returns the [K, G] STACK (not the max)
        so the host can surface the offending step index; ``"skip"``
        reverts each anomalous step's update inside the scan body.
        Exposed (like ``tbptt_scan_fn``) so ParallelWrapper can jit it
        over a mesh with the per-step batch axis sharded."""
        raw = self.train_step_fn(guards=guards)
        dtype = self._dtype

        def run(params, state, opt, features, labels, fmask, lmask,
                itc, ep, base_key):
            def body(carry, xs):
                params, state, opt, itc = carry
                f_s, l_s, fm_s, lm_s = xs
                if lm_s is None:
                    # same in-jit default as the standard step builder
                    lm_s = jnp.ones((f_s.shape[0],), dtype)
                it, rng = nn_io.step_scalars(itc, base_key)
                out = raw(params, state, opt, f_s, l_s, fm_s, lm_s, it,
                          ep, rng)
                if guards:
                    params, state, opt, loss, vec = out
                    return (params, state, opt, itc + 1), (loss, vec)
                params, state, opt, loss = out
                return (params, state, opt, itc + 1), loss

            (params, state, opt, itc), ys = jax.lax.scan(
                body, (params, state, opt, itc),
                (features, labels, fmask, lmask))
            if guards:
                losses, vecs = ys
                return params, state, opt, itc, losses, vecs
            return params, state, opt, itc, ys

        return run

    def tbptt_batch_arrays(self, ds: DataSet):
        """Stage one tBPTT batch fully normalized for ``tbptt_scan_fn``:
        prepadded time axis, per-timestep labels validated, all-ones
        default masks, 1-D labels mask expanded per-timestep. Used by
        ParallelWrapper to feed the sharded scan runner the exact arrays
        the single-device path trains on."""
        # go_backwards layers train under tBPTT with PER-SEGMENT RESET
        # (_is_go_backwards_layer; the round-3 refusal closed in round
        # 4) — only rnn_time_step streaming still refuses them.
        ds = self._tbptt_prepad(ds)
        features, labels, fmask, lmask = self._batch_arrays(
            ds, lazy_lmask=True, write_back=True)
        if labels.ndim != 3:
            raise ValueError(
                "truncated BPTT needs per-timestep labels [batch, time, "
                f"nOut], got shape {tuple(labels.shape)} (reference tBPTT "
                "operates on sequence labels; use STANDARD backprop for "
                "sequence-level classification heads)")
        n, total_t = features.shape[0], features.shape[1]
        if fmask is None:
            fmask = np.ones((n, total_t), self._dtype)
        if lmask is None:
            lmask = np.ones((n, total_t), self._dtype)
        elif lmask.ndim == 1:
            ones_t = (np.ones if isinstance(lmask, np.ndarray)
                      else jnp.ones)((n, total_t), self._dtype)
            lmask = lmask[:, None] * ones_t
        return features, labels, fmask, lmask

    def _fit_tbptt_scan(self, features, labels, fmask, lmask, seg, back):
        from deeplearning4j_tpu.telemetry import health

        mode = health.graph_mode()
        n_seg = -(-int(features.shape[1]) // seg)
        # cache keyed by (seg, back, health mode): a conf.tbptt_*_length
        # (or guard-mode) change between fits must not silently reuse a
        # closure compiled for the old configuration
        ktag = self._ktag()
        if self._tbptt_scan is None:
            self._tbptt_scan = {}
        if (seg, back, mode, ktag) not in self._tbptt_scan:
            self._tbptt_scan[seg, back, mode, ktag] = aot_cache.wrap(
                jax.jit(self.tbptt_scan_fn(seg, back, guards=mode),
                        donate_argnums=(0, 1, 2)),
                self._graph_key(),
                f"tbptt_scan:{seg}:{back}:d012{health.cache_tag()}{ktag}")
        gvec = None
        with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
            out = self._tbptt_scan[seg, back, mode, ktag](
                self.params, self.state, self.opt_state, features, labels,
                fmask, lmask, self.device_iteration(), self.device_epoch(),
                self._base_key)
            (self.params, self.state, self.opt_state, new_itc,
             mean_loss) = out[:5]
            if mode:
                gvec = out[5]
            _sp.set_result(mean_loss)
        telemetry.record_step("multilayer", int(features.shape[0]))
        self.iteration += n_seg
        self.advance_device_iteration(new_itc)
        self.last_batch_size = int(features.shape[0])
        self._score_dev = mean_loss
        self._score_cache = None
        if mode:
            self._guard_keys = health.bucket_keys(self.params)
            health.observe_step(
                self, "multilayer", self.iteration - 1, self.epoch,
                mean_loss, gvec, self._guard_keys,
                batch=(features, labels),
                rng_seed=int(getattr(self.conf, "seed", 0) or 0))
        if self.listeners:
            with telemetry.span("listeners"):
                for lst in self.listeners:
                    # one batch-level call, arg = last segment's
                    # iteration index (same contract as the
                    # segment-loop path)
                    lst.iteration_done(self, self.iteration - 1,
                                       self.epoch, mean_loss)
        return mean_loss  # device scalar: the async fit pipeline queues it

    def _fit_tbptt(self, features, labels, fmask, lmask) -> float:
        """Truncated BPTT: slice the time axis into segments of
        ``tbptt_fwd_length``, one parameter update per segment, RNN state
        carried (detached) between segments; when ``tbptt_back_length <
        fwd_length`` the head of each segment advances state without
        gradients. The WHOLE chain is one compiled ``lax.scan`` either way
        (round 2: the back<fwd Python segment loop became part of the scan
        body). The tail segment is zero-padded with a 0 mask so every
        segment has the same (compiled-once) shape. Inputs are
        pre-normalized by ``tbptt_batch_arrays`` (the single
        validation/defaulting path, shared with ParallelWrapper)."""
        seg = int(self.conf.tbptt_fwd_length)
        back = int(self.conf.tbptt_back_length or seg)
        return self._fit_tbptt_scan(features, labels, fmask, lmask, seg,
                                    min(back, seg))

    # --- stateful RNN inference (reference rnnTimeStep API) -----------------
    def rnn_time_step(self, x, fmask=None):
        """Streaming inference: feed a segment [batch, t, f], get outputs
        with RNN state persisted across calls (reference
        ``MultiLayerNetwork#rnnTimeStep``)."""
        if self.params is None:
            self.init()
        for i, layer in enumerate(self.conf.layers):
            nn_io.check_streaming_safe(layer, f"layer {i}")
        if self._rnn_step_fn is None:
            self._rnn_step_fn = self._build_rnn_step_fn()
        x = nn_io.as_device(x, self._dtype, feature=True)
        if x.ndim == 2:  # single timestep [batch, f]
            x = x[:, None, :]
        n = x.shape[0]
        if self._rnn_carries is None:
            self._rnn_carries = {
                str(i): layer.zero_carry(n, self._cdtype or self._dtype)
                for i, layer in enumerate(self.conf.layers)
                if getattr(layer, "has_carry", False)}
        fmask = (None if fmask is None
                 else jnp.asarray(np.asarray(fmask), self._dtype))
        y, self._rnn_carries = self._rnn_step_fn(
            self.params, self.state, self._rnn_carries, x, fmask)
        return y

    def rnn_clear_previous_state(self):
        """Reference ``#rnnClearPreviousState``."""
        self._rnn_carries = None

    def rnn_get_previous_state(self, layer_idx: int):
        """Reference ``#rnnGetPreviousState(layer)``. Returned state is in
        the storage dtype (internal carries live in the compute dtype)."""
        if self._rnn_carries is None:
            return None
        c = self._rnn_carries.get(str(layer_idx))
        if c is None or self._cdtype is None:
            return c
        return nn_io.cast_floats(c, self._dtype)

    def rnn_set_previous_state(self, layer_idx: int, state: dict):
        """Reference ``#rnnSetPreviousState(layer, state)``."""
        if self._rnn_carries is None:
            self._rnn_carries = {}
        self._rnn_carries[str(layer_idx)] = {
            k: jnp.asarray(v, self._cdtype or self._dtype)
            for k, v in state.items()}

    def feed_forward(self, x, fmask=None):
        """Per-layer activations, eval mode (reference
        ``MultiLayerNetwork#feedForward`` returning one activation per
        layer, input excluded). Powers the StatsListener activation
        histograms."""
        if self.params is None:
            self.init()
        if getattr(self, "_feed_forward_fn", None) is None:
            # one pass collecting every layer output (same walk as
            # _forward, kept inline so each activation is captured)
            def ff(params, state, x, fmask):
                params, x, fmask = self._fwd_cast(params, self._dequant(x),
                                                  fmask, full=True)
                acts = []
                for i, layer in enumerate(self.conf.layers):
                    p = params.get(str(i), {})
                    s = state.get(str(i), {})
                    kw = ({"mask": fmask}
                          if getattr(layer, "uses_mask", False) else {})
                    x, _ = layer.forward(p, s, x, train=False, rng=None,
                                         **kw)
                    fmask = nn_io.propagate_mask(fmask, x, layer)
                    acts.append(x.astype(self._dtype))
                return acts

            self._feed_forward_fn = jax.jit(ff)
        x = nn_io.as_device(x, self._dtype, feature=True)
        if fmask is not None:
            fmask = nn_io.as_device(fmask, self._dtype)
        return list(self._feed_forward_fn(self.params, self.state, x,
                                          fmask))

    # --- inference / scoring ----------------------------------------------
    def output(self, x, batch_size: Optional[int] = None, fmask=None):
        """Forward pass, eval mode (reference ``#output``)."""
        if self.params is None:
            self.init()
        if self._output_fn is None \
                or getattr(self, "_output_ktag", "") != self._ktag():
            self._output_fn = self._build_output_fn()
        # jax.Arrays pass through (keeps committed shardings); uint8
        # features stay uint8 and dequantize inside the jit, matching
        # training
        x = nn_io.as_device(x, self._dtype, feature=True)
        if fmask is not None:
            fmask = nn_io.as_device(fmask, self._dtype)
        return self._output_fn(self.params, self.state, x, fmask)

    def score(self, ds: DataSet = None) -> float:
        """Loss on a DataSet without updating (reference ``#score``), or the
        last training score when called with no args."""
        if ds is None:
            return self.score_value
        if self.params is None:
            self.init()
        if self._score_fn is None \
                or getattr(self, "_score_ktag", "") != self._ktag():
            self._score_fn = self._build_score_fn()
        features, labels, fmask, lmask = self._batch_arrays(ds)
        return float(self._score_fn(self.params, self.state, features, labels,
                                    fmask, lmask))

    def evaluate(self, iterator, evaluation: Optional[Evaluation] = None):
        """Reference ``#evaluate(DataSetIterator)`` -> Evaluation."""
        ev = evaluation if evaluation is not None else Evaluation()
        iterator = _as_iterator(iterator)
        for ds in iterator:
            out = self.output(ds.features, fmask=ds.features_mask)
            ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        iterator.reset()
        return ev

    # --- gradients (for gradient checks / ParallelWrapper) -----------------
    def compute_gradient_and_score(self, ds: DataSet):
        """(grads pytree, score) without updating params — the hook the
        gradient-check oracle and the gradient-sharing trainer use
        (reference ``#computeGradientAndScore``)."""
        if self.params is None:
            self.init()
        features, labels, fmask, lmask = self._batch_arrays(ds)

        def loss_fn(p):
            return self._loss(p, self.state, features, labels, fmask, lmask,
                              rng=None)

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(self.params)
        return grads, float(loss)

    # --- params vector (serializer parity) ---------------------------------
    def params_flat(self) -> np.ndarray:
        """The ONE contiguous params vector (reference ``#params()``)."""
        return params_util.flatten_params(self.conf, self.params)

    def set_params_flat(self, flat: np.ndarray):
        self.params = params_util.unflatten_params(self.conf, flat, self.params)
        return self

    def num_params(self) -> int:
        return int(self.params_flat().size)

    def clone(self) -> "MultiLayerNetwork":
        """Config + params copy (reference ``#clone``)."""
        other = MultiLayerNetwork(self.conf)
        if self.params is not None:
            other.init()
            # true copies: the train step donates its input buffers, so
            # shared references would be invalidated by the next fit
            other.params = jax.tree_util.tree_map(jnp.copy, self.params)
            other.state = jax.tree_util.tree_map(jnp.copy, self.state)
            other.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
        return other

    def summary(self) -> str:
        """Layer table (reference ``#summary``)."""
        types = self.conf.input_types()
        lines = ["=" * 70,
                 f"{'idx':<4} {'layer':<30} {'output':<20} {'params':>10}",
                 "-" * 70]
        total = 0
        for i, (layer, itype) in enumerate(zip(self.conf.layers, types)):
            out_t = layer.output_type(itype)
            n = 0
            if self.params and str(i) in self.params:
                n = sum(int(np.prod(p.shape)) for p in self.params[str(i)].values())
            total += n
            lines.append(f"{i:<4} {type(layer).__name__:<30} "
                         f"{_fmt_type(out_t):<20} {n:>10,}")
        lines += ["-" * 70, f"Total params: {total:,}", "=" * 70]
        return "\n".join(lines)


def _pad_time(arr, seg: int):
    """Zero-pad [batch, t, ...] (or [batch, t]) to t == seg on axis 1.
    numpy stays numpy (host masks stage with the step call); device arrays
    pad on device."""
    t = arr.shape[1]
    if t == seg:
        return arr
    width = [(0, 0), (0, seg - t)] + [(0, 0)] * (arr.ndim - 2)
    return (np.pad if isinstance(arr, np.ndarray) else jnp.pad)(arr, width)


def _fmt_type(t) -> str:
    from deeplearning4j_tpu.conf import inputs as it

    if isinstance(t, it.Convolutional):
        return f"[{t.height},{t.width},{t.channels}]"
    if isinstance(t, it.Recurrent):
        return f"[t={t.timesteps},{t.size}]"
    if isinstance(t, (it.FeedForward,)):
        return f"[{t.size}]"
    return str(t)
