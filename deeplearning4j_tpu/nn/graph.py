"""ComputationGraph — DAG model runtime.

Reference: ``org.deeplearning4j.nn.graph.ComputationGraph`` (~5k LoC):
multi-input/multi-output DAG of GraphVertex, cached topological order,
``fit``/``output``/``score``/``evaluate``, flattened params.

TPU-native inversion (SURVEY.md §3.2): the reference's hot loop — walk the
topo order calling ``GraphVertex#doForward`` then reverse for ``doBackward``,
each vertex issuing per-op JNI calls — becomes ONE jitted XLA program; the
topo walk happens once at trace time and XLA fuses across vertex boundaries.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.conf.graph import (
    ComputationGraphConfiguration,
    LayerVertex,
)
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import DataSetIterator
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.nn import io as nn_io
from deeplearning4j_tpu.optimize import aot_cache, solver
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.util import params as params_util


def _is_go_backwards(vertex) -> bool:
    """True for vertices whose (possibly wrapped) layer processes time
    REVERSED (Keras go_backwards). Under tBPTT these get PER-SEGMENT
    RESET semantics: the reversed scan's carry would have to arrive from
    the FUTURE segment, so each segment is treated as an independent
    sequence for the reversed direction (the same contract Bidirectional
    wrappers — has_carry=False — already follow; single-segment training
    is exactly standard BPTT, pinned in tests/test_graph_tbptt.py)."""
    return nn_io.contains_go_backwards(getattr(vertex, "layer", None))


def _as_multi(ds) -> MultiDataSet:
    """DataSet -> single-input/single-output MultiDataSet (reference
    ``ComputationGraph#fit(DataSet)`` convenience overload)."""
    if isinstance(ds, MultiDataSet):
        return ds
    return MultiDataSet(
        features=[ds.features], labels=[ds.labels],
        features_masks=[ds.features_mask] if ds.features_mask is not None else None,
        labels_masks=[ds.labels_mask] if ds.labels_mask is not None else None)


class ComputationGraph(nn_io.LazyScoreMixin):
    """DAG network (reference ``ComputationGraph``)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: Optional[Dict[str, dict]] = None
        self.state: Dict[str, dict] = {}
        self.opt_state: Dict[str, dict] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[TrainingListener] = []
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
        self._topo = conf.topo_order()
        self._vmap = conf.vertex_map()
        # a vertex whose layer is tied to another's parameters (a language
        # model's head that IS its embedding): name -> the vertex it reads
        self._tied = {
            name: spec.vertex.layer.tied_to
            for name, spec in self._vmap.items()
            if getattr(getattr(spec.vertex, "layer", None), "tied_to", "")}
        # feature-mask propagation: see nn_io.propagate_mask (reference
        # ComputationGraph feedForwardMaskArrays) — decided per vertex from
        # TRACED output shapes in _forward, so variable-length configs
        # (unknown conf timesteps) keep/resize/terminate correctly too

    # --- lifecycle ---------------------------------------------------------
    def init(self) -> "ComputationGraph":
        key = self._base_key
        types = self.conf.vertex_output_types()
        self.params, self.state, self.opt_state = {}, {}, {}
        for i, name in enumerate(self._topo):
            spec = self._vmap[name]
            in_types = [self._input_type_of(src, types) for src in spec.inputs]
            p = spec.vertex.init(jax.random.fold_in(key, i), in_types,
                                 self._dtype)
            if p:
                self.params[name] = p
            s = spec.vertex.init_state(in_types, self._dtype)
            if s:
                self.state[name] = s
        for k, vp in self.params.items():
            upd = self._updater_for(k)
            self.opt_state[k] = {pk: upd.init_state(pv) for pk, pv in vp.items()}
        return self

    def _input_type_of(self, src: str, types: Dict[str, object]):
        return types[src]

    def _params_of(self, params, name: str):
        """The parameters vertex ``name`` computes with: its own, or those
        of the vertex its layer is tied to (an entry under a tied vertex's
        own name is ``_fwd_cast``'s: the master it keeps for the head)."""
        return params.get(name) or params.get(self._tied.get(name, name), {})

    def set_listeners(self, *listeners: TrainingListener):
        self.listeners = list(listeners)
        return self

    def _updater_for(self, name: str):
        v = self._vmap[name].vertex
        layer = getattr(v, "layer", None)
        return (getattr(layer, "updater", None) if layer is not None else None) \
            or self.conf.updater

    def _graph_key(self) -> str:
        """AOT-cache graph signature (optimize.aot_cache): content-keyed
        on the conf when its repr is deterministic, so clones and fresh
        instances of the same graph reuse compiled step executables."""
        if getattr(self, "_graph_key_cache", None) is None:
            self._graph_key_cache = "cg:" + aot_cache.graph_signature(
                self.conf, fallback=self)
        return self._graph_key_cache

    def _ktag(self) -> str:
        """Kernel-registry step-key tokens (``kernels.cache_tag``;
        empty unless ``conf.use_kernels`` — see MultiLayerNetwork._ktag
        for the re-key contract)."""
        if not getattr(self.conf, "use_kernels", False):
            return ""
        from deeplearning4j_tpu import kernels

        return kernels.cache_tag(self.conf)

    # --- functional core ---------------------------------------------------
    def _forward(self, params, state, inputs: Sequence, train: bool, rng,
                 skip=frozenset(), fmasks=None, carries=None):
        """Pure DAG forward. ``inputs`` aligned with conf.network_inputs.
        Returns (activations dict incl. every vertex, new_state,
        new_carries). ``skip``: vertex names left unevaluated (the loss path
        skips output vertices — their fused activation+loss is computed by
        score()). ``fmasks``: per-input [batch, time] feature masks (or
        None), propagated along sequence-shaped paths and handed to
        mask-consuming layers (reference ``feedForwardMaskArrays``).
        ``carries``: {vertex name: carry} recurrent state threaded across
        tBPTT segments (reference ``rnnUpdateStateWithTBPTTState``);
        None = every RNN vertex starts from its zero carry."""
        acts: Dict[str, object] = dict(zip(self.conf.network_inputs, inputs))
        masks: Dict[str, object] = {}
        if fmasks is not None:
            masks.update(zip(self.conf.network_inputs, fmasks))
        new_state, new_carries = {}, {}
        for i, name in enumerate(self._topo):
            if name in skip:
                continue
            spec = self._vmap[name]
            xs = [acts[src] for src in spec.inputs]
            in_masks = [masks.get(src) for src in spec.inputs
                        if masks.get(src) is not None]
            # multiple masked inputs (merge vertices): AND the masks —
            # a step is valid only where every input is (reference
            # combines per-input masks the same way)
            mask = None
            for m in in_masks:
                mask = m if mask is None else jnp.minimum(mask, m)
            p = self._params_of(params, name)
            s = state.get(name, {})
            vrng = jax.random.fold_in(rng, i) if rng is not None else None
            kw = ({"mask": mask} if mask is not None
                  and isinstance(spec.vertex, LayerVertex) else {})
            routed = None
            # one scope a vertex, its name: what the device's time is
            # filed under (``telemetry.device_time``); the backward pass
            # carries it as ``transpose(jvp(<name>))``
            with jax.named_scope(name):
                if getattr(self.conf, "use_kernels", False) \
                        and (carries is None
                             or not getattr(spec.vertex, "has_carry", False)):
                    # kernel-registry routing (conf.use_kernels): a TUNED
                    # Pallas kernel covering the wrapped layer's concrete
                    # shapes replaces the vertex forward; None = stock XLA
                    from deeplearning4j_tpu import kernels as _kernels

                    routed = _kernels.maybe_vertex_forward(
                        spec.vertex, p, s, xs, train=train, rng=vrng, **kw)
                if routed is not None:
                    y, s2 = routed
                elif carries is not None \
                        and getattr(spec.vertex, "has_carry", False) \
                        and not _is_go_backwards(spec.vertex):
                    c = carries.get(name)
                    if c is None:
                        c = spec.vertex.zero_carry(xs[0].shape[0],
                                                   xs[0].dtype)
                    y, c2 = spec.vertex.forward_with_carry(
                        p, c, xs, train=train, rng=vrng, **kw)
                    new_carries[name] = c2
                    s2 = s
                else:
                    y, s2 = spec.vertex.forward(p, s, xs, train=train,
                                                rng=vrng, **kw)
            acts[name] = y
            masks[name] = nn_io.propagate_mask(mask, y, spec.vertex)
            if name in state:
                new_state[name] = s2
        return acts, new_state, new_carries

    def _output_specs(self):
        specs = self.conf.output_vertices()
        for s in specs:
            if not (hasattr(s.vertex, "score") and getattr(s.vertex, "is_output",
                                                           lambda: False)()):
                raise TypeError(
                    f"output vertex {s.name!r} is not an output layer "
                    "(reference: outputs must be IOutputLayer vertices)")
        return specs

    def _fwd_cast(self, params, features: Sequence, full: bool = False):
        """Mixed-precision cast: params/features to the compute dtype.
        ``full=True`` = the pass runs through the output vertices — their
        params stay f32 masters so logits land in the storage dtype.
        No-op without a policy."""
        if self._cdtype is None:
            return params, tuple(features)
        cast = nn_io.cast_floats(params, self._cdtype)
        if full:
            for name in self.conf.network_outputs:
                master = self._params_of(params, name)
                if master:
                    cast[name] = master
        return cast, nn_io.cast_floats(tuple(features), self._cdtype)

    def _loss(self, params, state, features: Sequence, labels: Sequence,
              fmasks: Sequence, lmasks: Sequence, rng, train=True,
              carries=None):
        # the scopes (``cast``, one a vertex in ``_forward``, ``loss``) are
        # what ``telemetry.device_time`` files the device's time under
        with jax.named_scope("cast"):
            features = tuple(self._dequant(f, i)
                             for i, f in enumerate(features))
            out_specs = self._output_specs()
            fwd_params, features = self._fwd_cast(params, features)
            if self._cdtype is not None and carries is not None:
                carries = nn_io.cast_floats(carries, self._cdtype)
        acts, new_state, new_carries = self._forward(
            fwd_params, state, features, train, rng,
            skip={s.name for s in out_specs}, fmasks=fmasks,
            carries=carries)
        with jax.named_scope("loss"):
            loss = 0.0
            for i, spec in enumerate(out_specs):
                # output-vertex activation + loss in the storage dtype on
                # the f32 master params (bf16 log-softmax loses gradient
                # bits)
                x = acts[spec.inputs[0]].astype(self._dtype)
                loss = loss + spec.vertex.score(
                    self._params_of(params, spec.name), x, labels[i],
                    lmasks[i])
            loss = loss + self._regularization_score(params)
            # auxiliary TRAIN-time loss terms layers stash in their state
            # (MoE load-balance); eval scores must not pick up the stale
            # last-training-step value
            if train:
                from deeplearning4j_tpu.conf.layers_moe import sum_aux_losses

                loss = loss + sum_aux_losses(new_state, self._dtype)
        return loss, (new_state, new_carries)

    def _regularization_score(self, params):
        total = 0.0
        for name, vparams in params.items():
            v = self._vmap[name].vertex
            conf = getattr(v, "layer", None) or v
            reg_keys = set(v.regularized_param_keys())
            for k, p in vparams.items():
                regs = (getattr(conf, "regularization", ()) if k in reg_keys
                        else getattr(conf, "regularization_bias", ()))
                for r in regs or ():
                    total = total + r.score_term(p)
        return total

    def _apply_updaters(self, params, opt_state, grads, it, ep):
        """Every vertex's updater over its gradients, under the scope
        ``updater`` (``telemetry.device_time``): ``(new_params,
        new_opt_state)``."""
        new_params, new_opt = {}, {}
        with jax.named_scope("updater"):
            for k in params:
                v = self._vmap[k].vertex
                layer_conf = getattr(v, "layer", None) or v
                upd = self._updater_for(k)
                lr = upd.current_lr(it, ep)
                g = solver.normalize_layer_gradients(layer_conf, grads[k])
                new_params[k], new_opt[k] = solver.apply_updater_to_layer(
                    layer_conf, upd, params[k], g, opt_state[k], lr, it, ep)
        return new_params, new_opt

    def train_step_fn(self, guards: str = ""):
        """Raw (unjitted) pure train step for parallel wrappers (stage-7).

        ``guards`` (``telemetry.health.graph_mode()``): ``"observe"``
        appends the packed health guard vector; ``"skip"`` additionally
        applies the in-graph SKIP_STEP select (see MultiLayerNetwork
        ``train_step_fn`` — identical contract)."""
        from deeplearning4j_tpu.telemetry import health

        def step(params, state, opt_state, features, labels, fmasks,
                 lmasks, it, ep, rng, carries=None):
            def loss_fn(p):
                return self._loss(p, state, features, labels, fmasks,
                                  lmasks, rng, carries=carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updaters(
                params, opt_state, grads, it, ep)
            if carries is not None:
                # tBPTT: the next segment resumes from this segment's
                # final RNN state, detached (gradients do not flow across
                # segments — reference BackpropType.TruncatedBPTT)
                new_carries = jax.lax.stop_gradient(new_carries)
            if guards:
                with jax.named_scope("guards"):
                    vec = health.guard_vector(loss, grads, params=params,
                                              new_params=new_params)
                    if guards == "skip":
                        if carries is None:
                            (new_params, new_state,
                             new_opt) = health.apply_skip(
                                vec, (new_params, new_state, new_opt),
                                (params, state, opt_state))
                        else:
                            (new_params, new_state, new_opt,
                             new_carries) = health.apply_skip(
                                vec,
                                (new_params, new_state, new_opt,
                                 new_carries),
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
        labels, fmasks, lmasks, rng) -> (loss, new_state, grads).
        ParallelWrapper's gradient-exchange hook point (SURVEY.md §3.4).
        With ``carries`` (a tBPTT segment) the return gains detached
        ``new_carries``."""

        def gfn(params, state, features, labels, fmasks, lmasks, rng,
                carries=None):
            def loss_fn(p):
                return self._loss(p, state, features, labels, fmasks,
                                  lmasks, rng, carries=carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if carries is None:
                return loss, new_state, grads
            return loss, new_state, grads, jax.lax.stop_gradient(new_carries)

        return gfn

    def apply_updates_fn(self):
        """Updater half: (params, opt_state, grads, it, ep) ->
        (new_params, new_opt_state)."""

        return self._apply_updaters

    # --- training ----------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1,
            fused_steps: Optional[int] = None):
        """Train (reference ``ComputationGraph#fit`` overloads:
        MultiDataSetIterator / DataSetIterator / (MultiData)Set /
        (features, labels) arrays).

        ``fused_steps=K`` (round 11): K optimization steps per compiled
        dispatch via the ``lax.scan`` fused runner, fed by a K-stacking
        ``DeviceRingIterator`` — same contract as
        ``MultiLayerNetwork.fit`` (bit-identical to K=1, K per-step
        losses to listeners, STANDARD backprop only)."""
        if self.params is None:
            self.init()
        if isinstance(data, (DataSet, MultiDataSet)):
            batches = [data]
            reset = lambda: None  # noqa: E731
        elif isinstance(data, DataSetIterator) or hasattr(data, "reset"):
            batches = data
            reset = data.reset
        elif labels is not None:
            f = data if isinstance(data, (list, tuple)) else [data]
            l = labels if isinstance(labels, (list, tuple)) else [labels]
            batches = [MultiDataSet(features=list(f), labels=list(l))]
            reset = lambda: None  # noqa: E731
        else:
            raise TypeError(f"cannot fit from {type(data)}")
        if int(fused_steps or 0) > 1:
            from deeplearning4j_tpu.nn.multilayer import _wrap_fused

            if isinstance(batches, list):
                # single (Multi)DataSet / array inputs go through the
                # same wrap so the tBPTT refusal (and K semantics) match
                # MultiLayerNetwork.fit exactly
                from deeplearning4j_tpu.datasets.iterators import (
                    ListDataSetIterator,
                )

                batches = ListDataSetIterator(batches)
            batches = _wrap_fused(batches, fused_steps, self.conf)
            reset = batches.reset
        from deeplearning4j_tpu.telemetry import flightrec

        telemetry.host_gap_reset()
        try:
            with telemetry.span("fit", epochs=epochs), \
                    flightrec.flight_recorder(model=self):
                for _ in range(epochs):
                    for lst in self.listeners:
                        lst.on_epoch_start(self, self.epoch)
                    pending = []
                    for ds in nn_io.timed_batches(batches):
                        pending.append(self._fit_batch_async(ds))
                        nn_io.drain(pending)
                    nn_io.drain(pending, force=True)
                    reset()
                    for lst in self.listeners:
                        lst.on_epoch_end(self, self.epoch)
                    self.epoch += 1
        finally:
            telemetry.host_gap_stop()
        return self

    def _dequant(self, x, idx: int = 0):
        scale = (nn_io.image_input(self.conf.input_types[idx])
                 if idx < len(self.conf.input_types) else True)
        return nn_io.dequant(x, self._cdtype or self._dtype, scale=scale)

    def _prep_batch(self, ds, lazy_lmasks: bool = False,
                    write_back: bool = False):
        """``lazy_lmasks``: missing masks stay None (the jitted step builds
        all-ones defaults on device — eager ``jnp.ones`` would cost a
        dispatch round-trip per step). ``write_back``: store staged device
        arrays back into the container so a DataSet reused across epochs
        transfers once (reference ``DataSet#migrate``, applied by the fit
        path only — score/eval leave the caller's arrays untouched)."""
        mds = _as_multi(ds)
        # uint8 features transfer as uint8 and dequantize inside the jit;
        # already-on-device arrays pass through without a host round-trip
        features = tuple(nn_io.as_device(f, self._dtype, feature=True)
                         for f in mds.features)
        labels = tuple(nn_io.as_device(l, self._dtype)
                       for l in mds.labels)
        n_out = len(labels)
        fmasks = tuple(
            nn_io.as_device(m, self._dtype) if m is not None else None
            for m in (mds.features_masks if mds.features_masks is not None
                      else (None,) * len(features)))
        masks = (mds.labels_masks if mds.labels_masks is not None
                 else (None,) * n_out)
        # as_device passes an already-on-device mask through (the
        # write-back below stores device masks; re-staging them would pull
        # device->host and re-upload per step)
        lmasks = tuple(
            nn_io.as_device(m, self._dtype) if m is not None
            else (None if lazy_lmasks
                  else jnp.ones((labels[i].shape[0],), self._dtype))
            for i, m in enumerate(masks))
        if write_back:
            if isinstance(ds, MultiDataSet):
                ds.features = list(features)
                ds.labels = list(labels)
                if ds.features_masks is not None:
                    ds.features_masks = list(fmasks)
                if ds.labels_masks is not None:
                    ds.labels_masks = [
                        lm if orig is not None else None
                        for lm, orig in zip(lmasks, ds.labels_masks)]
            elif isinstance(ds, DataSet):
                ds.features = features[0]
                ds.labels = labels[0]
                if ds.features_mask is not None:
                    ds.features_mask = fmasks[0]
                if ds.labels_mask is not None:
                    ds.labels_mask = lmasks[0]
        return features, labels, fmasks, lmasks

    def fit_batch(self, ds) -> float:
        """One synced optimization step."""
        try:
            return float(self._fit_batch_async(ds))
        finally:
            # standalone step: idle-until-next-call is not host gap
            telemetry.host_gap_stop()

    def _fit_batch_async(self, ds):
        """One step without forcing a host sync (see
        MultiLayerNetwork._fit_batch_async)."""
        from deeplearning4j_tpu.conf.multilayer import BackpropType

        if self.params is None:
            self.init()
        k = int(getattr(ds, "fused_stack", 0) or 0)
        if k > 1:
            return self._fit_fused(ds, k)
        if self.conf.backprop_type is BackpropType.TRUNCATED_BPTT:
            ndims = [np.ndim(f) for f in _as_multi(ds).features]
            if all(d == 3 for d in ndims):
                from deeplearning4j_tpu.resilience import faults

                # one normalization path shared with ParallelWrapper
                with telemetry.span(telemetry.PHASE_INGEST):
                    args = self.tbptt_batch_arrays(ds)
                # same once-per-optimization-step injection site as the
                # standard branch — tBPTT steps are killable too (the
                # corrupt action poisons the first input sequence)
                feats = args[0]
                args = ((faults.fault_point("train.step", feats[0]),
                         ) + tuple(feats[1:]),) + tuple(args[1:])
                return self._fit_tbptt(*args)
            if any(d == 3 for d in ndims):
                # a MIXED seq/static batch must not silently train
                # STANDARD against a tBPTT config (ParallelWrapper raises
                # for the same model; fit must not diverge from it)
                raise ValueError(
                    "ComputationGraph truncated BPTT requires every "
                    "network input to be a sequence [batch, time, size]; "
                    f"got feature ranks {ndims}. Use STANDARD backprop "
                    "for mixed sequence/static inputs")
            # no sequence inputs at all: plain static batch under a tBPTT
            # conf trains via the standard step (MultiLayerNetwork's
            # behavior for 2-D features)
        from deeplearning4j_tpu.telemetry import health

        mode = health.graph_mode()
        if self._train_step is None \
                or getattr(self, "_train_step_mode", "") != mode \
                or getattr(self, "_train_step_ktag", "") != self._ktag():
            raw = self.train_step_fn(guards=mode)
            dtype = self._dtype

            # per-step scalars (iteration, epoch, rng fold, default masks)
            # live inside the jit — each eager host op would cost a
            # dispatch round-trip (see nn_io device counters)
            def step(params, state, opt_state, features, labels, fmasks,
                     lmasks, itc, ep, base_key):
                with jax.named_scope("updater"):   # its clock, the rng
                    it, rng = nn_io.step_scalars(itc, base_key)
                with jax.named_scope("loss"):
                    lmasks = tuple(
                        jnp.ones((l.shape[0],), dtype) if m is None else m
                        for m, l in zip(lmasks, labels))
                out = raw(params, state, opt_state, features, labels,
                          fmasks, lmasks, it, ep, rng)
                new_p, new_s, new_o, loss = out[:4]
                with jax.named_scope("updater"):
                    itc = itc + 1
                if mode:
                    return new_p, new_s, new_o, loss, itc, out[4]
                return new_p, new_s, new_o, loss, itc

            self._train_step_ktag = self._ktag()
            self._train_step = aot_cache.wrap(
                jax.jit(step, donate_argnums=(0, 1, 2, 7)),
                self._graph_key(),
                f"train_step:d012+itc{health.cache_tag()}"
                f"{self._train_step_ktag}")
            self._train_step_mode = mode
            self._guard_keys = health.bucket_keys(self.params or {})
        with telemetry.span(telemetry.PHASE_INGEST):
            features, labels, fmasks, lmasks = self._prep_batch(
                ds, lazy_lmasks=True, write_back=True)
        from deeplearning4j_tpu.resilience import faults

        # injection site (raise = preemption/crash, corrupt = poisoned
        # first input feeding the health guards); host-side, pre-jit
        features = (faults.fault_point("train.step", features[0]),
                    ) + tuple(features[1:])
        gvec = None
        with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
            telemetry.host_gap_close()
            out = self._train_step(
                self.params, self.state, self.opt_state, features, labels,
                fmasks, lmasks, self.device_iteration(),
                self.device_epoch(), self._base_key)
            (self.params, self.state, self.opt_state, loss,
             new_itc) = out[:5]
            if mode:
                gvec = out[5]
            _sp.set_result(loss)
        with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
            _sp.set_result(self.params)  # single device: ~0 (see MLN)
        # post-span: under enable(sync=True) the gap excludes device time
        telemetry.host_gap_open()
        telemetry.record_step("graph", int(features[0].shape[0]))
        self._score_dev = loss
        self._score_cache = None
        cur = self.iteration
        self.iteration += 1  # listeners see iteration == next-to-run
        self.advance_device_iteration(new_itc)
        if mode:
            health.observe_step(
                self, "graph", cur, self.epoch, loss, gvec,
                self._guard_keys, batch=(features, labels),
                rng_seed=int(getattr(self.conf, "seed", 0) or 0))
        if self.listeners:
            with telemetry.span("listeners"):
                for lst in self.listeners:
                    lst.iteration_done(self, cur, self.epoch, loss)
        return loss

    # --- truncated BPTT (reference ComputationGraph#doTruncatedBPTT) -------
    def _tbptt_prepad(self, ds):
        """Variable-length host batches: pad T to a multiple of
        tbptt_fwd_length in NUMPY (free) so the scan jit's cache key
        quantizes to the segment count instead of retracing per distinct T
        (same scheme as MultiLayerNetwork._tbptt_prepad, generalized to
        MultiDataSet). Padded steps get zero masks; with back < fwd the
        padding goes BEFORE the tail segment's real steps so they stay
        inside the gradient window. Returns a MultiDataSet (a new one when
        padding applies — the caller's arrays are never mutated)."""
        mds = _as_multi(ds)
        fs = list(mds.features)
        if not all(isinstance(f, np.ndarray) and f.ndim == 3 for f in fs):
            return mds
        seg = int(self.conf.tbptt_fwd_length)
        t = fs[0].shape[1]
        pad = (-t) % seg
        back = min(int(self.conf.tbptt_back_length or seg), seg)
        # reuse the padded (or wrapped) copy across epochs (write_back
        # migrates ITS arrays to device on first fit). Keyed on the
        # IDENTITY of every array consumed — replacing any invalidates.
        key = (tuple(fs), tuple(mds.labels),
               tuple(mds.features_masks or ()),
               tuple(mds.labels_masks or ()), seg, back)
        cached = getattr(ds, "_tbptt_padded", None)
        if cached is not None and len(cached[0]) == len(key) and all(
                (a is b if not isinstance(a, tuple)
                 else len(a) == len(b) and all(x is y for x, y in zip(a, b)))
                for a, b in zip(cached[0], key)):
            return cached[1]
        if pad == 0:
            # no padding needed — but still cache the MultiDataSet wrapper
            # (a DataSet input gets a FRESH wrapper per _as_multi call, and
            # the device write-back would be lost every epoch otherwise)
            if ds is not mds:
                try:
                    ds._tbptt_padded = (key, mds)
                except AttributeError:
                    pass
            return mds
        n = fs[0].shape[0]
        split = t - (t % seg) if back < seg else t

        def pad_t(a):
            a = np.asarray(a)
            z = np.zeros((n, pad) + a.shape[2:], a.dtype)
            return np.concatenate([a[:, :split], z, a[:, split:]], axis=1)

        in_masks = (list(mds.features_masks)
                    if mds.features_masks is not None else [None] * len(fs))
        fmasks = [pad_t(m if m is not None else np.ones((n, t), self._dtype))
                  for m in in_masks]
        out_masks = (list(mds.labels_masks)
                     if mds.labels_masks is not None
                     else [None] * len(mds.labels))
        lmasks = []
        for m in out_masks:
            if m is not None and np.ndim(m) == 1:  # per-example -> per-step
                m = np.asarray(m)[:, None] * np.ones((n, t), self._dtype)
            lmasks.append(pad_t(m if m is not None
                                else np.ones((n, t), self._dtype)))
        labels = [pad_t(l) if np.ndim(l) == 3 else l for l in mds.labels]
        padded = MultiDataSet(features=[pad_t(f) for f in fs], labels=labels,
                              features_masks=fmasks, labels_masks=lmasks)
        try:
            ds._tbptt_padded = (key, padded)
        except AttributeError:
            pass  # exotic immutable containers just re-pad
        return padded

    def tbptt_scan_parts(self, seg: int, back: Optional[int] = None):
        """Shared tBPTT scan plumbing for the DAG — ``(segments,
        zero_carries, advance, cut)`` — the vertex-topology generalization
        of ``MultiLayerNetwork.tbptt_scan_parts`` (same contract, so
        ParallelWrapper's scans work for both model types):

        - ``segments(group)``: tree-maps [B, T, ...] -> [n_seg, B, seg,
          ...] over a tuple of per-input (or per-output) arrays in-trace.
        - ``zero_carries(features)``: per-RNN-vertex zero carries keyed by
          vertex name, vma-anchored to the batch for shard_map.
        - ``advance(params, state, carries, f, l, fm, lm)``: consume each
          segment's no-grad head (``cut`` steps, inference mode through
          the DAG minus output vertices) and return the trimmed gradient
          window + advanced carries."""
        back = seg if back is None else min(int(back), seg)
        cut = seg - back
        out_names = set(self.conf.network_outputs)
        cdt = self._cdtype or self._dtype

        def _seg_one(arr):
            # INSIDE the jit: static shapes, zero extra dispatches. n_seg
            # derives from the traced shape (a different T retraces with
            # its own count).
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
            elif pad:
                width = [(0, 0), (0, pad)] + [(0, 0)] * (arr.ndim - 2)
                arr = jnp.pad(arr, width)
            shaped = arr.reshape(arr.shape[0], ns, seg, *arr.shape[2:])
            return jnp.moveaxis(shaped, 1, 0)

        def segments(group):
            return jax.tree_util.tree_map(_seg_one, group)

        def zero_carries(features):
            # anchor to the features: under shard_map the batch is varied
            # over the mesh axis and a bare jnp.zeros is not — lax.scan
            # would reject the carry (vma mismatch). Free under plain jit.
            f0 = jax.tree_util.tree_leaves(features)[0]
            anchor = jnp.sum(f0[:1, :1]) * 0
            carries = {
                name: self._vmap[name].vertex.zero_carry(f0.shape[0], cdt)
                for name in self._topo
                if getattr(self._vmap[name].vertex, "has_carry", False)
                and not _is_go_backwards(self._vmap[name].vertex)}
            return jax.tree_util.tree_map(
                lambda z: z + anchor.astype(z.dtype), carries)

        def advance(params, state, carries, f_s, l_s, fm_s, lm_s):
            if cut:
                # state-advance over the head of the segment: no gradient
                # reaches these timesteps (reference truncates the
                # backward pass at back_length); output vertices skipped
                f_c = tuple(self._dequant(f[:, :cut], i)
                            for i, f in enumerate(f_s))
                fm_c = tuple(m[:, :cut] for m in fm_s)
                fwd_p, f_c = self._fwd_cast(params, f_c)
                _, _, carries = self._forward(
                    fwd_p, state, f_c, train=False, rng=None,
                    skip=out_names, fmasks=fm_c, carries=carries)
                f_s, l_s, fm_s, lm_s = jax.tree_util.tree_map(
                    lambda a: a[:, cut:], (f_s, l_s, fm_s, lm_s))
            return f_s, l_s, fm_s, lm_s, carries

        return segments, zero_carries, advance, cut

    def tbptt_scan_fn(self, seg: int, back: Optional[int] = None,
                      guards: str = ""):
        """The raw (unjitted) whole-batch tBPTT runner for the DAG —
        ``(params, state, opt, features, labels, fmasks, lmasks, itc, ep,
        base_key) -> (params, state, opt, new_itc, mean_loss)`` with tuple
        batch groups — segment scan with detached carries, same contract
        as ``MultiLayerNetwork.tbptt_scan_fn`` so ParallelWrapper jits it
        over a mesh unchanged (``guards`` appends the max-aggregated
        health guard vector, same as there)."""
        raw = self.train_step_fn(guards=guards)
        segments, zero_carries, advance, _ = self.tbptt_scan_parts(seg,
                                                                   back)

        def run(params, state, opt, features, labels, fmasks, lmasks,
                itc, ep, base_key):
            from deeplearning4j_tpu.telemetry import health

            segs = tuple(segments(g)
                         for g in (features, labels, fmasks, lmasks))
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

    def fused_scan_fn(self, k: int, guards: str = ""):
        """The raw (unjitted) K-step fused runner for the DAG — the
        tuple-batch generalization of
        ``MultiLayerNetwork.fused_scan_fn`` (same contract: scan the
        standard train step over [K, B, ...] stacks, K steps per
        dispatch, bit-identical to K standard steps; guards ride the
        ys as the [K, G] stack). ParallelWrapper jits it over a mesh
        unchanged."""
        raw = self.train_step_fn(guards=guards)
        dtype = self._dtype

        def run(params, state, opt, features, labels, fmasks, lmasks,
                itc, ep, base_key):
            def body(carry, xs):
                params, state, opt, itc = carry
                f_s, l_s, fm_s, lm_s = xs
                # same in-jit defaults as the standard step builder
                lm_s = tuple(
                    jnp.ones((l.shape[0],), dtype) if m is None else m
                    for m, l in zip(lm_s, l_s))
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
                (features, labels, fmasks, lmasks))
            if guards:
                losses, vecs = ys
                return params, state, opt, itc, losses, vecs
            return params, state, opt, itc, ys

        return run

    def _fit_fused(self, ds, k: int):
        """K fused optimization steps from one stacked (Multi)DataSet —
        the DAG counterpart of ``MultiLayerNetwork._fit_fused`` (one
        scan dispatch, donated carry, K-keyed AOT cache, K per-step
        listener losses, super-step health granularity)."""
        from deeplearning4j_tpu.conf.multilayer import BackpropType
        from deeplearning4j_tpu.resilience import faults
        from deeplearning4j_tpu.telemetry import health

        if self.conf.backprop_type is BackpropType.TRUNCATED_BPTT:
            raise ValueError(
                "fused_steps composes with STANDARD backprop only: a "
                "tBPTT batch already trains as one compiled segment scan")
        with telemetry.span(telemetry.PHASE_INGEST):
            features, labels, fmasks, lmasks = self._prep_batch(
                ds, lazy_lmasks=True, write_back=True)
        features = (faults.fault_point("train.step", features[0]),
                    ) + tuple(features[1:])
        mode = health.graph_mode()
        ktag = self._ktag()
        if self._fused_scan is None:
            self._fused_scan = {}
        if (k, mode, ktag) not in self._fused_scan:
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
                fmasks, lmasks, self.device_iteration(),
                self.device_epoch(), self._base_key)
            (self.params, self.state, self.opt_state, new_itc,
             losses) = out[:5]
            if mode:
                gvecs = out[5]
            _sp.set_result(losses)
        with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
            _sp.set_result(self.params)  # single device: ~0 (see MLN)
        telemetry.host_gap_open()  # post-span: sync mode excludes device
        telemetry.record_step(
            "graph",
            int(features[0].shape[0]) * int(features[0].shape[1]),
            steps=k)
        self._score_dev = losses[-1]
        self._score_cache = None
        cur = self.iteration
        self.iteration += k
        self.advance_device_iteration(new_itc)
        if mode:
            self._guard_keys = health.bucket_keys(self.params)
            health.observe_fused(
                self, "graph", cur, self.epoch, losses, gvecs,
                self._guard_keys, k, batch=(features, labels),
                rng_seed=int(getattr(self.conf, "seed", 0) or 0))
        if self.listeners:
            with telemetry.span("listeners"):
                for j in range(k):
                    loss_j = losses[j]
                    for lst in self.listeners:
                        lst.iteration_done(self, cur + j, self.epoch,
                                           loss_j)
        return losses[-1]  # device scalar: the async fit pipeline queues it

    def tbptt_batch_arrays(self, ds):
        """Stage one tBPTT batch fully normalized for ``tbptt_scan_fn``:
        prepadded time axis, every input a sequence sharing one T,
        per-timestep labels validated, all-ones default masks, 1-D labels
        masks expanded per-timestep. ParallelWrapper feeds the sharded
        scan runner these exact arrays."""
        # go_backwards layers train under tBPTT with PER-SEGMENT RESET
        # (see _is_go_backwards; round-3 refusal closed in round 4) —
        # only rnn_time_step streaming still refuses them.
        mds = self._tbptt_prepad(ds)
        features, labels, fmasks, lmasks = self._prep_batch(
            mds, lazy_lmasks=True, write_back=True)
        if any(np.ndim(f) != 3 for f in features):
            raise ValueError(
                "ComputationGraph truncated BPTT requires every network "
                "input to be a sequence [batch, time, size]; got shapes "
                f"{[tuple(np.shape(f)) for f in features]}")
        ts = {int(f.shape[1]) for f in features}
        if len(ts) != 1:
            raise ValueError(
                f"tBPTT inputs must share one time length, got {sorted(ts)}")
        total_t = ts.pop()
        n = int(features[0].shape[0])
        for i, l in enumerate(labels):
            if np.ndim(l) != 3 or int(l.shape[1]) != total_t:
                raise ValueError(
                    f"truncated BPTT needs per-timestep labels [batch, "
                    f"{total_t}, nOut] for output {i}, got shape "
                    f"{tuple(np.shape(l))} (reference tBPTT operates on "
                    "sequence labels)")
        fmasks = tuple(m if m is not None
                       else np.ones((n, total_t), self._dtype)
                       for m in fmasks)
        norm_lmasks = []
        for m in lmasks:
            if m is None:
                m = np.ones((n, total_t), self._dtype)
            elif np.ndim(m) == 1:  # per-example -> per-step
                ones_t = (np.ones if isinstance(m, np.ndarray)
                          else jnp.ones)((n, total_t), self._dtype)
                m = m[:, None] * ones_t
            norm_lmasks.append(m)
        for kind, group in (("features mask", fmasks),
                            ("labels mask", norm_lmasks)):
            for i, m in enumerate(group):
                if int(np.shape(m)[1]) != total_t:
                    raise ValueError(
                        f"truncated BPTT {kind} {i} has {np.shape(m)[1]} "
                        f"timesteps but the sequences have {total_t} — "
                        "masks must be at the INPUT rate (a wrong-length "
                        "mask would desynchronize the segment scan)")
        return features, labels, fmasks, tuple(norm_lmasks)

    def _fit_tbptt(self, features, labels, fmasks, lmasks):
        """Truncated BPTT over the DAG: one parameter update per
        tbptt_fwd_length segment, RNN-vertex carries threaded (detached)
        between segments, back<fwd no-grad head — the WHOLE chain one
        compiled ``lax.scan`` (the DAG equivalent of
        ``MultiLayerNetwork._fit_tbptt``)."""
        from deeplearning4j_tpu.telemetry import health

        mode = health.graph_mode()
        seg = int(self.conf.tbptt_fwd_length)
        back = min(int(self.conf.tbptt_back_length or seg), seg)
        n_seg = -(-int(features[0].shape[1]) // seg)
        # cache keyed by (seg, back, health mode): a conf length (or
        # guard-mode) change between fits must not silently reuse a
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
                fmasks, lmasks, self.device_iteration(),
                self.device_epoch(), self._base_key)
            (self.params, self.state, self.opt_state, new_itc,
             mean_loss) = out[:5]
            if mode:
                gvec = out[5]
            _sp.set_result(mean_loss)
        telemetry.record_step("graph", int(features[0].shape[0]))
        self.iteration += n_seg
        self.advance_device_iteration(new_itc)
        self._score_dev = mean_loss
        self._score_cache = None
        if mode:
            self._guard_keys = health.bucket_keys(self.params)
            health.observe_step(
                self, "graph", self.iteration - 1, self.epoch, mean_loss,
                gvec, self._guard_keys, batch=(features, labels),
                rng_seed=int(getattr(self.conf, "seed", 0) or 0))
        if self.listeners:
            with telemetry.span("listeners"):
                for lst in self.listeners:
                    # one batch-level call, arg = last segment's
                    # iteration index
                    lst.iteration_done(self, self.iteration - 1,
                                       self.epoch, mean_loss)
        return mean_loss  # device scalar: the async fit pipeline queues it

    # --- stateful RNN inference (reference CG#rnnTimeStep) ------------------
    def rnn_time_step(self, *inputs, fmasks=None):
        """Streaming inference: feed sequence segments [batch, t, f], get
        outputs with per-RNN-vertex state persisted across calls
        (reference ``ComputationGraph#rnnTimeStep``)."""
        if self.params is None:
            self.init()
        for name in self._topo:
            # checks the VERTEX itself too (AttentionVertex attends over
            # the whole sequence and has no .layer), then its layer chain
            nn_io.check_streaming_safe(self._vmap[name].vertex,
                                       f"vertex {name!r}")
        if self._rnn_step_fn is None:
            def out(params, state, carries, xs, fmasks):
                xs = tuple(self._dequant(x, i) for i, x in enumerate(xs))
                params, xs = self._fwd_cast(params, xs, full=True)
                if self._cdtype is not None:
                    carries = nn_io.cast_floats(carries, self._cdtype)
                acts, _, new_carries = self._forward(
                    params, state, xs, train=False, rng=None,
                    fmasks=fmasks, carries=carries)
                return (tuple(acts[n].astype(self._dtype)
                              for n in self.conf.network_outputs),
                        new_carries)

            self._rnn_step_fn = jax.jit(out)
        xs = tuple(nn_io.as_device(x, self._dtype, feature=True)
                   for x in inputs)
        xs = tuple(x[:, None, :] if x.ndim == 2 else x for x in xs)
        n = xs[0].shape[0]
        if self._rnn_carries is None:
            self._rnn_carries = {
                name: self._vmap[name].vertex.zero_carry(
                    n, self._cdtype or self._dtype)
                for name in self._topo
                if getattr(self._vmap[name].vertex, "has_carry", False)}
        fm = tuple(nn_io.as_device(m, self._dtype) if m is not None else None
                   for m in (fmasks if fmasks is not None
                             else (None,) * len(xs)))
        outs, self._rnn_carries = self._rnn_step_fn(
            self.params, self.state, self._rnn_carries, xs, fm)
        return outs[0] if len(outs) == 1 else list(outs)

    def rnn_clear_previous_state(self):
        """Reference ``#rnnClearPreviousState``."""
        self._rnn_carries = None

    def rnn_get_previous_state(self, vertex_name: str):
        """Reference ``#rnnGetPreviousState(layerName)``. Returned state is
        in the storage dtype (internal carries live in the compute dtype)."""
        if self._rnn_carries is None:
            return None
        c = self._rnn_carries.get(vertex_name)
        if c is None or self._cdtype is None:
            return c
        return nn_io.cast_floats(c, self._dtype)

    def rnn_set_previous_state(self, vertex_name: str, state: dict):
        """Reference ``#rnnSetPreviousState(layerName, state)``."""
        if self._rnn_carries is None:
            self._rnn_carries = {}
        self._rnn_carries[vertex_name] = {
            k: jnp.asarray(v, self._cdtype or self._dtype)
            for k, v in state.items()}

    def feed_forward(self, *inputs, fmasks=None) -> Dict[str, object]:
        """Per-vertex activations, eval mode (reference
        ``ComputationGraph#feedForward`` returning Map<String, INDArray>).
        Powers the StatsListener activation histograms."""
        if self.params is None:
            self.init()
        if getattr(self, "_feed_forward_fn", None) is None:
            def ff(params, state, xs, fmasks):
                xs = tuple(self._dequant(x, i) for i, x in enumerate(xs))
                params, xs = self._fwd_cast(params, xs, full=True)
                acts, _, _ = self._forward(params, state, xs, train=False,
                                           rng=None, fmasks=fmasks)
                return {n: acts[n].astype(self._dtype)
                        for n in self._topo}

            self._feed_forward_fn = jax.jit(ff)
        xs = tuple(nn_io.as_device(x, self._dtype, feature=True)
                   for x in inputs)
        fm = tuple(nn_io.as_device(m, self._dtype) if m is not None else None
                   for m in (fmasks if fmasks is not None
                             else (None,) * len(xs)))
        return dict(self._feed_forward_fn(self.params, self.state, xs, fm))

    # --- inference / scoring ----------------------------------------------
    def output(self, *inputs, fmasks=None):
        """Forward pass, eval mode (reference ``#output(INDArray...)``).
        Returns a list aligned with conf.network_outputs (single array if
        one output). ``fmasks``: per-input feature masks (reference
        ``#output(INDArray[], INDArray[] featureMasks, ...)``)."""
        if self.params is None:
            self.init()
        if self._output_fn is None \
                or getattr(self, "_output_ktag", "") != self._ktag():
            def out(params, state, xs, fmasks):
                xs = tuple(self._dequant(x, i) for i, x in enumerate(xs))
                params, xs = self._fwd_cast(params, xs, full=True)
                acts, _, _ = self._forward(params, state, xs, train=False,
                                           rng=None, fmasks=fmasks)
                return tuple(acts[n].astype(self._dtype)
                             for n in self.conf.network_outputs)

            self._output_ktag = self._ktag()
            self._output_fn = aot_cache.wrap(
                jax.jit(out), self._graph_key(),
                f"output{self._output_ktag}")
        # jax.Arrays pass through (keeps committed shardings); uint8
        # features dequantize inside the jit, matching training
        xs = tuple(nn_io.as_device(x, self._dtype, feature=True)
                   for x in inputs)
        fm = tuple(nn_io.as_device(m, self._dtype) if m is not None else None
                   for m in (fmasks if fmasks is not None
                             else (None,) * len(xs)))
        outs = self._output_fn(self.params, self.state, xs, fm)
        return outs[0] if len(outs) == 1 else list(outs)

    def score(self, ds=None) -> float:
        if ds is None:
            return self.score_value
        if self.params is None:
            self.init()
        if self._score_fn is None \
                or getattr(self, "_score_ktag", "") != self._ktag():
            def score(params, state, features, labels, fmasks, lmasks):
                loss, _ = self._loss(params, state, features, labels,
                                     fmasks, lmasks, rng=None, train=False)
                return loss

            self._score_ktag = self._ktag()
            self._score_fn = aot_cache.wrap(
                jax.jit(score), self._graph_key(),
                f"score{self._score_ktag}")
        features, labels, fmasks, lmasks = self._prep_batch(ds)
        return float(self._score_fn(self.params, self.state, features,
                                    labels, fmasks, lmasks))

    def evaluate(self, iterator, evaluation: Optional[Evaluation] = None):
        """Reference ``#evaluate(DataSetIterator)`` — first output vertex."""
        ev = evaluation if evaluation is not None else Evaluation()
        for ds in iterator:
            mds = _as_multi(ds)
            out = self.output(*mds.features,
                              fmasks=mds.features_masks)
            if isinstance(out, list):
                out = out[0]
            mask = (mds.labels_masks[0]
                    if mds.labels_masks is not None else None)
            ev.eval(mds.labels[0], np.asarray(out), mask=mask)
        if hasattr(iterator, "reset"):
            iterator.reset()
        return ev

    def compute_gradient_and_score(self, ds):
        """(grads pytree, score) without updating (reference
        ``#computeGradientAndScore``)."""
        if self.params is None:
            self.init()
        features, labels, fmasks, lmasks = self._prep_batch(ds)

        def loss_fn(p):
            return self._loss(p, self.state, features, labels, fmasks,
                              lmasks, rng=None)

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            self.params)
        return grads, float(loss)

    # --- params vector (serializer parity) ---------------------------------
    def params_flat(self) -> np.ndarray:
        return params_util.flatten_params(self.conf, self.params)

    def set_params_flat(self, flat: np.ndarray):
        self.params = params_util.unflatten_params(self.conf, flat,
                                                   self.params)
        return self

    def num_params(self) -> int:
        return int(self.params_flat().size)

    def clone(self) -> "ComputationGraph":
        other = ComputationGraph(self.conf)
        if self.params is not None:
            other.init()
            # true copies: the train step donates its input buffers, so
            # shared references would be invalidated by the next fit
            other.params = jax.tree_util.tree_map(jnp.copy, self.params)
            other.state = jax.tree_util.tree_map(jnp.copy, self.state)
            other.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
        return other

    def summary(self) -> str:
        types = self.conf.vertex_output_types()
        lines = ["=" * 78,
                 f"{'vertex':<24} {'type':<24} {'inputs':<18} {'params':>9}",
                 "-" * 78]
        total = 0
        for name in self._topo:
            spec = self._vmap[name]
            n = 0
            if self.params and name in self.params:
                n = sum(int(np.prod(p.shape))
                        for p in self.params[name].values())
            total += n
            vname = type(spec.vertex).__name__
            if hasattr(spec.vertex, "layer") and spec.vertex.layer is not None:
                vname = type(spec.vertex.layer).__name__
            lines.append(f"{name:<24} {vname:<24} "
                         f"{','.join(spec.inputs):<18} {n:>9,}")
        lines += ["-" * 78, f"Total params: {total:,}", "=" * 78]
        return "\n".join(lines)
