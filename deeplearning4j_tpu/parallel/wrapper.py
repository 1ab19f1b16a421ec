"""ParallelWrapper — single-process multi-device data-parallel training.

Reference: ``org.deeplearning4j.parallelism.ParallelWrapper`` (SURVEY.md
§2.2, §3.4): N model replicas pinned to devices via ``AffinityManager``, a
splitter feeding per-worker ``MagicQueue``s, and two training modes —
periodic parameter AVERAGING, or per-iteration SHARED_GRADIENTS through the
``EncodedGradientsAccumulator`` (threshold-compressed, residual-corrected).

TPU-native inversion: replicas/threads/queues collapse into sharding over a
``jax.sharding.Mesh``'s ``data`` axis —

- **SHARED_GRADIENTS (exact, default):** ONE jitted train step whose batch
  inputs are sharded ``P('data')`` and whose params are replicated; XLA's
  SPMD partitioner inserts the gradient all-reduce over ICI. This is
  mathematically the reference's gradient sharing with a lossless
  accumulator — and is the recommended mode on TPU (ICI makes compression
  pointless intra-slice).
- **SHARED_GRADIENTS + ThresholdAlgorithm:** ``shard_map`` step that keeps a
  per-replica residual, threshold-encodes ``grad + residual`` to ±tau, sums
  the encoded tensors with ``lax.psum`` (the accumulator's message exchange)
  and applies the updater to the shared sum — exact reference semantics
  (sum of peers' messages, residual self-correction, adaptive tau), useful
  when gradients must cross DCN.
- **AVERAGING:** replicas hold *independent* params stacked on a leading
  device axis sharded ``P('data')``; each step is a purely local
  ``shard_map`` update, and every ``averaging_frequency`` iterations params
  (and optionally updater state) are averaged across the axis — the
  reference's barrier-averaging, as one compiled collective.

Works with both ``MultiLayerNetwork`` and ``ComputationGraph``. The same
code scales 1 chip -> pod: only the mesh changes (multi-host via
``mesh.initialize_distributed``).
"""

from __future__ import annotations

import enum
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.nn import io as nn_io
from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.parallel.compression import (
    ThresholdAlgorithm,
    bucket_layout,
    bucketed_psum,
    bucketed_psum_scatter,
    encode_tree,
)

shard_map = mesh_mod.shard_map
DATA = mesh_mod.DATA_AXIS


class TrainingMode(enum.Enum):
    """Reference ``ParallelWrapper.TrainingMode`` (AVERAGING /
    SHARED_GRADIENTS; CUSTOM is covered by subclassing)."""

    AVERAGING = "averaging"
    SHARED_GRADIENTS = "shared_gradients"


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _proc_token() -> str:
    """Multi-process step-key component: the same mesh axis sizes over a
    different process topology compile different SPMD programs (per-host
    shard ownership differs), so pod executables must never collide with
    single-host ones in the AOT cache. Empty at ``process_count == 1``
    — every pre-pod cache key is unchanged."""
    procs = jax.process_count()
    return f":p{procs}" if procs > 1 else ""


_vary_on = mesh_mod.ensure_varying


def _local_copy(params):
    """The replicated params as this shard's own (varying) copy, for the
    explicit-exchange steps to differentiate: a gradient taken w.r.t.
    the replicated tree itself is typed replicated, so shard_map's AD
    psums it across shards — a hidden all-reduce in front of the
    exchange the step then issues on the already-summed gradient."""
    return _tree_map(lambda p: _vary_on(p, (DATA,)), params)


def _stack(tree, n: int):
    return _tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def _pad_axis1(tree, target: int):
    """Zero-pad every leaf's axis 1 — the per-step batch rows of a
    [K, B, ...] fused stack — to ``target`` rows (the stacked counterpart
    of ``mesh.pad_leading``; the materialized labels masks zero out the
    padded rows' loss contribution)."""
    def pad(x):
        x = jnp.asarray(x)
        if x.shape[1] == target:
            return x
        z = jnp.zeros(x.shape[:1] + (target - x.shape[1],) + x.shape[2:],
                      x.dtype)
        return jnp.concatenate([x, z], axis=1)

    return _tree_map(pad, tree)


def _mean_leading(tree):
    return _tree_map(lambda x: x.mean(axis=0), tree)


class ParallelWrapper(nn_io.LazyScoreMixin):
    """Multi-device data-parallel trainer (reference ``ParallelWrapper``).

    Usage (reference ``ParallelWrapper.Builder`` equivalent)::

        pw = ParallelWrapper(net, workers=8,
                             training_mode=TrainingMode.SHARED_GRADIENTS)
        pw.fit(iterator, epochs=2)

    ``workers`` = size of the mesh's data axis (reference: number of model
    replicas); defaults to all local devices.
    """

    def __init__(self, model, workers: Optional[int] = None,
                 training_mode: TrainingMode = TrainingMode.SHARED_GRADIENTS,
                 averaging_frequency: int = 5,
                 average_updaters: bool = True,
                 threshold_algorithm: Optional[ThresholdAlgorithm] = None,
                 prefetch_buffer: int = 2,
                 mesh=None, expert_parallel: bool = False,
                 gradient_bucket_mb: Optional[float] = None,
                 fused_steps: Optional[int] = None,
                 zero_optimizer: bool = False,
                 partition_rules=None):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        if model.params is None:
            model.init()
        self.model = model
        self._is_graph = isinstance(model, ComputationGraph)
        if not self._is_graph and not isinstance(model, MultiLayerNetwork):
            raise TypeError(f"unsupported model type {type(model)}")
        self.mesh = mesh if mesh is not None else mesh_mod.single_host_mesh(
            n_devices=workers)
        self.workers = self.mesh.shape[DATA]
        if workers is not None and self.workers != workers:
            raise ValueError(
                f"mesh data axis = {self.workers}, workers = {workers}")
        from deeplearning4j_tpu.conf.multilayer import BackpropType

        # both model types expose the same tbptt_scan_fn/parts/
        # batch_arrays protocol (ComputationGraph since round 3)
        self._tbptt = model.conf.backprop_type is BackpropType.TRUNCATED_BPTT
        if self._tbptt:
            seg = int(model.conf.tbptt_fwd_length)
            back = int(model.conf.tbptt_back_length or seg)
            self._tbptt_seg = seg
            self._tbptt_back = min(back, seg)
        procs = jax.process_count()
        if self.workers % procs != 0 or self.workers < procs:
            raise ValueError(
                f"data axis size {self.workers} must be a positive multiple "
                f"of the process count {procs} (each host owns "
                f"data_axis/process_count shards)")
        self.local_workers = self.workers // procs
        self.training_mode = training_mode
        self.expert_parallel = bool(expert_parallel)
        if self.expert_parallel:
            # GShard layout: experts ride the data axis — one mesh axis
            # serves both batch and expert sharding
            if (training_mode is not TrainingMode.SHARED_GRADIENTS
                    or threshold_algorithm is not None or self._tbptt):
                raise ValueError(
                    "expert_parallel composes with the exact "
                    "SHARED_GRADIENTS mode only (no threshold "
                    "compression, no tBPTT)")
            for name, layer in self._layer_confs():
                axes = getattr(layer, "param_shard_axes", lambda: {})()
                if axes and layer.n_experts % self.workers != 0:
                    raise ValueError(
                        f"layer {name}: n_experts={layer.n_experts} must "
                        f"be a multiple of the data-axis size "
                        f"{self.workers}")
        self.averaging_frequency = int(averaging_frequency)
        self.average_updaters = bool(average_updaters)
        self.threshold_algorithm = threshold_algorithm
        self.prefetch_buffer = int(prefetch_buffer)
        # bucketed, overlap-scheduled gradient sync (compression.py
        # bucketed_psum): None = the default single-collective paths
        # (exact mode: XLA-SPMD-inserted all-reduce; threshold mode: one
        # fused psum of the encoded tree). A number switches both
        # SHARED_GRADIENTS variants to explicit reverse-topological
        # buckets of ~that many MB, issue-order pinned so communication
        # overlaps the remaining backward pass; 0 means "explicit
        # shard_map exchange, single fused collective" (the bucketing
        # A/B baseline). AVERAGING mode buckets its periodic parameter-
        # averaging collective the same way.
        if gradient_bucket_mb is None:
            self.gradient_bucket_bytes = None
            self._explicit_exchange = False
        else:
            mb = float(gradient_bucket_mb)
            if mb < 0:
                raise ValueError(
                    f"gradient_bucket_mb must be >= 0, got {mb}")
            self.gradient_bucket_bytes = (int(mb * 2 ** 20) if mb > 0
                                          else None)
            self._explicit_exchange = True
        if self._explicit_exchange and (self.expert_parallel or self._tbptt):
            raise ValueError(
                "gradient_bucket_mb composes with the standard "
                "SHARED_GRADIENTS / AVERAGING steps only (no "
                "expert_parallel, no tBPTT yet)")
        # ZeRO-style optimizer-state sharding (sharding/zero.py): the
        # SHARED_GRADIENTS exchange becomes reduce-scatter(grads) ->
        # local 1/n optimizer update -> all-gather(params), so each
        # device holds 1/workers of every moment buffer. Numerically
        # identical to the all-reduce path (elementwise updaters on a
        # flat partition; XLA's reduce-scatter performs the same
        # per-element reduction as its all-reduce — pinned by tests).
        # gradient_bucket_mb composes: it sets the reduce-scatter /
        # all-gather bucket layout exactly as it does for bucketed_psum.
        self._zero = bool(zero_optimizer)
        if self._zero and (training_mode is not TrainingMode.SHARED_GRADIENTS
                           or threshold_algorithm is not None
                           or self.expert_parallel or self._tbptt):
            raise ValueError(
                "zero_optimizer composes with the exact SHARED_GRADIENTS "
                "path only (no threshold compression, no expert_parallel, "
                "no tBPTT, no AVERAGING)")
        # multi-host ZeRO (pod scale-out): the host-side scatter stages
        # through make_array_from_callback (each process commits only
        # its addressable slices) and the gather replicates process-
        # spanning slices through a compiled identity — see
        # sharding/zero.py + parallel/mesh.py. No process-count refusal:
        # the same wrapper spans hosts when jax.distributed is up.
        # declarative DP x TP placement (sharding/plan.py): a regex rule
        # table (or prebuilt ShardingPlan) places params/opt-state over
        # the mesh's data x model axes; the exact SPMD step runs under
        # those shardings (XLA partitions the matmuls and inserts the
        # collectives) and its executable is AOT-cached under the plan's
        # sharding tag.
        if partition_rules is None:
            self._plan = None
        else:
            from deeplearning4j_tpu.sharding import ShardingPlan

            self._plan = (partition_rules
                          if isinstance(partition_rules, ShardingPlan)
                          else ShardingPlan(partition_rules,
                                            mesh=self.mesh))
            if self._plan.mesh is not self.mesh:
                raise ValueError(
                    "partition_rules plan must be built on the wrapper's "
                    "mesh (pass mesh=plan.mesh or let the wrapper build "
                    "the plan from a rule table)")
            # multi-host plans work: placement host arrays stage via
            # make_array_from_callback (comms.reshard), the write-back
            # gather replicates TP-sharded leaves through a compiled
            # identity (mesh_mod.host_gather), and the plan's cache_tag
            # keys the process topology so pod executables never
            # collide with single-host ones.
            if (training_mode is not TrainingMode.SHARED_GRADIENTS
                    or threshold_algorithm is not None
                    or self.expert_parallel or self._tbptt or self._zero
                    or self._explicit_exchange):
                raise ValueError(
                    "partition_rules composes with the exact "
                    "SHARED_GRADIENTS SPMD path only (no threshold "
                    "compression, no expert_parallel, no tBPTT, no "
                    "AVERAGING, no gradient_bucket_mb — XLA owns the "
                    "collective schedule under GSPMD — and no "
                    "zero_optimizer yet)")
        # K-step fused dispatch (round 11): the model's fused_scan_fn
        # jitted over the mesh with the per-step batch axis sharded —
        # exact SPMD mode only (the other modes' per-step host feedback
        # loops — adaptive tau, averaging cadence — defeat fusion)
        self.fused_steps = int(fused_steps or 0)
        if self.fused_steps > 1:
            if (training_mode is not TrainingMode.SHARED_GRADIENTS
                    or threshold_algorithm is not None
                    or self.expert_parallel or self._explicit_exchange
                    or self._tbptt or self._zero or self._plan is not None):
                raise ValueError(
                    "fused_steps composes with the exact SHARED_GRADIENTS "
                    "SPMD path only (no threshold compression, no "
                    "gradient_bucket_mb, no expert_parallel, no tBPTT, "
                    "no AVERAGING, no zero_optimizer/partition_rules)")
            # multi-host fused dispatch works: stacked super-batches
            # stage via make_array_from_process_local_data (each host
            # contributes its local [K, B_local, ...] partition) and
            # the per-fit shape lock covers the stacked per-step rows
            # exactly as it covers single-step batches (_fit_batch_fused)
        self.score_value = float("nan")
        # device-resident training trees (replicated or replica-stacked)
        self._params = self._state = self._opt = None
        self._residual = None
        self._tau = None
        self._step = None
        self._avg = None
        self._collect = None
        self._mp_target = None
        self._fused_step = None
        self._fused_step_k = None
        # True while the staged device trees and the model's host arrays
        # agree — _write_back (the gather) is skipped when clean, so the
        # stacked gather-on-save hooks (session snapshot -> write_model
        # -> snapshot_training_state) cost ONE device_get, not three
        self._synced = False

    # --- model-type adapters -----------------------------------------------
    def _prep(self, ds):
        """-> tuple of batch arrays matching the model's train-step args."""
        if self._tbptt:
            return self.model.tbptt_batch_arrays(ds)
        if self._is_graph:
            return self.model._prep_batch(ds)
        return self.model._batch_arrays(ds)

    def _batch_rows(self, batch) -> int:
        return jax.tree_util.tree_leaves(batch)[0].shape[0]

    # --- device setup -------------------------------------------------------
    def _replicated(self, tree):
        return mesh_mod.replicate(self.mesh, tree)

    def _data_sharded(self, tree):
        return mesh_mod.shard_batch(self.mesh, tree)

    def _setup(self):
        """Place model params on the mesh; compile step fns only once (they
        are config-keyed, so repeated fit() calls reuse the jit cache).
        A health-mode change between fits invalidates the compiled step
        (guarded and unguarded executables differ)."""
        from deeplearning4j_tpu.telemetry import health

        m = self.model
        mode = health.graph_mode()
        if getattr(self, "_health_mode", None) != mode:
            self._step = None
            self._fused_step = None
            self._health_mode = mode
        # one-shot prestaged trees from comms.reshard_training_state: a
        # cross-mesh hand-off already recommitted params/state/opt onto
        # THIS mesh device-to-device — adopt them instead of re-staging
        # from the model's host arrays (exact/ZeRO/plan modes only; the
        # hand-off refuses the others)
        pre = self.__dict__.pop("_prestaged", None)
        if self.training_mode is TrainingMode.AVERAGING:
            # multi-process: each process contributes its LOCAL replicas;
            # shard_batch assembles the [workers]-leading global tree
            stacked = _stack((m.params, m.state, m.opt_state),
                             self.local_workers)
            stacked = self._data_sharded(stacked)
            self._params, self._state, self._opt = stacked
            if self._step is None:
                self._step = self._build_averaging_step()
                self._avg = self._build_average_fn()
            if self._collect is None:
                self._collect = jax.jit(
                    _mean_leading,
                    out_shardings=mesh_mod.replicated_spec(self.mesh))
        elif self.threshold_algorithm is not None:
            self._params = self._replicated(m.params)
            self._state = self._replicated(m.state)
            self._opt = self._replicated(m.opt_state)
            self._residual = self._data_sharded(
                _stack(_tree_map(jnp.zeros_like, m.params),
                       self.local_workers))
            if self._tau is None:
                self._tau = float(self.threshold_algorithm.threshold)
            if self._step is None:
                self._step = self._build_threshold_step()
        elif self.expert_parallel:
            specs = self._param_specs()

            def put(k, pk, v):
                sh = NamedSharding(self.mesh, specs[k][pk])
                return _tree_map(lambda a: jax.device_put(a, sh), v)

            self._params = {k: {pk: put(k, pk, v)
                                for pk, v in d.items()}
                            for k, d in m.params.items()}
            self._opt = {k: {pk: put(k, pk, v) for pk, v in d.items()}
                         for k, d in m.opt_state.items()}
            self._state = self._replicated(m.state)
            # the step is built on first batch (its arity depends on the
            # model type's batch tuple)
        elif self._zero:
            from deeplearning4j_tpu.sharding.zero import ZeroSpec

            if pre is not None:
                self._params, self._state, self._opt = pre
            else:
                self._params = self._replicated(m.params)
                self._state = self._replicated(m.state)
                # optimizer state lives SCATTERED: flat 1/workers
                # slices, each shard's slice resident on its devices
                # only — the ZeRO memory footprint. Device-resident
                # trees (a restored checkpoint, a rolled-back state)
                # re-scatter through comms.reshard's slice-intersection
                # path instead of the numpy round-trip.
                self._zero_pspec = ZeroSpec(m.params, self.workers)
                self._zero_ospec = ZeroSpec(m.opt_state, self.workers)
                self._opt = self._zero_ospec.scatter(m.opt_state,
                                                     self.mesh, DATA)
            if self._step is None:
                self._step = self._build_zero_step()
            telemetry.record_shard_bytes(
                self._zero_pspec.total_bytes(),
                self._zero_ospec.bytes_per_device(), self.mesh)
        elif self._plan is not None:
            from deeplearning4j_tpu.optimize import aot_cache

            plan = self._plan
            pspecs = plan.param_specs(m.params)
            ospecs = plan.opt_specs(m.params, m.opt_state)
            if pre is not None:
                self._params, self._state, self._opt = pre
            else:
                self._params = plan.place(m.params, pspecs)
                self._state = self._replicated(m.state)
                self._opt = plan.place(m.opt_state, ospecs)
            if self._step is None:
                raw = m.train_step_fn(guards=mode)

                def plan_step(params, state, opt, *rest):
                    *batch, itc, ep, base_key = rest
                    it, rng = nn_io.step_scalars(itc, base_key)
                    return raw(params, state, opt, *batch, it, ep, rng)

                rep = mesh_mod.replicated_spec(self.mesh)
                out_sh = (plan.shardings(pspecs),
                          _tree_map(lambda _: rep, m.state),
                          plan.shardings(ospecs), rep)
                if mode:
                    out_sh = out_sh + (rep,)
                jit_fn = jax.jit(plan_step, donate_argnums=(0, 1, 2),
                                 out_shardings=out_sh)
                # the plan's sharding tag keys the executable: two plans
                # over the same graph never share a compiled program,
                # and a re-instantiated wrapper on the same plan hits
                self._step = aot_cache.wrap(
                    jit_fn, m._graph_key(),
                    f"pw_rules:{plan.cache_tag()}"
                    f"{health.cache_tag()}")
            plan.publish_metrics(m.params, m.opt_state)
        else:
            if pre is not None:
                self._params, self._state, self._opt = pre
            else:
                self._params = self._replicated(m.params)
                self._state = self._replicated(m.state)
                self._opt = self._replicated(m.opt_state)
            # exact mode: the model's own fused step, jitted over the mesh —
            # batch shardings drive SPMD partitioning, XLA inserts the
            # all-reduce. With gradient_bucket_mb set, the explicit
            # shard_map exchange takes over (bucketed_psum schedule).
            if self._step is None:
                if self._explicit_exchange:
                    self._step = self._build_bucketed_exact_step()
                elif self._tbptt:
                    # the model's whole-batch segment-scan runner, SPMD-
                    # partitioned: batch axis sharded, params replicated;
                    # the per-segment gradient all-reduce is XLA-inserted
                    # exactly as in the standard step (guards ride along
                    # from the model's own scan)
                    self._step = jax.jit(
                        m.tbptt_scan_fn(self._tbptt_seg, self._tbptt_back,
                                        guards=mode),
                        donate_argnums=(0, 1, 2))
                else:
                    raw = m.train_step_fn(guards=mode)

                    def exact_step(params, state, opt, *rest):
                        *batch, itc, ep, base_key = rest
                        it, rng = nn_io.step_scalars(itc, base_key)
                        return raw(params, state, opt, *batch, it, ep, rng)

                    self._step = jax.jit(exact_step,
                                         donate_argnums=(0, 1, 2))
        # freshly staged from the model: trees and host arrays agree —
        # except after a prestaged cross-mesh hand-off, whose device
        # trees are AHEAD of the model's host arrays until a gather
        self._synced = pre is None

    # --- expert-parallel (GShard: experts ride the data axis) --------------
    def _layer_confs(self):
        """-> (name, conf layer) for every parameterized vertex/layer."""
        if self._is_graph:
            for name, vs in self.model._vmap.items():
                v = vs.vertex
                yield name, (getattr(v, "layer", None) or v)
        else:
            for i, layer in enumerate(self.model.conf.layers):
                yield str(i), layer

    def _param_specs(self):
        """PartitionSpec tree over model.params: leaves a MoE-style layer
        declares in ``param_shard_axes`` shard their LEADING axis over
        the data/expert axis; everything else replicates."""
        confs = dict(self._layer_confs())
        specs = {}
        for k, vparams in self.model.params.items():
            axes = getattr(confs.get(k), "param_shard_axes", lambda: {})()
            specs[k] = {pk: (P(DATA) if pk in axes else P())
                        for pk in vparams}
        return specs

    def _build_expert_step(self, n_batch: int):
        from deeplearning4j_tpu.nn import io as _io
        from deeplearning4j_tpu.parallel import expert as expert_mod

        m = self.model
        afn = self.model.apply_updates_fn()
        pspec = self._param_specs()

        def step(params, state, opt, *rest):
            *batch, itc, ep, base_key = rest
            it, rng = _io.step_scalars(itc, base_key)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA))

            # differentiate the PMEAN'd loss: under shard_map's varying-
            # manual-axes AD, the cotangent of a replicated param
            # accumulates (psums) across shards automatically, so grads
            # of the pmean'd loss arrive as the full global-mean
            # gradient on every shard — the round-3 moe_train_step
            # finding, pinned by test_moe_expert_parallel_matches_
            # single_device. Expert-sharded leaves (varying) get their
            # exact local-expert gradient with no collective.
            # regularization over EXPERT-SHARDED leaves: m._loss sees
            # only the local expert slice, and pmean would then divide
            # the true (sum over all experts) penalty by n_shards. The
            # correction psum(extra) - pmean(extra) restores it exactly
            # (zero when no regularization is configured).
            reg_confs = [
                (name, layer, set(layer.regularized_param_keys()),
                 set(getattr(layer, "param_shard_axes", lambda: {})()))
                for name, layer in self._layer_confs()
                if getattr(layer, "param_shard_axes", lambda: {})()
                and (getattr(layer, "regularization", ())
                     or getattr(layer, "regularization_bias", ()))]

            def sharded_reg(p):
                total = 0.0
                for name, layer, reg_keys, axes in reg_confs:
                    for pk in axes:
                        if pk not in p.get(name, {}):
                            continue
                        regs = (layer.regularization if pk in reg_keys
                                else layer.regularization_bias)
                        for r in regs or ():
                            total = total + r.score_term(p[name][pk])
                return total

            def loss_fn(p):
                with expert_mod.active_expert_axis(DATA):
                    loss, aux = m._loss(p, state, *batch, rng)
                loss = jax.lax.pmean(loss, DATA)
                if reg_confs:
                    extra = sharded_reg(p)
                    loss = loss + jax.lax.psum(extra, DATA) \
                        - jax.lax.pmean(extra, DATA)
                return loss, aux

            ((loss, (new_state, _)), grads) = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # replicated leaves: pmean (an identity on a gradient the
            # vma transpose already made invariant). Expert-SHARDED
            # leaves keep their exact local-expert gradient (see
            # parallel/expert.py for the same calculus on the raw MoE
            # step, pinned by
            # test_moe_expert_parallel_matches_single_device).
            grads = {
                k: {pk: (g if pspec[k][pk] != P()
                         else _tree_map(
                             lambda a: jax.lax.pmean(a, DATA), g))
                    for pk, g in vg.items()}
                for k, vg in grads.items()}
            new_state = _tree_map(
                lambda s: (jax.lax.pmean(s, DATA)
                           if jnp.issubdtype(s.dtype, jnp.floating) else s),
                new_state)
            new_params, new_opt = afn(params, opt, grads, it, ep)
            return new_params, new_state, new_opt, loss

        opt_spec = {k: {pk: v for pk, v in d.items()}
                    for k, d in pspec.items()}
        sharded = shard_map(
            step, self.mesh,
            in_specs=(pspec, P(), opt_spec) + (P(DATA),) * n_batch
            + (P(), P(), P()),
            out_specs=(pspec, P(), opt_spec, P()))
        return jax.jit(sharded, donate_argnums=(0, 1, 2))

    # --- step builders ------------------------------------------------------
    def _build_threshold_step(self):
        from deeplearning4j_tpu.telemetry import health

        gfn = self.model.grad_fn()
        afn = self.model.apply_updates_fn()
        tbptt = self._tbptt
        mode = health.graph_mode()
        if tbptt:
            segments, zero_carries, advance, _ = \
                self.model.tbptt_scan_parts(self._tbptt_seg,
                                            self._tbptt_back)

        def exchange(params, opt, res, grads, loss, new_state, old_state,
                     c, ctot, n, it, ep, tau):
            """The accumulator's per-iteration exchange: reweight for
            ragged shards, encode(grad + residual) -> ±tau flips, psum
            the messages, apply the shared sum (shared by the standard
            and per-segment tBPTT paths). With a health mode the guard
            vector is computed on the SHARED (summed) messages — what the
            updater actually consumes — and SKIP_STEP reverts params/
            state/opt AND the residual."""
            w = c * n / ctot
            grads = _tree_map(lambda g: g * w, grads)
            enc, new_res, sparsity = encode_tree(grads, res, tau)
            # the accumulator's message exchange: one fused collective by
            # default, or reverse-topological size-targeted buckets whose
            # issue order is pinned so the reduce of the last layers'
            # messages overlaps the backward still producing the first
            # layers' (compression.bucketed_psum)
            shared = bucketed_psum(enc, DATA, self.gradient_bucket_bytes)
            new_params, new_opt = afn(params, opt, shared, it, ep)
            loss = jax.lax.psum(loss * c, DATA) / ctot
            new_state = _tree_map(
                lambda s: jax.lax.psum(s * (c / ctot), DATA), new_state)
            vec = None
            if mode:
                vec = health.guard_vector(loss, shared, params=params,
                                          new_params=new_params)
                if mode == "skip":
                    (new_params, new_state, new_opt,
                     new_res) = health.apply_skip(
                        vec, (new_params, new_state, new_opt, new_res),
                        (params, old_state, opt, res))
            return (new_params, new_state, new_opt, new_res, loss,
                    jax.lax.pmean(sparsity, DATA), vec)

        def tbptt_step(params, state, opt, residual, batch, itc, ep,
                       base_key, tau, cvec):
            """Per-SEGMENT threshold exchange inside one compiled scan —
            the reference exchanges every iteration, and tBPTT counts one
            iteration per segment; residuals carry across segments and
            batches."""
            c = cvec[0]
            n = jax.lax.psum(1.0, DATA)
            ctot = jnp.maximum(jax.lax.psum(c, DATA), 1.0)
            res = _tree_map(lambda r: r[0], residual)
            features, labels, fmask, lmask = batch
            segs = tuple(segments(a)
                         for a in (features, labels, fmask, lmask))
            carries = zero_carries(features)

            algo = self.threshold_algorithm

            def body(carry, xs):
                params, state, opt, res, carries, itc, tau_c = carry
                f_s, l_s, fm_s, lm_s = xs
                f_s, l_s, fm_s, lm_s, carries = advance(
                    params, state, carries, f_s, l_s, fm_s, lm_s)
                it, rng = nn_io.step_scalars(itc, base_key)
                rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA))
                loss, new_state, grads, carries = gfn(
                    _local_copy(params), state, f_s, l_s, fm_s, lm_s, rng,
                    carries=carries)
                params, state, opt, res, loss, sparsity, vec = exchange(
                    params, opt, res, grads, loss, new_state, state, c,
                    ctot, n, it, ep, tau_c)
                # per-SEGMENT adaptive tau (the reference's EncodingHandler
                # retunes every iteration; update() is pure jnp by design)
                tau_c = jnp.asarray(algo.update(tau_c, sparsity),
                                    jnp.float32)
                ys = (loss, vec) if mode else loss
                return ((params, state, opt, res, carries, itc + 1, tau_c),
                        ys)

            ((params, state, opt, res, carries, itc, tau),
             ys) = jax.lax.scan(
                body, (params, state, opt, res, carries, itc,
                       jnp.asarray(tau, jnp.float32)), segs)
            out = (params, state, opt, _tree_map(lambda r: r[None], res))
            if mode:
                from deeplearning4j_tpu.telemetry import health as _h

                losses, vecs = ys
                return out + (jnp.mean(losses), tau, _h.combine(vecs))
            return out + (jnp.mean(ys), tau)

        def step(params, state, opt, residual, batch, itc, ep, base_key,
                 tau, cvec):
            if tbptt:
                return tbptt_step(params, state, opt, residual, batch,
                                  itc, ep, base_key, tau, cvec)
            it, rng = nn_io.step_scalars(itc, base_key)
            idx = jax.lax.axis_index(DATA)
            rng = jax.random.fold_in(rng, idx)
            loss, new_state, grads = gfn(_local_copy(params), state, *batch,
                                           rng)
            # ragged batches: gfn normalizes by the LOCAL shard's valid
            # rows; reweight so the summed exchange equals the global
            # per-example average (and all-padding shards contribute 0,
            # including their regularization grads)
            c = cvec[0]
            n = jax.lax.psum(1.0, DATA)
            ctot = jnp.maximum(jax.lax.psum(c, DATA), 1.0)
            res = _tree_map(lambda r: r[0], residual)
            (new_params, new_state, new_opt, new_res, loss,
             sparsity, vec) = exchange(params, opt, res, grads, loss,
                                       new_state, state, c, ctot, n, it,
                                       ep, tau)
            out = (new_params, new_state, new_opt,
                   _tree_map(lambda r: r[None], new_res), loss, sparsity)
            return out + (vec,) if mode else out

        out_specs = (P(), P(), P(), P(DATA), P(), P())
        if mode:
            out_specs = out_specs + (P(),)
        sharded = shard_map(
            step, self.mesh,
            in_specs=(P(), P(), P(), P(DATA), P(DATA), P(), P(), P(), P(),
                      P(DATA)),
            out_specs=out_specs)
        jit_fn = jax.jit(sharded, donate_argnums=(0, 1, 2, 3))
        # scheduler-keyed AOT entry: the message exchange's collective
        # plan (layout + choices) and the threshold algorithm's constants
        # key the executable, so a changed bucket config or retuned
        # algorithm can never silently reuse a stale program — and a
        # fresh wrapper on the same config recompiles nothing
        from deeplearning4j_tpu.comms import scheduler as comms_sched
        from deeplearning4j_tpu.optimize import aot_cache

        plan = comms_sched.plan_for(self.model.params, "all_reduce", DATA,
                                    self.gradient_bucket_bytes)
        alg = aot_cache.graph_signature(self.threshold_algorithm)[:12]
        return aot_cache.wrap(
            jit_fn, self.model._graph_key(),
            f"pw_thresh:n{self.workers}{_proc_token()}"
            f":b{self.gradient_bucket_bytes or 0}:{plan.key_token()}"
            f":alg{alg}{health.cache_tag()}")

    def _build_bucketed_exact_step(self):
        """Exact SHARED_GRADIENTS as an EXPLICIT shard_map exchange: the
        per-shard backward runs locally, the raw gradients all-reduce
        through ``bucketed_psum`` (issue-order-pinned reverse-topological
        buckets — or one fused collective at bucket size 0), and the
        updater applies the global-mean gradient. Semantically identical
        to the default SPMD path (which lets XLA insert one fused
        all-reduce), with the collective schedule under our control so
        communication overlaps the remaining backprop."""
        from deeplearning4j_tpu.telemetry import health

        gfn = self.model.grad_fn()
        afn = self.model.apply_updates_fn()
        bucket = self.gradient_bucket_bytes
        mode = health.graph_mode()

        def step(params, state, opt, batch, itc, ep, base_key, cvec):
            it, rng = nn_io.step_scalars(itc, base_key)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA))
            loss, new_state, grads = gfn(_local_copy(params), state, *batch,
                                           rng)
            # ragged batches: gfn normalized by the LOCAL shard's valid
            # rows; reweight by c/ctot so the bucketed sum equals the
            # global per-example mean (all-padding shards contribute 0)
            c = cvec[0]
            ctot = jnp.maximum(jax.lax.psum(c, DATA), 1.0)
            w = c / ctot
            grads = _tree_map(lambda g: g * w, grads)
            shared = bucketed_psum(grads, DATA, bucket)
            new_params, new_opt = afn(params, opt, shared, it, ep)
            loss = jax.lax.psum(loss * c, DATA) / ctot
            new_state = _tree_map(
                lambda s: (jax.lax.psum(s * w, DATA)
                           if jnp.issubdtype(s.dtype, jnp.floating) else s),
                new_state)
            if mode:
                # guard on the SHARED (post-psum) gradients — exactly what
                # the updater consumed, so a non-finite accumulation on
                # any replica is caught on every replica
                vec = health.guard_vector(loss, shared, params=params,
                                          new_params=new_params)
                if mode == "skip":
                    new_params, new_state, new_opt = health.apply_skip(
                        vec, (new_params, new_state, new_opt),
                        (params, state, opt))
                return new_params, new_state, new_opt, loss, vec
            return new_params, new_state, new_opt, loss

        out_specs = ((P(), P(), P(), P(), P()) if mode
                     else (P(), P(), P(), P()))
        sharded = shard_map(
            step, self.mesh,
            in_specs=(P(), P(), P(), P(DATA), P(), P(), P(), P(DATA)),
            out_specs=out_specs)
        jit_fn = jax.jit(sharded, donate_argnums=(0, 1, 2))
        # plan-keyed AOT entry: the gradient exchange's CollectivePlan
        # digest joins the step key, so a changed bucket layout or
        # collective choice recompiles instead of silently reusing the
        # old schedule's executable (and identical re-instantiations hit)
        from deeplearning4j_tpu.comms import scheduler as comms_sched
        from deeplearning4j_tpu.optimize import aot_cache

        plan = comms_sched.plan_for(self.model.params, "all_reduce", DATA,
                                    bucket)
        return aot_cache.wrap(
            jit_fn, self.model._graph_key(),
            f"pw_bucketed:n{self.workers}{_proc_token()}:b{bucket or 0}"
            f":{plan.key_token()}{health.cache_tag()}")

    def _build_zero_step(self):
        """ZeRO-1 data parallelism as an explicit shard_map exchange:
        the per-shard backward runs locally, gradients REDUCE-SCATTER so
        each shard receives only its 1/n flat slice of the cross-shard
        sum (``compression.bucketed_psum_scatter``, same reverse-
        topological bucket layout as ``bucketed_psum``), the updater +
        regularization run on the local slice of params/moments (they
        are elementwise, so the slice update equals the all-reduce
        path's update bitwise), and the new params ALL-GATHER back to
        replicated (``bucketed_all_gather``). Only the optimizer state
        stays scattered — the 1/n-per-device memory footprint that lets
        a model train when moments for the whole net don't fit one chip.

        Norm-based GradientNormalization needs full-tensor norms; those
        come from one extra psum of per-leaf squared sums (exact math,
        but the reduction ORDER differs from the dense path, so bit-
        identity holds for elementwise/no normalization — the default —
        and allclose otherwise)."""
        from deeplearning4j_tpu.conf.layers import GradientNormalization
        from deeplearning4j_tpu.optimize import aot_cache, solver
        from deeplearning4j_tpu.telemetry import health

        m = self.model
        gfn = m.grad_fn()
        bucket = self.gradient_bucket_bytes
        mode = health.graph_mode()
        pz = self._zero_pspec
        confs = dict(self._layer_confs())
        layer_keys = sorted(m.params)          # jax dict-flatten order
        gn_layers = {
            k for k in layer_keys
            if getattr(confs.get(k), "gradient_normalization", None)
            not in (None, GradientNormalization.NONE)}

        def norm_slices(k, gdict, sq):
            """solver.normalize_layer_gradients on flat slices, per-
            tensor/per-layer norms supplied from the psum'd squared
            sums ``sq`` ({param_key: full-tensor sq sum})."""
            conf = confs[k]
            gn = conf.gradient_normalization
            thr = getattr(conf, "gradient_normalization_threshold", 1.0)
            if gn is GradientNormalization.CLIP_ELEMENTWISE_ABSOLUTE_VALUE:
                return {pk: jnp.clip(g, -thr, thr)
                        for pk, g in gdict.items()}
            if gn is GradientNormalization.RENORMALIZE_L2_PER_PARAM_TYPE:
                return {pk: g / (jnp.sqrt(sq[pk]) + 1e-12)
                        for pk, g in gdict.items()}
            lnorm = jnp.sqrt(sum(sq.values()) + 1e-24)
            if gn is GradientNormalization.RENORMALIZE_L2_PER_LAYER:
                return {pk: g / lnorm for pk, g in gdict.items()}
            if gn is GradientNormalization.CLIP_L2_PER_LAYER:
                scale = jnp.minimum(1.0, thr / lnorm)
                return {pk: g * scale for pk, g in gdict.items()}
            if gn is GradientNormalization.CLIP_L2_PER_PARAM_TYPE:
                return {pk: g * jnp.minimum(
                    1.0, thr / (jnp.sqrt(sq[pk]) + 1e-12))
                    for pk, g in gdict.items()}
            raise ValueError(f"unhandled GradientNormalization {gn}")

        def sq_sums(tree_slices, keys):
            """psum'd full-tensor squared sums of the scattered shared
            gradient, one scalar per (layer, param) pair in ``keys`` —
            slices partition the tensor, so the cross-shard sum of
            slice squares IS the full tensor's squared sum."""
            f32 = jnp.float32
            loc = jnp.stack([
                jnp.sum(tree_slices[k][pk].astype(f32) ** 2)
                for k, pk in keys]) if keys else jnp.zeros((0,), f32)
            return jax.lax.psum(loc, DATA)

        def step(params, state, opt_slices, batch, itc, ep, base_key,
                 cvec):
            it, rng = nn_io.step_scalars(itc, base_key)
            idx = jax.lax.axis_index(DATA)
            rng = jax.random.fold_in(rng, idx)
            loss, new_state, grads = gfn(_local_copy(params), state, *batch,
                                           rng)
            # ragged-batch reweight: identical to the bucketed exact step
            c = cvec[0]
            ctot = jnp.maximum(jax.lax.psum(c, DATA), 1.0)
            w = c / ctot
            grads = _tree_map(lambda g: g * w, grads)
            # the ZeRO first half: every shard receives its slice of the
            # summed gradient — 1/n of the all-reduce payload
            gslices = bucketed_psum_scatter(pz.flat_padded(grads), DATA,
                                            bucket)
            pslices = pz.local_slices(params, idx)
            gn_keys = [(k, pk) for k in layer_keys if k in gn_layers
                       for pk in sorted(m.params[k])]
            gn_map = {}
            if gn_keys:
                gn_sq = sq_sums(gslices, gn_keys)
                gn_map = {kp: gn_sq[i] for i, kp in enumerate(gn_keys)}
            new_p_slices, new_o_slices = {}, {}
            for k in layer_keys:
                layer = confs[k]
                upd = m._updater_for(k if self._is_graph else int(k))
                lr = upd.current_lr(it, ep)
                g_k = gslices[k]
                if k in gn_layers:
                    g_k = norm_slices(
                        k, g_k, {pk: gn_map[(k, pk)] for pk in g_k})
                # regularization + updater are elementwise: the slice
                # update equals the corresponding elements of the dense
                # path's update exactly
                new_p_slices[k], new_o_slices[k] = \
                    solver.apply_updater_to_layer(
                        layer, upd, pslices[k], g_k, opt_slices[k], lr,
                        it, ep)
            # the ZeRO second half: updated param slices all-gather back
            # to the replicated tree the next forward consumes
            new_params = pz.assemble(new_p_slices, DATA, bucket)
            loss = jax.lax.psum(loss * c, DATA) / ctot
            new_state = _tree_map(
                lambda s: (jax.lax.psum(s * w, DATA)
                           if jnp.issubdtype(s.dtype, jnp.floating) else s),
                new_state)
            if mode:
                # guard on the SHARED gradient, reconstructed from the
                # scattered slices' psum'd squared sums — same vector
                # layout/semantics as the dense paths
                keys = health.bucket_keys(m.params)
                bsq = sq_sums(gslices,
                              [(k, pk) for k in keys
                               for pk in sorted(m.params.get(k, {}))])
                off, bucket_sq = 0, []
                for k in keys:
                    n_k = len(m.params.get(k, {}))
                    bucket_sq.append(jnp.sum(bsq[off:off + n_k]))
                    off += n_k
                vec = health.guard_vector_from_sq(
                    loss, bucket_sq, params=params, new_params=new_params)
                if mode == "skip":
                    (new_params, new_state,
                     new_o_slices) = health.apply_skip(
                        vec, (new_params, new_state, new_o_slices),
                        (params, state, opt_slices))
                return new_params, new_state, new_o_slices, loss, vec
            return new_params, new_state, new_o_slices, loss

        opt_spec = _tree_map(lambda _: P(DATA), self._opt)
        out_specs = ((P(), P(), opt_spec, P(), P()) if mode
                     else (P(), P(), opt_spec, P()))
        sharded = shard_map(
            step, self.mesh,
            in_specs=(P(), P(), opt_spec, P(DATA), P(), P(), P(),
                      P(DATA)),
            out_specs=out_specs)
        jit_fn = jax.jit(sharded, donate_argnums=(0, 1, 2))
        # sharding- AND plan-keyed AOT entry: the scattered layout
        # (worker count) plus both exchange plans — the gradient
        # reduce-scatter and the param all-gather, each carrying bucket
        # layout + collective choice in its digest — key the executable,
        # so ZeRO and all-reduce programs for the same graph never
        # collide, a changed schedule never reuses a stale executable,
        # and a fresh wrapper on the same mesh recompiles nothing. The
        # PRG205 audit resolves the digests back to the plans to verify
        # the compiled collective sequence.
        rs_plan, ag_plan = pz.exchange_plans(DATA, bucket)
        return aot_cache.wrap(
            jit_fn, m._graph_key(),
            f"pw_zero:n{self.workers}{_proc_token()}:b{bucket or 0}"
            f":{rs_plan.key_token()}"
            f":{ag_plan.key_token()}{health.cache_tag()}")

    def _build_averaging_step(self):
        from deeplearning4j_tpu.telemetry import health

        mode = health.graph_mode()
        if self._tbptt:
            run = self.model.tbptt_scan_fn(self._tbptt_seg,
                                           self._tbptt_back, guards=mode)
        else:
            raw = self.model.train_step_fn(guards=mode)

        def step(params, state, opt, batch, itc, ep, base_key, cvec):
            idx = jax.lax.axis_index(DATA)
            p = _tree_map(lambda x: x[0], params)
            s = _tree_map(lambda x: x[0], state)
            o = _tree_map(lambda x: x[0], opt)
            vec = None
            if self._tbptt:
                # per-replica rng stream via the folded base key; the
                # runner derives per-segment scalars itself
                key = jax.random.fold_in(base_key, idx)
                out = run(p, s, o, *batch, itc, ep, key)
                new_p, new_s, new_o, _, loss = out[:5]
                if mode:
                    vec = out[5]
            else:
                it, rng = nn_io.step_scalars(itc, base_key)
                rng = jax.random.fold_in(rng, idx)
                out = raw(p, s, o, *batch, it, ep, rng)
                new_p, new_s, new_o, loss = out[:4]
                if mode:
                    vec = out[4]
            # an all-padding replica (final ragged batch smaller than the
            # worker count) must not move: regularization/momentum would
            # otherwise update it and later be averaged into real replicas
            ok = cvec[0] > 0
            new_p = _tree_map(lambda a, b: jnp.where(ok, a, b), new_p, p)
            new_s = _tree_map(lambda a, b: jnp.where(ok, a, b), new_s, s)
            new_o = _tree_map(lambda a, b: jnp.where(ok, a, b), new_o, o)
            c = cvec[0]
            loss = (jax.lax.psum(loss * c, DATA)
                    / jnp.maximum(jax.lax.psum(c, DATA), 1.0))
            out = (_tree_map(lambda x: x[None], (new_p, new_s, new_o))
                   + (loss,))
            if mode:
                # per-replica guards (the raw step already applied its
                # in-graph SKIP per replica); any replica's anomaly is
                # the step's anomaly — padding replicas report 0
                vec = jnp.where(ok, vec, jnp.zeros_like(vec))
                out = out + (health.combine_across(vec, DATA),)
            return out

        out_specs = (P(DATA), P(DATA), P(DATA), P())
        if mode:
            out_specs = out_specs + (P(),)
        sharded = shard_map(
            step, self.mesh,
            in_specs=(P(DATA), P(DATA), P(DATA), P(DATA), P(), P(), P(),
                      P(DATA)),
            out_specs=out_specs)
        return jax.jit(sharded, donate_argnums=(0, 1, 2))

    def _build_average_fn(self):
        avg_upd = self.average_updaters
        if self._explicit_exchange:
            return self._build_bucketed_average_fn()

        def average(params, state, opt):
            def bmean(x):
                return jnp.broadcast_to(x.mean(axis=0, keepdims=True),
                                        x.shape)

            params = _tree_map(bmean, params)
            state = _tree_map(bmean, state)
            if avg_upd:
                opt = _tree_map(bmean, opt)
            return params, state, opt

        return jax.jit(average, donate_argnums=(0, 1, 2))

    def _build_bucketed_average_fn(self):
        """The periodic barrier-average as an explicit shard_map exchange:
        each shard contributes its local replica sum and the cross-replica
        mean arrives through ``bucketed_psum`` — the same issue-order-
        pinned bucket schedule as the gradient paths, applied to the
        AVERAGING collective."""
        avg_upd = self.average_updaters
        total = float(self.workers)
        bucket = self.gradient_bucket_bytes

        def average(params, state, opt):
            def local_sum(tree):
                return _tree_map(lambda x: jnp.sum(x, axis=0), tree)

            group = (local_sum(params), local_sum(state))
            if avg_upd:
                group = group + (local_sum(opt),)
            shared = bucketed_psum(group, DATA, bucket)

            def back(mean_tree, like):
                return _tree_map(
                    lambda m, x: _vary_on(
                        jnp.broadcast_to((m / total)[None],
                                         x.shape).astype(x.dtype), (DATA,)),
                    mean_tree, like)

            new_params = back(shared[0], params)
            new_state = back(shared[1], state)
            new_opt = back(shared[2], opt) if avg_upd else opt
            return new_params, new_state, new_opt

        sharded = shard_map(
            average, self.mesh,
            in_specs=(P(DATA), P(DATA), P(DATA)),
            out_specs=(P(DATA), P(DATA), P(DATA)))
        jit_fn = jax.jit(sharded, donate_argnums=(0, 1, 2))
        # plan-keyed like the gradient exchanges: the AVERAGING barrier-
        # average rides the same scheduler, and its plan digest keys the
        # executable
        from deeplearning4j_tpu.comms import scheduler as comms_sched
        from deeplearning4j_tpu.optimize import aot_cache

        m = self.model
        group = (m.params, m.state) + ((m.opt_state,) if avg_upd else ())
        plan = comms_sched.plan_for(group, "all_reduce", DATA, bucket)
        return aot_cache.wrap(
            jit_fn, m._graph_key(),
            f"pw_avg:n{self.workers}{_proc_token()}:b{bucket or 0}:u{int(avg_upd)}"
            f":{plan.key_token()}")

    # --- training loop ------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1):
        """Train over the mesh (reference ``ParallelWrapper#fit``)."""
        from deeplearning4j_tpu.datasets.prefetch import AsyncDataSetIterator
        from deeplearning4j_tpu.nn.multilayer import _as_iterator

        m = self.model
        if self._is_graph:
            if labels is not None:
                from deeplearning4j_tpu.datasets.dataset import DataSet

                data = DataSet(np.asarray(data), np.asarray(labels))
            iterator = data if hasattr(data, "reset") else None
            if iterator is None:
                from deeplearning4j_tpu.datasets.iterators import (
                    ListDataSetIterator,
                )
                iterator = ListDataSetIterator([data])
        else:
            iterator = _as_iterator(data, labels)
        already_async = isinstance(iterator, AsyncDataSetIterator)
        if self.fused_steps > 1 and getattr(
                iterator, "stack_batches", 0) != self.fused_steps:
            from deeplearning4j_tpu.datasets.prefetch import (
                StackBatchIterator,
            )

            # host-side stacking only: the wrapper owns device placement
            # (the stack is sharded over the mesh, not default-device-
            # put). Wrapped INSIDE the async prefetcher below so the
            # K-batch np.stack runs on the prefetch thread, not in the
            # dispatch loop's host gap (a user-provided async iterator
            # keeps its single prefetch thread; the stack then runs
            # consumer-side rather than double-wrapping).
            iterator = StackBatchIterator(iterator, self.fused_steps)
        if self.prefetch_buffer > 0 and not already_async \
                and not isinstance(iterator, AsyncDataSetIterator):
            iterator = AsyncDataSetIterator(
                iterator, queue_size=self.prefetch_buffer)
        from deeplearning4j_tpu.telemetry import flightrec

        self._setup()
        # gather-on-save hook: while this wrapper owns the live training
        # trees, any write_model on the wrapped model (CheckpointListener,
        # TrainingSession snapshots) first gathers them back — a
        # checkpoint is never a stale or shard-local view
        import weakref

        m._live_trainer = weakref.ref(self)
        # each fit() may use a different batch size; the multi-host shape
        # lock applies within one fit only
        self._mp_target = None
        telemetry.host_gap_reset()
        try:
            with flightrec.flight_recorder(model=m):
                for _ in range(epochs):
                    for lst in m.listeners:
                        lst.on_epoch_start(m, m.epoch)
                    for ds in iterator:
                        self._fit_batch(ds)
                    iterator.reset()
                    for lst in m.listeners:
                        lst.on_epoch_end(m, m.epoch)
                    m.epoch += 1
        finally:
            telemetry.host_gap_stop()
            self._write_back()
            # disarm the gather-on-save hook: outside fit the model's
            # host arrays are authoritative again (a later solo
            # model.fit() must not be clobbered by these device trees
            # at the next write_model)
            m._live_trainer = None
        return m

    # --- health-layer rollback hooks ---------------------------------------
    def _health_snapshot(self):
        """Device copies of the wrapper's live training trees (the
        donated step buffers can never invalidate them) + the model
        counters — what ROLLBACK restores mid-fit."""
        copy = lambda t: _tree_map(jnp.copy, t)  # noqa: E731
        snap = {"params": copy(self._params), "state": copy(self._state),
                "opt": copy(self._opt),
                "iteration": int(self.model.iteration),
                "epoch": int(self.model.epoch)}
        if self._residual is not None:
            snap["residual"] = copy(self._residual)
            snap["tau"] = self._tau
        return snap

    def _health_restore(self, snap):
        copy = lambda t: _tree_map(jnp.copy, t)  # noqa: E731
        # fresh copies: the snapshot must survive repeated rollbacks
        # (the next step donates whatever trees it is handed)
        self._params = copy(snap["params"])
        self._state = copy(snap["state"])
        self._opt = copy(snap["opt"])
        if "residual" in snap:
            self._residual = copy(snap["residual"])
            self._tau = snap["tau"]
        self.model.iteration = snap["iteration"]
        self.model.epoch = snap["epoch"]
        self._synced = False  # rolled-back trees differ from host arrays
        # both score mirrors point at the rolled-back step's loss — drop
        # them (matches checkpoint.restore_training_state for networks)
        self._score_dev = None
        self._score_cache = None
        self.model._score_dev = None
        self.model._score_cache = None

    def _record_exchange(self, did_average: bool = False, steps: int = 1):
        """Telemetry: count this step's cross-replica payload (the
        per-shard gradient tree — what one fused all-reduce or the bucket
        chain moves; an upper bound under expert_parallel, whose sharded
        leaves stay local). The bucket layout is recorded once per
        schedule."""
        m = self.model
        if self.training_mode is TrainingMode.AVERAGING:
            # params (+state, + optionally opt) cross only on averaging
            # iterations, not every step
            if did_average:
                group = (m.params, m.state) + (
                    (m.opt_state,) if self.average_updaters else ())
                layout = bucket_layout(group, self.gradient_bucket_bytes
                                       if self._explicit_exchange else None)
                telemetry.record_collective("average", sum(layout),
                                            len(layout))
            return
        if self._zero:
            # ZeRO's two collectives per step — gradient reduce-scatter
            # and param all-gather — on the scheduler's bucket layout
            # over the flat-padded tree. Counters record the LOGICAL
            # per-shard payload of each (docs/collectives.md). Same
            # counter series as every other exchange
            # (dl4j_collective_bytes/ops + the bucket-layout histogram),
            # new op labels — pinned by test_sharding.
            layout = getattr(self, "_zero_layout", None)
            if layout is None:
                layout = self._zero_layout = self._zero_pspec.layout_bytes(
                    self.gradient_bucket_bytes)
                telemetry.record_bucket_layout("grad_reduce_scatter",
                                               layout)
                telemetry.record_bucket_layout("param_all_gather", layout)
            for op in ("grad_reduce_scatter", "param_all_gather"):
                telemetry.record_collective(op, sum(layout) * steps,
                                            len(layout) * steps)
            return
        layout = getattr(self, "_grad_layout", None)
        if layout is None:
            if self._plan is not None:
                # DP x TP: gradients of model-sharded leaves cross the
                # data axis as 1/t shards — count the PER-SHARD payload
                # the all-reduce actually moves, not the dense tree
                # (XLA-inserted activation collectives are not counted)
                from deeplearning4j_tpu.sharding import rules as _rules

                layout = [_rules.bytes_per_device(
                    m.params, self._plan.param_specs(m.params),
                    self.mesh)]
            else:
                layout = bucket_layout(m.params,
                                       self.gradient_bucket_bytes)
            self._grad_layout = layout
            op = ("threshold_psum" if self.threshold_algorithm is not None
                  else "grad_psum")
            telemetry.record_bucket_layout(op, layout)
        telemetry.record_collective(
            "threshold_psum" if self.threshold_algorithm is not None
            else "grad_psum", sum(layout) * steps, len(layout) * steps)

    def _fit_batch(self, ds):
        from deeplearning4j_tpu.resilience import faults

        faults.fault_point("train.step")  # preemption/crash injection site
        k = int(getattr(ds, "fused_stack", 0) or 0)
        if k > 1:
            return self._fit_batch_fused(ds, k)
        m = self.model
        with telemetry.span(telemetry.PHASE_INGEST):
            batch = self._prep(ds)
            rows = self._batch_rows(batch)
            # multi-process: this batch is the LOCAL partition; pad/split
            # over the local worker count, then assemble the global
            # sharded batch
            target = (math.ceil(rows / self.local_workers)
                      * self.local_workers)
            if jax.process_count() > 1:
                # SPMD: every host must present identically-shaped local
                # batches. Lock the shape to the first batch's padded size
                # and pad tails up to it (unequal partitions beyond that
                # are a documented contract violation -> clear error, not
                # a hang).
                if self._mp_target is None:
                    self._mp_target = target
                if target > self._mp_target:
                    raise ValueError(
                        f"multi-host batch of {rows} rows exceeds the "
                        f"established per-host batch of {self._mp_target}; "
                        f"all hosts must feed equal-size batches "
                        f"(repartition your data as Spark does in the "
                        f"reference)")
                target = self._mp_target
            batch = self._data_sharded(mesh_mod.pad_leading(batch, target))
            counts = mesh_mod.shard_valid_counts(rows, self.local_workers)
            cvec = self._data_sharded(jnp.asarray(counts))
        # numpy scalars stage with the call — python ints or eager
        # jnp.asarray/fold_in would each be a dispatch of their own
        itc = np.int32(m.iteration)
        ep = np.float32(m.epoch)
        # tBPTT counts one iteration per SEGMENT (reference semantics)
        inc = (-(-int(jax.tree_util.tree_leaves(batch)[0].shape[1])
                 // self._tbptt_seg) if self._tbptt else 1)

        from deeplearning4j_tpu.telemetry import health

        mode = getattr(self, "_health_mode", "")
        gvec = None
        did_avg = False
        with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
            telemetry.host_gap_close()
            if self.training_mode is TrainingMode.AVERAGING:
                out = self._step(
                    self._params, self._state, self._opt, batch, itc, ep,
                    m._base_key, cvec)
                (self._params, self._state, self._opt, loss) = out[:4]
                if mode:
                    gvec = out[4]
                did_avg = ((m.iteration + inc) // self.averaging_frequency
                           > m.iteration // self.averaging_frequency)
                if did_avg:
                    self._params, self._state, self._opt = self._avg(
                        self._params, self._state, self._opt)
            elif self.threshold_algorithm is not None:
                tau = np.float32(self._tau)
                out = self._step(self._params, self._state,
                                 self._opt, self._residual, batch,
                                 itc, ep, m._base_key, tau, cvec)
                (self._params, self._state, self._opt, self._residual,
                 loss, feedback) = out[:6]
                if mode:
                    gvec = out[6]
                # the adaptive threshold needs feedback on host — this mode
                # inherently syncs per step (as the reference's
                # EncodingHandler feedback loop does). tBPTT steps retune
                # tau per SEGMENT inside the scan and return the final tau
                # directly.
                if self._tbptt:
                    self._tau = float(feedback)
                else:
                    self._tau = float(self.threshold_algorithm.update(
                        self._tau, float(feedback)))
            elif self._explicit_exchange or self._zero:
                out = self._step(
                    self._params, self._state, self._opt, batch, itc, ep,
                    m._base_key, cvec)
                (self._params, self._state, self._opt, loss) = out[:4]
                if mode:
                    gvec = out[4]
            else:
                if self.expert_parallel and self._step is None:
                    self._step = self._build_expert_step(len(batch))
                out = self._step(self._params, self._state, self._opt,
                                 *batch, itc, ep, m._base_key)
                if self.expert_parallel:
                    # expert-sharded grads stay local to their shard; the
                    # guard here covers the loss (a NaN gradient reaches
                    # the loss within one step through the shared layers)
                    self._params, self._state, self._opt, loss = out[:4]
                    if mode:
                        gvec = health.loss_guard(loss)
                elif self._tbptt:
                    (self._params, self._state, self._opt, _,
                     loss) = out[:5]
                    if mode:
                        gvec = out[5]
                else:
                    self._params, self._state, self._opt, loss = out[:4]
                    if mode:
                        gvec = out[4]
            _sp.set_result(loss)
        with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
            # the gradient all-reduce runs INSIDE the compiled step and the
            # psum'd loss already depends on it, so the separable host-side
            # residue here is the wait for the updated params tree (~0;
            # use XProf for the kernel-level collective/compute split)
            _sp.set_result(self._params)
        # post-span: under enable(sync=True) the gap excludes device time
        telemetry.host_gap_open()
        if telemetry.enabled():
            telemetry.record_step("parallel", rows)
            self._record_exchange(did_avg)

        self._score_dev = loss
        self._score_cache = None
        m._score_dev = loss
        m._score_cache = None
        self._synced = False  # device trees moved past the host arrays
        m.iteration += inc  # listeners see iteration == next-to-run
        if mode:
            keys = (health.bucket_keys(m.params)
                    if not self.expert_parallel else ("all",))
            # expert-parallel applies no in-graph skip (loss-only guard):
            # never report its anomalous updates as discarded
            health.observe_step(
                self, "parallel", m.iteration - 1, m.epoch, loss, gvec,
                keys, batch=batch,
                rng_seed=int(getattr(m.conf, "seed", 0) or 0),
                skipped=False if self.expert_parallel else None)
        for lst in m.listeners:
            lst.iteration_done(m, m.iteration - 1, m.epoch, loss)

    def _prep_fused(self, ds):
        """Stacked [K, B, ...] batch arrays for the fused SPMD step, with
        labels masks MATERIALIZED (ones [K, B]) — axis-1 padding must
        zero them so padded rows contribute nothing, same contract as
        ``pad_leading`` on the single-step path."""
        m = self.model
        if self._is_graph:
            f, l, fm, lm = m._prep_batch(ds, lazy_lmasks=True)
            lm = tuple(jnp.ones(lab.shape[:2], m._dtype) if mm is None
                       else mm for mm, lab in zip(lm, l))
            return f, l, fm, lm
        f, l, fm, lm = m._batch_arrays(ds, lazy_lmask=True)
        if lm is None:
            lm = jnp.ones(f.shape[:2], m._dtype)
        return f, l, fm, lm

    def _fit_batch_fused(self, ds, k: int):
        """K fused optimization steps per dispatch over the mesh: the
        model's ``fused_scan_fn`` jitted with the stack's PER-STEP batch
        axis (axis 1) sharded ``P(None, 'data')`` and params replicated —
        each scan step is the same SPMD-partitioned step as the K=1 exact
        path (XLA inserts the per-step gradient all-reduce), so K=1 and
        K=K train bit-identically while the host pays one dispatch per K
        steps."""
        from deeplearning4j_tpu.telemetry import health

        if (self.training_mode is not TrainingMode.SHARED_GRADIENTS
                or self.threshold_algorithm is not None
                or self.expert_parallel or self._explicit_exchange
                or self._tbptt):
            # a hand-fed stacked batch must not silently train the exact
            # SPMD math under a different configured mode
            raise ValueError(
                "fused [K, B, ...] batches require the exact "
                "SHARED_GRADIENTS SPMD path (see fused_steps)")
        m = self.model
        mode = getattr(self, "_health_mode", "")
        with telemetry.span(telemetry.PHASE_INGEST):
            batch = self._prep_fused(ds)
            rows = jax.tree_util.tree_leaves(batch)[0].shape[1]
            target = (math.ceil(rows / self.local_workers)
                      * self.local_workers)
            if jax.process_count() > 1:
                # same per-fit shape lock as the single-step path: every
                # host must present identically-shaped [K, B, ...] local
                # stacks (SPMD), tails padding up to the locked size
                if self._mp_target is None:
                    self._mp_target = target
                if target > self._mp_target:
                    raise ValueError(
                        f"multi-host fused stack of {rows} per-step rows "
                        f"exceeds the established per-host batch of "
                        f"{self._mp_target}; all hosts must feed "
                        f"equal-size super-batches")
                target = self._mp_target
            batch = _pad_axis1(batch, target)
            sh = NamedSharding(self.mesh, P(None, DATA))
            if jax.process_count() > 1:
                # each host contributes its LOCAL [K, B_local, ...]
                # partition of the global stacked super-batch
                batch = _tree_map(
                    lambda x: jax.make_array_from_process_local_data(
                        sh, np.asarray(x)), batch)
            else:
                batch = _tree_map(lambda x: jax.device_put(x, sh), batch)
        if self._fused_step is None or self._fused_step_k != k:
            self._fused_step = jax.jit(
                m.fused_scan_fn(k, guards=mode), donate_argnums=(0, 1, 2))
            self._fused_step_k = k
        itc = np.int32(m.iteration)
        ep = np.float32(m.epoch)
        gvecs = None
        with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
            telemetry.host_gap_close(k)
            out = self._fused_step(self._params, self._state, self._opt,
                                   *batch, itc, ep, m._base_key)
            (self._params, self._state, self._opt, _, losses) = out[:5]
            if mode:
                gvecs = out[5]
            _sp.set_result(losses)
        with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
            _sp.set_result(self._params)  # in-graph collective (see above)
        telemetry.host_gap_open()  # post-span: sync mode excludes device
        if telemetry.enabled():
            telemetry.record_step("parallel", int(rows) * k, steps=k)
            self._record_exchange(steps=k)  # K in-scan all-reduces
        loss = losses[-1]
        self._score_dev = loss
        self._score_cache = None
        m._score_dev = loss
        m._score_cache = None
        self._synced = False  # device trees moved past the host arrays
        cur = m.iteration
        m.iteration += k
        if mode:
            health.observe_fused(
                self, "parallel", cur, m.epoch, losses, gvecs,
                health.bucket_keys(m.params), k, batch=batch,
                rng_seed=int(getattr(m.conf, "seed", 0) or 0))
        if m.listeners:
            for j in range(k):
                loss_j = losses[j]
                for lst in m.listeners:
                    lst.iteration_done(m, cur + j, m.epoch, loss_j)
        return loss

    def sync_model(self):
        """Gather the live device training trees back onto the wrapped
        model WITHOUT ending training — the gather-on-save hook
        ``serializer.write_model`` calls through ``model._live_trainer``
        so a checkpoint taken mid-``fit`` serializes the CURRENT
        (possibly ZeRO-scattered or TP-sharded) state as plain full host
        arrays, restorable onto any mesh. No-op before the first
        ``fit`` stages anything."""
        self._write_back()
        return self.model

    def _write_back(self):
        """Publish trained params back onto the wrapped model (reference:
        fit() ends with params <- averaged replicas / shared replica 0).
        Sharded trees (ZeRO opt slices, partition-rule placements)
        gather to full host arrays here — checkpoints are always
        mesh-shape-agnostic."""
        if self._params is None or self._synced:
            return
        self._synced = True
        m = self.model
        # host_gather handles pod-spanning trees: a leaf whose shards
        # live on remote hosts (ZeRO opt slices, TP-sharded params)
        # replicates through one compiled identity before the read;
        # fully-addressable leaves keep the direct device_get bitwise
        host = mesh_mod.host_gather
        if self.training_mode is TrainingMode.AVERAGING:
            m.params = host(self._collect(self._params))
            m.state = host(self._collect(self._state))
            m.opt_state = host(self._collect(self._opt))
        else:
            m.params = host(self._params)
            m.state = host(self._state)
            if self._zero:
                # scattered flat slices -> original shapes (gather_host
                # pulls every shard's slice, cross-host when needed)
                m.opt_state = self._zero_ospec.gather_host(self._opt)
            else:
                m.opt_state = host(self._opt)
        m.params = _tree_map(jnp.asarray, m.params)
        m.state = _tree_map(jnp.asarray, m.state)
        m.opt_state = _tree_map(jnp.asarray, m.opt_state)
        # model-level cached jitted fns were built for unsharded inputs;
        # they remain valid (shardings are input-driven), nothing to clear
