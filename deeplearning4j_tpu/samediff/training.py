"""SameDiff training (reference ``TrainingConfig`` + ``TrainingSession`` —
SURVEY.md §3.3).

Where the reference's ``TrainingSession#trainingIteration`` executes the
graph op-by-op then applies regularization + ``GradientUpdater`` per
variable (one JNI crossing each), here one jitted ``train_step`` fuses
forward + ``jax.grad`` backward + regularization + updater into a single
XLA program, compiled once and reused across batches/epochs.
"""

from __future__ import annotations

import dataclasses
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.conf.updaters import IUpdater, Sgd


@dataclasses.dataclass
class TrainingConfig:
    """Reference ``org.nd4j.autodiff.samediff.TrainingConfig``."""
    updater: IUpdater = dataclasses.field(default_factory=Sgd)
    data_set_feature_mapping: tp.Sequence[str] = ()
    data_set_label_mapping: tp.Sequence[str] = ()
    data_set_feature_mask_mapping: tp.Sequence[str] = ()
    data_set_label_mask_mapping: tp.Sequence[str] = ()
    loss_variables: tp.Sequence[str] = ()
    regularization: tp.Sequence = ()  # conf.regularization.* instances
    minimize: bool = True

    class Builder:
        def __init__(self):
            self._cfg = TrainingConfig()

        def updater(self, u):
            self._cfg.updater = u
            return self

        def data_set_feature_mapping(self, *names):
            self._cfg.data_set_feature_mapping = list(names)
            return self

        def data_set_label_mapping(self, *names):
            self._cfg.data_set_label_mapping = list(names)
            return self

        def data_set_feature_mask_mapping(self, *names):
            self._cfg.data_set_feature_mask_mapping = list(names)
            return self

        def data_set_label_mask_mapping(self, *names):
            self._cfg.data_set_label_mask_mapping = list(names)
            return self

        def loss_variables(self, *names):
            self._cfg.loss_variables = [
                n if isinstance(n, str) else n.name for n in names]
            return self

        def regularization(self, *regs):
            self._cfg.regularization = list(regs)
            return self

        def minimize(self, m=True):
            self._cfg.minimize = m
            return self

        def build(self):
            return self._cfg

    @staticmethod
    def builder() -> "TrainingConfig.Builder":
        return TrainingConfig.Builder()


class History:
    """Reference ``org.nd4j.autodiff.listeners.records.History`` (thin).

    Losses accumulate as device scalars and materialize to floats on read
    — a per-step ``float()`` would force a full host sync per iteration.
    The pending list self-flushes past
    ``_FLUSH_AT`` so a long unobserved run doesn't pin one device buffer
    per step (one stacked transfer, not a sync per scalar)."""

    _FLUSH_AT = 512

    def __init__(self):
        self._pending: list = []
        self._curve: list[float] = []

    def append(self, loss):
        self._pending.append(loss)
        if len(self._pending) >= self._FLUSH_AT:
            self._flush()

    def _flush(self):
        if self._pending:
            self._curve.extend(
                np.asarray(jnp.stack(self._pending)).tolist())
            self._pending.clear()

    @property
    def loss_curve(self) -> list[float]:
        self._flush()
        return self._curve


def make_train_step(sd, cfg: TrainingConfig):
    """Build the pure jitted step:
    (trainables, opt_state, t, placeholders) -> (trainables', opt_state',
    loss). Regularization mirrors the reference's apply-before/after-updater
    split (``Regularization.ApplyStep``)."""
    loss_names = tuple(cfg.loss_variables or sd.loss_variables)
    if not loss_names:
        raise ValueError("TrainingConfig has no loss variables and none "
                         "were marked on the graph")
    trainable_names = tuple(sd.trainable_variables())
    fn = sd.make_function(loss_names)
    updater = cfg.updater
    regs = tuple(cfg.regularization)
    sign = 1.0 if cfg.minimize else -1.0

    def loss_fn(trainables, frozen, placeholders):
        merged = dict(frozen)
        merged.update(trainables)
        outs = fn(merged, placeholders)
        return sign * sum(jnp.sum(v) for v in outs.values())

    from deeplearning4j_tpu.telemetry import health

    mode = health.graph_mode()

    def train_step(trainables, frozen, opt_state, t, placeholders):
        loss, grads = jax.value_and_grad(loss_fn)(trainables, frozen,
                                                  placeholders)
        lr = updater.current_lr(t, 0)
        new_params, new_state = {}, {}
        for n in trainable_names:
            g, p = grads[n], trainables[n]
            for r in regs:
                g = r.apply_before_updater(g, p, lr)
            upd, new_state[n] = updater.update_leaf(g, opt_state[n], lr, t,
                                                    param=p)
            for r in regs:
                upd = r.apply_after_updater(upd, p, lr)
            new_params[n] = p - upd
        if mode:
            vec = health.guard_vector(loss, grads, params=trainables,
                                      new_params=new_params)
            if mode == "skip":
                new_params, new_state = health.apply_skip(
                    vec, (new_params, new_state), (trainables, opt_state))
            return new_params, new_state, loss, vec
        return new_params, new_state, loss

    from deeplearning4j_tpu.optimize import aot_cache

    # the executable bakes in the updater, regularization, minimize sign
    # and the loss-variable subset — they MUST be part of the key, or two
    # TrainingConfigs over the same graph would share one compiled step
    # with the first config's lr/sign/loss frozen in (the health guard
    # mode joins the key the same way via cache_tag)
    cfg_key = aot_cache.graph_signature(
        (repr(updater), tuple(map(repr, regs)), sign, loss_names),
        fallback=cfg)
    # donate trainables + opt state (argnums 0, 2): every step's outputs
    # reuse the previous step's buffers instead of allocating a second
    # copy of the model — the same aliasing contract the network train
    # steps carry (PRG201). fit() stages per-fit copies so ``sd.arrays``
    # never aliases a donated buffer.
    step = aot_cache.wrap(jax.jit(train_step, donate_argnums=(0, 2)),
                          "sd:" + sd.graph_signature(),
                          f"train_step:d02:{cfg_key}{health.cache_tag()}")
    return step, trainable_names, loss_names


def fit(sd, iterator=None, epochs: int = 1, features=None, labels=None):
    """Reference ``SameDiff#fit(DataSetIterator, epochs)``. Also accepts
    raw (features, labels) arrays for single-dataset fitting."""
    cfg = sd.training_config
    if cfg is None:
        raise ValueError("call set_training_config() first")
    # cache the jitted step inside _fn_cache (cleared on graph mutation):
    # rebuilding per fit() call would retrace/recompile every time. Keyed
    # by cfg IDENTITY (the entry holds the cfg, so its id can't be
    # recycled); set_training_config() with a new cfg misses naturally.
    # Mutating a TrainingConfig in place between fits is not supported —
    # call set_training_config with a fresh config.
    from deeplearning4j_tpu.telemetry import flightrec, health

    mode = health.graph_mode()
    cached = sd._fn_cache.get("__train_step__")
    if cached is None or cached[0] is not cfg or cached[1] != mode:
        cached = (cfg, mode, make_train_step(sd, cfg))
        sd._fn_cache["__train_step__"] = cached
    step, trainable_names, _ = cached[2]

    # the step DONATES trainables + opt state, so the loop must own its
    # buffers: stage device COPIES at fit entry (one copy per fit, not
    # per step) — ``sd.arrays`` / ``sd._updater_state`` keep their own
    # live arrays until the final write-back below, and a fit that dies
    # mid-run never leaves the graph pointing at deleted donated buffers
    trainables = {n: jnp.array(sd.arrays[n]) for n in trainable_names}
    frozen = {k: v for k, v in sd.arrays.items()
              if k not in set(trainable_names)}
    if sd._updater_state is None:
        sd._updater_state = {n: cfg.updater.init_state(trainables[n])
                             for n in trainable_names}
    opt_state = jax.tree_util.tree_map(jnp.array, sd._updater_state)
    history = History()

    def batches():
        if iterator is not None:
            if hasattr(iterator, "reset"):
                iterator.reset()
            for ds in iterator:
                yield ds
        else:
            from deeplearning4j_tpu.datasets.dataset import DataSet
            yield DataSet(features, labels)

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn import io as nn_io

    # the dispatch queue persists ACROSS fit() calls: forcing a sync per
    # call would pay the expensive post-program host sync every step for
    # the common one-batch-per-fit pattern; the bounded queue syncs every
    # DISPATCH_DEPTH steps instead, wherever those steps came from
    pending = sd.__dict__.setdefault("_dispatch_pending", [])
    from deeplearning4j_tpu import telemetry

    # health-layer rollback hooks over the loop-local training trees
    # (the functional update below rebinds them, so the restore closure
    # writes back through nonlocal)
    def _snapshot():
        host = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: np.asarray(x), t)
        return (host(trainables), host(opt_state), sd._iteration_count)

    def _restore(snap):
        nonlocal trainables, opt_state
        dev = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.asarray(x), t)
        trainables = dev(snap[0])
        opt_state = dev(snap[1])
        sd._iteration_count = snap[2]

    guard_keys = health.bucket_keys(trainables) if mode else ()

    with flightrec.flight_recorder():
        for _ in range(epochs):
            for ds in batches():
                with telemetry.span(telemetry.PHASE_INGEST):
                    ph = {}
                    feats = (ds.features
                             if isinstance(ds.features, (list, tuple))
                             else [ds.features])
                    labs = (ds.labels
                            if isinstance(ds.labels, (list, tuple))
                            else [ds.labels])
                    for name, arr in zip(cfg.data_set_feature_mapping,
                                         feats):
                        ph[name] = jnp.asarray(arr)
                    for name, arr in zip(cfg.data_set_label_mapping, labs):
                        ph[name] = jnp.asarray(arr)
                    if cfg.data_set_feature_mask_mapping and \
                            getattr(ds, "features_mask", None) is not None:
                        ph[cfg.data_set_feature_mask_mapping[0]] = \
                            jnp.asarray(ds.features_mask)
                    if cfg.data_set_label_mask_mapping and \
                            getattr(ds, "labels_mask", None) is not None:
                        ph[cfg.data_set_label_mask_mapping[0]] = \
                            jnp.asarray(ds.labels_mask)
                    # write staged arrays back so a reused DataSet
                    # transfers once (reference DataSet#migrate semantics,
                    # matching the networks)
                    if isinstance(ds, DataSet):
                        fmap = list(cfg.data_set_feature_mapping
                                    or [])[:len(feats)]
                        lmap = list(cfg.data_set_label_mapping
                                    or [])[:len(labs)]
                        if len(fmap) == len(feats):
                            staged = [ph[n] for n in fmap]
                            ds.features = (staged if isinstance(
                                ds.features, (list, tuple)) else staged[0])
                        if len(lmap) == len(labs):
                            staged = [ph[n] for n in lmap]
                            ds.labels = (staged if isinstance(
                                ds.labels, (list, tuple)) else staged[0])
                # np scalar stages with the call; a bare python int would
                # take the slow weak-type conversion path
                gvec = None
                with telemetry.span(telemetry.PHASE_COMPUTE) as _sp:
                    out = step(trainables, frozen, opt_state,
                               np.float32(sd._iteration_count), ph)
                    trainables, opt_state, loss = out[:3]
                    if mode:
                        gvec = out[3]
                    _sp.set_result(loss)
                with telemetry.span(telemetry.PHASE_GRAD_SYNC) as _sp:
                    _sp.set_result(trainables)  # single device: ~0
                if telemetry.enabled():
                    rows = getattr(ph.get(next(iter(ph), None), None),
                                   "shape", (0,))
                    telemetry.record_step("samediff",
                                          int(rows[0]) if rows else 0)
                sd._iteration_count += 1
                if mode:
                    health.observe_step(
                        sd, "samediff", sd._iteration_count - 1,
                        sd._epoch_count, loss, gvec, guard_keys,
                        batch=tuple(ph.values()),
                        snapshot=_snapshot, restore=_restore)
                history.append(loss)
                pending.append(loss)
                nn_io.drain(pending)  # bounded async dispatch, no sync
                for lst in sd._listeners:
                    if hasattr(lst, "iteration_done"):
                        lst.iteration_done(sd, sd._iteration_count,
                                           float(loss))
            sd._epoch_count += 1

    sd.arrays.update(trainables)
    sd._updater_state = opt_state
    return history
