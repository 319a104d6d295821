"""Boosting drivers: GBDT / DART / GOSS sampling / RF.

TPU-native equivalent of the reference boosting layer (reference:
src/boosting/gbdt.cpp GBDT::Train/TrainOneIter, goss.hpp, dart.hpp, rf.hpp,
score_updater.hpp). The training loop stays on host (it is O(iterations),
not O(rows)); all O(rows) work — gradients, histograms, score updates,
prediction routing — is jitted device code. Scores are float32 device arrays
(the reference keeps double; the f32 choice follows its GPU precedent).
"""
from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import runtime
from .config import Config
from .dataset import BinnedDataset
from .learner import (SerialTreeLearner, TreeLog, assign_leaves,
                      leaf_values_by_row)
from .metric import Metric, create_metrics
from .obs import count_trees, host_phase, trace_phase, track_jit
from .objective import ObjectiveFunction, create_objective
from .tree import Tree
from .utils.log import Log


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("class_id",), donate_argnums=(0,))
def _score_add(score, lv, leaf_assign, scale, class_id):
    """One fused launch per tree contribution (kept jitted: the eager form
    retraced per op and dominated DART/rollback wall-clock)."""
    with trace_phase("lgbtpu/score_update"):
        vals = leaf_values_by_row(lv, leaf_assign, lv.shape[0]) * scale
        if score.ndim > 1:
            return score.at[:, class_id].add(vals)
        return score + vals


_score_add = track_jit("boosting/score_add", _score_add)
# host-facing tracked alias: the learner's own (traced) assign_leaves calls
# stay on the raw jit, so only eager-path dispatches count here
assign_leaves = track_jit("learner/assign_leaves", assign_leaves)


class ScoreTracker:
    """Running raw scores for one dataset (reference: score_updater.hpp:21)."""

    def __init__(self, num_data: int, num_class: int, init: np.ndarray) -> None:
        shape = (num_data, num_class) if num_class > 1 else (num_data,)
        s = np.zeros(shape, dtype=np.float32)
        s += init if num_class > 1 else init[0]
        self.score = jnp.asarray(s)

    def add(self, leaf_values: np.ndarray, leaf_assign: jax.Array, class_id: int,
            num_class: int, scale: float = 1.0) -> None:
        lv = jnp.asarray(leaf_values, jnp.float32)
        self.score = _score_add(self.score, lv, leaf_assign,
                                jnp.float32(scale), int(class_id))

    def np(self) -> np.ndarray:
        return np.asarray(self.score)


class GBDT:
    """Gradient Boosting (reference: src/boosting/gbdt.cpp:264 Train,
    :369 TrainOneIter)."""

    name = "gbdt"

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 comm_axis: Optional[str] = None) -> None:
        self.config = config
        self.train_set = train_set
        self.models: List[Tree] = []
        self.iter_ = 0
        self.num_class = max(1, int(config.num_class))
        self.objective: Optional[ObjectiveFunction] = None
        self.metrics: List[Metric] = []
        self.init_scores = np.zeros(self.num_class, dtype=np.float64)
        self.valid_sets: List[Tuple[str, BinnedDataset, ScoreTracker]] = []
        self.learner: Optional[SerialTreeLearner] = None
        self.train_score: Optional[ScoreTracker] = None
        self._rng = np.random.RandomState(
            config.seed if config.seed is not None else config.data_random_seed)
        self._key = jax.random.PRNGKey(
            config.seed if config.seed is not None else 0)
        self._inbag: Optional[jax.Array] = None  # (N,) f32 0/1
        self._grad_fn = None
        self.best_iteration = -1
        self.comm_axis = comm_axis
        # monotonic token bumped whenever self.models changes content —
        # train/rollback/score-rebuild/fused-commit. Device-resident
        # prediction packs key on it (an (len, id(tree)) key is unsafe:
        # rollback + retrain can reproduce both with different trees)
        self._model_version = 0
        # guards models mutations, the version token and the serving
        # caches (_pack_cache/_serve_sessions/_tree_log_cache): a
        # PredictSession worker thread must never pack a half-committed
        # model. Re-entrant because _rebuild_scores bumps the version
        # from inside locked sections.
        self._cache_lock = threading.RLock()
        # engine.train's obs.JobStart, until the first dispatch writes it
        self._job_start = None
        if train_set is not None:
            self._setup(train_set)

    # ------------------------------------------------------------------ setup
    def _setup(self, train_set: BinnedDataset) -> None:
        cfg = self.config
        # device-cost capture is process-global (obs_device mirrors the
        # trace_spans configure contract: last writer wins)
        from . import obs_device
        obs_device.configure(cost_enabled=cfg.obs_device_cost)
        self.objective = create_objective(cfg)
        with host_phase("lgbtpu/objective_init"):
            self.objective.init(train_set.metadata)
        self.num_tree_per_iteration = self.objective.num_model_per_iteration
        self.metrics = create_metrics(cfg, self.objective.name)
        from .parallel.mesh import create_tree_learner, make_mesh
        mesh = None
        if cfg.tree_learner != "serial":
            import jax as _jax
            if len(_jax.devices()) > 1:
                mesh = make_mesh()
        with host_phase("lgbtpu/learner_init"):   # uploads included
            self.learner = create_tree_learner(cfg, train_set, mesh)
        n = train_set.num_data
        # boost_from_average (reference: gbdt.cpp:333; distributed mean is a
        # psum at objective level — labels are row-sharded the same way)
        if cfg.boost_from_average and self.objective.name != "none" \
                and train_set.metadata.label is not None:
            for k in range(self.num_tree_per_iteration):
                self.init_scores[k] = self.objective.boost_from_score(k)
        if train_set.metadata.init_score is not None:
            base = train_set.metadata.init_score.reshape(
                n, -1) if self.num_class > 1 else train_set.metadata.init_score.ravel()
        else:
            base = None
        self.train_score = ScoreTracker(
            n, self.num_tree_per_iteration, self.init_scores)
        if base is not None:
            self.train_score.score = self.train_score.score + jnp.asarray(
                base, jnp.float32)
        self._inbag = jnp.ones((n,), jnp.float32)
        self._cegb_used = np.zeros(train_set.num_features, dtype=bool)
        self._grad_fn = track_jit("boosting/grads",
                                  jax.jit(self.objective.gradients))

    def add_valid(self, name: str, valid_set: BinnedDataset) -> None:
        vs = ScoreTracker(valid_set.num_data, self.num_tree_per_iteration,
                          self.init_scores)
        if valid_set.metadata.init_score is not None:
            base = valid_set.metadata.init_score
            base = base.reshape(valid_set.num_data, -1) if self.num_class > 1 \
                else base.ravel()
            vs.score = vs.score + jnp.asarray(base, jnp.float32)
        # replay already-trained trees (continued training)
        if self.models:
            bins = jnp.asarray(valid_set.binned)
            Log.debug("Replaying %d trees onto valid set %s", len(self.models), name)
            for i, tree in enumerate(self.models):
                vals, leaf = self._route_tree_device(tree, valid_set)
                vs.add(vals, leaf, i % self.num_tree_per_iteration,
                       self.num_tree_per_iteration)
        self.valid_sets.append((name, valid_set, vs))

    def _route_tree_device(self, tree: Tree, ds: BinnedDataset):
        """Route a dataset's binned rows through a host Tree on device.

        Converts the tree into leaf-slot split order (bin-space thresholds)
        and reuses the learner's arithmetic router — replaces the round-1
        per-node Python walk that made DART/rollback quadratic (reference
        analogs: score_updater.hpp, dart.hpp score replay). Returns
        (slot-ordered leaf values (L,), per-row slots (N,) device array).
        """
        from .ops.predict import tree_to_bin_log

        # logs are cached per (tree state, dataset): DART re-drops the same
        # trees every iteration and each conversion costs host work plus
        # ~a dozen host->device uploads
        # content key (not id()): a GC'd tree's address can be reused by a
        # new tree with byte-identical leaf values after rollback
        key = (tree.num_leaves, tree.split_feature.tobytes(),
               tree.threshold.tobytes(), tree.decision_type.tobytes(),
               tree.leaf_value.tobytes(), id(ds))
        with self._cache_lock:
            cache = getattr(self, "_tree_log_cache", None)
            if cache is None:
                cache = self._tree_log_cache = {}
            log = cache.get(key)
        if log is None:
            # convert outside the lock (host work + uploads); a racing
            # duplicate conversion is harmless, a held lock is not
            log = tree_to_bin_log(tree, ds)
            with self._cache_lock:
                if len(cache) > 4096:
                    cache.clear()
                cache[key] = log
        if ds is self.train_set and self.learner is not None:
            bins = self.learner.bins
            bundle = self.learner.bundle
            hc = self.learner.hp.has_categorical
        else:
            bins = self._valid_bins(ds)
            bundle = None
            if ds.has_bundles:
                bundle = {k: jnp.asarray(v)
                          for k, v in ds.bundle_maps().items()}
            from .ops.binning import BIN_CATEGORICAL
            hc = any(m.bin_type == BIN_CATEGORICAL for m in ds.bin_mappers)
        leaf = assign_leaves(bins, log, has_categorical=hc, bundle=bundle)
        if leaf.shape[0] != ds.num_data:
            # mesh learners pad rows to a multiple of the device count; the
            # score buffers are unpadded (num_data) — truncate before use
            leaf = leaf[:ds.num_data]
        return np.asarray(log.leaf_value), leaf

    # --------------------------------------------------------------- sampling
    def _bagging(self, it: int, grad: jax.Array, hess: jax.Array) -> None:
        """Refresh the in-bag mask (reference: gbdt.cpp:228 Bagging,
        goss.hpp:103 for data_sample_strategy=goss).

        Uses the SAME seed-derived samplers as the fused device blocks
        (fused.make_sampler), so a given config trains the identical model
        through either path."""
        cfg = self.config
        if not hasattr(self, "_sampler_fn"):
            from .fused import make_balanced_sampler, make_sampler
            lab = self.objective.label if self.objective is not None else None
            if lab is None and self.train_set is not None \
                    and self.train_set.metadata.label is not None:
                # custom objectives (objective=none) still bag by label
                lab = self.train_set.metadata.device_label()
            # GOSS takes precedence over any bagging params (the reference's
            # data_sample_strategy switch, gbdt.cpp:228)
            if cfg.data_sample_strategy != "goss" \
                    and (cfg.pos_bagging_fraction < 1.0
                         or cfg.neg_bagging_fraction < 1.0) \
                    and cfg.bagging_freq > 0 and lab is not None:
                self._sampler_fn = make_balanced_sampler(cfg, lab)
            else:
                self._sampler_fn = make_sampler(cfg,
                                                self.train_set.num_data)
        if self._sampler_fn is None:
            self._amp = None
            return
        with trace_phase("lgbtpu/sample"):
            g = grad if grad.ndim == 1 else jnp.sum(jnp.abs(grad), axis=1)
            h = hess if hess.ndim == 1 else jnp.sum(jnp.abs(hess), axis=1)
            inbag, amp = self._sampler_fn(None, it, g, h)
        self._inbag = inbag
        self._amp = amp if cfg.data_sample_strategy == "goss" else None

    def _tree_channels(self, grad: jax.Array, hess: jax.Array, k: int) -> jax.Array:
        with trace_phase("lgbtpu/sample"):
            g = grad if grad.ndim == 1 else grad[:, k]
            h = hess if hess.ndim == 1 else hess[:, k]
            if getattr(self, "_amp", None) is not None:
                g, h = g * self._amp, h * self._amp
            m = self._inbag
            return jnp.stack([g * m, h * m, m], axis=1)

    def _feature_mask(self, it: int) -> jax.Array:
        cfg = self.config
        nf = self.train_set.num_features
        if not hasattr(self, "_fmask_fn"):
            from .fused import make_feature_mask_fn
            self._fmask_fn = make_feature_mask_fn(cfg, nf)
        with trace_phase("lgbtpu/sample"):
            if self._fmask_fn is None:
                return jnp.ones((nf,), bool)
            return self._fmask_fn(it)

    # --------------------------------------------------------------- training
    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference: gbdt.cpp:369 TrainOneIter).
        Returns True when no tree could be grown (all-stop signal)."""
        # while a fused block is in flight, score already includes it but
        # models/iter_ lag; entry points that read or extend them must
        # finalize first so external callers never observe divergent state
        self.finish_fused("train_one_iter")
        it = self.iter_
        if grad is None:
            g, h = self._grad_fn(self.train_score.score, jnp.int32(it))
        else:
            g = jnp.asarray(grad, jnp.float32)
            h = jnp.asarray(hess, jnp.float32)
            if self.num_class > 1:
                g = g.reshape(self.train_set.num_data, self.num_class)
                h = h.reshape(self.train_set.num_data, self.num_class)
        if self.config.obs_check_finite != "off":
            # opt-in watchdog (eager path): one fused isfinite reduction
            # over this iteration's gradients — a custom fobj or an
            # exploding objective surfaces here, at the iteration it
            # happened. Gated BEFORE any array op: off builds nothing.
            from . import obs_device
            obs_device.check_finite("grads", (g, h),
                                    self.config.obs_check_finite)
        self._bagging(it, g, h)
        self._last_grad, self._last_hess = g, h
        fmask = self._feature_mask(it)
        any_nonconstant = False
        for k in range(self.num_tree_per_iteration):
            ghc = self._tree_channels(g, h, k)
            self._last_ghc = ghc
            key = jax.random.fold_in(self._key, it * 131 + k)
            log = self.learner.train(ghc, fmask, key,
                                     jnp.asarray(self._cegb_used))
            tree = self._finalize_tree(log, k)
            with self._cache_lock:
                self.models.append(tree)
            self._note_used_features(tree)
            count_trees([tree])     # tree/*: what the fused loop counts
            if tree.num_leaves > 1:
                any_nonconstant = True
        if self.config.obs_check_finite != "off":
            from . import obs_device
            obs_device.check_finite("scores", (self.train_score.score,),
                                    self.config.obs_check_finite)
        with self._cache_lock:
            self.iter_ += 1
            self._bump_model_version()
        return not any_nonconstant

    def _note_used_features(self, tree: Tree) -> None:
        """Track model-level feature usage for CEGB coupled penalties
        (reference: cost_effective_gradient_boosting.hpp
        is_feature_used_in_split_)."""
        if tree.num_leaves > 1 and self.train_set is not None:
            for f in tree.split_feature[:tree.num_internal]:
                inner = self.train_set.inner_feature_index(int(f))
                if inner >= 0:
                    self._cegb_used[inner] = True

    def _shrinkage_rate(self, log: TreeLog) -> float:
        return float(self.config.learning_rate)

    def _fit_linear_tree(self, tree: Tree, log: TreeLog, grad, hess,
                         class_id: int, rate: float) -> None:
        """Fit ridge linear models in the leaves (reference:
        LinearTreeLearner::CalculateLinear, linear_tree_learner.cpp:7):
        solve -(Z^T H Z + lambda I') beta = Z^T g per leaf over the leaf's
        branch numerical features; rows with NaN in those features are
        excluded; under-determined leaves keep the plain output. The first
        iteration only copies constants (the reference skips the fit).

        Under ``linear_device`` the solve runs batched on device
        (lightgbm_tpu/linear/fit.py: all leaves' Gram matrices at once);
        this host loop stays as the parity oracle."""
        from .ops.binning import BIN_CATEGORICAL

        ds = self.train_set
        tree.is_linear = True
        # leaf_value is already shrunk; solved coefficients get the same
        # shrinkage below (reference applies Tree::Shrinkage to both)
        tree.leaf_const = tree.leaf_value.copy()
        if len(self.models) <= self.num_tree_per_iteration - 1 \
                or tree.num_leaves <= 1 or ds.raw_numeric is None:
            return
        lam = float(self.config.linear_lambda)
        if self._linear_fit_on_device():
            from .linear import fit_linear_leaves
            fit_linear_leaves(tree, ds, log.row_leaf, self._last_ghc,
                              lam=lam, rate=rate,
                              num_leaves_cap=int(self.config.num_leaves))
            return
        leaf = np.asarray(log.row_leaf)
        # use the bagged/amplified channels the tree was grown on (reference
        # fits over the bagged partition only; out-of-bag rows carry h=0
        # here, excluding them from the normal equations)
        ghc = np.asarray(self._last_ghc, np.float64)
        gk, hk = ghc[:, 0], ghc[:, 1]
        del grad, hess
        X = ds.raw_numeric
        for l in range(tree.num_leaves):
            feats = [int(f) for f in tree.branch_features(l)
                     if ds.inner_feature_index(int(f)) >= 0
                     and ds.bin_mappers[ds.inner_feature_index(int(f))]
                     .bin_type != BIN_CATEGORICAL]
            rows = np.flatnonzero(leaf == l)
            if not feats or len(rows) < len(feats) + 1:
                continue
            Z = X[np.ix_(rows, feats)].astype(np.float64)
            ok = ~np.isnan(Z).any(axis=1)
            if int(ok.sum()) < len(feats) + 1:
                continue
            Zk = np.concatenate([Z[ok], np.ones((int(ok.sum()), 1))], axis=1)
            hr = hk[rows][ok]
            A = Zk.T @ (Zk * hr[:, None])
            A[np.arange(len(feats)), np.arange(len(feats))] += lam
            b = Zk.T @ gk[rows][ok]
            try:
                beta = -np.linalg.solve(A, b)
            except np.linalg.LinAlgError:
                continue
            keep = np.abs(beta[:-1]) > 1e-35
            tree.leaf_features[l] = np.asarray(feats, np.int64)[keep]
            tree.leaf_coeff[l] = beta[:-1][keep] * rate
            tree.leaf_const[l] = float(beta[-1]) * rate

    def _linear_fit_on_device(self) -> bool:
        """Resolve ``linear_device``: off -> host oracle, on -> batched
        device solve, auto -> device only when a TPU backend is up (the
        host loop beats a CPU-jax round trip at small leaf counts)."""
        mode = self.config.linear_device
        if mode == "off":
            return False
        if mode == "on":
            return True
        return runtime.on_tpu()

    def _linear_score_updates(self, tree: Tree, log: TreeLog,
                              class_id: int) -> None:
        """Score updates for linear leaves need raw feature values, so they
        run on host (reference: score updates via Tree::AddPredictionToScore
        with PredictionFunLinear, tree.cpp:246)."""
        leaf = np.asarray(log.row_leaf)
        vals = tree.linear_predict(self.train_set.raw_numeric.astype(np.float64),
                                   leaf)
        self.train_score.score = self.train_score.score + (
            jnp.asarray(vals, jnp.float32) if self.num_tree_per_iteration == 1
            else jnp.zeros_like(self.train_score.score)
            .at[:, class_id].set(jnp.asarray(vals, jnp.float32)))
        for _, vset, vscore in self.valid_sets:
            slot_vals, vleaf = self._route_tree_device(tree, vset)
            if vset.raw_numeric is None:
                # no raw features (e.g. binary-cache valid set): fall back to
                # the plain leaf outputs so metrics stay meaningful
                Log.warning("valid set lacks raw features for linear trees; "
                            "using plain leaf outputs for its scores")
                vscore.add(slot_vals, vleaf, class_id,
                           self.num_tree_per_iteration)
                continue
            # the device router returns to_split_arrays SLOTS (BFS order);
            # linear_predict keys coefficients by LEAF id — map through
            # leaf_of_slot (they only coincide when BFS == creation order)
            leaf_of_slot = tree.to_split_arrays()["leaf_of_slot"]
            vvals = tree.linear_predict(vset.raw_numeric.astype(np.float64),
                                        leaf_of_slot[np.asarray(vleaf)])
            vscore.score = vscore.score + (
                jnp.asarray(vvals, jnp.float32)
                if self.num_tree_per_iteration == 1
                else jnp.zeros_like(vscore.score)
                .at[:, class_id].set(jnp.asarray(vvals, jnp.float32)))

    def _finalize_tree(self, log: TreeLog, class_id: int) -> Tree:
        rate = self._shrinkage_rate(log)
        if self.objective.need_renew:
            # objective-specific leaf renewal needs host stats (reference:
            # serial_tree_learner.cpp:684 RenewTreeOutput) — slow path
            tree = self.learner.log_to_tree(log)
            if tree.num_leaves > 1:
                assign = np.asarray(log.row_leaf)
                score_before = self.train_score.np()
                renewed = self.objective.renew_leaf_values(
                    assign, tree.num_leaves, score_before)
                if renewed is not None:
                    tree.leaf_value = renewed.astype(np.float64)
            tree.apply_shrinkage(rate)
            leaf_vals_dev = jnp.asarray(tree.leaf_value, jnp.float32)
        else:
            # fast path: score updates run fully on device from the log;
            # host Tree construction is a single batched transfer after
            leaf_vals_dev = log.leaf_value * jnp.float32(rate)
            tree = self.learner.log_to_tree(log)
            tree.apply_shrinkage(rate)
        if self.config.linear_tree and not self.objective.need_renew:
            self._fit_linear_tree(tree, log, self._last_grad, self._last_hess,
                                  class_id, rate)
            if tree.num_leaves > 1:
                self._linear_score_updates(tree, log, class_id)
            return tree
        # score updates: train via the partition the learner already holds
        # (reference: score_updater.hpp:88), valid via device routing.
        # Constant (1-leaf) trees contribute nothing (reference:
        # gbdt.cpp TrainOneIter skips UpdateScore when no split was found).
        if tree.num_leaves > 1:
            self.train_score.add(leaf_vals_dev, log.row_leaf, class_id,
                                 self.num_tree_per_iteration)
            for _, vset, vscore in self.valid_sets:
                vbins = self._valid_bins(vset)
                vleaf = assign_leaves(
                    vbins, log,
                    has_categorical=self.learner.hp.has_categorical,
                    bundle=self.learner.bundle)
                vscore.add(leaf_vals_dev, vleaf, class_id,
                           self.num_tree_per_iteration)
        return tree

    def _valid_bins(self, vset: BinnedDataset) -> jax.Array:
        if not hasattr(vset, "_device_bins"):
            vset._device_bins = jnp.asarray(vset.binned)
        return vset._device_bins

    # ---------------------------------------------------------- fused blocks
    def supports_fused(self) -> bool:
        """True when K iterations can run as one device launch (no per-iter
        host observation needed): plain GBDT, built-in objective without
        leaf renewal, no valid sets, single-device learner."""
        from .parallel.mesh import _MeshTreeLearner
        return (type(self) is GBDT
                and not self.config.linear_tree
                and self.objective is not None
                and self.objective.name != "none"
                and not self.objective.need_renew
                and not self.valid_sets
                and self.train_set is not None
                and not isinstance(self.learner, _MeshTreeLearner))

    def train_block(self, k: int) -> bool:
        """Train k iterations fused in one launch (see fused.py). The
        phase is opened HERE, not around the engine's call: a caller that
        wraps this method in an annotation of its own (the benchmark's
        ``bench/train_block``) then holds ``lgbtpu/train_block`` and every
        ``lgbtpu/fused_*`` phase inside it, and a moment between two of
        them still lies under a name of the program's."""
        with host_phase("lgbtpu/train_block"):
            if getattr(self, "_fused", None) is None:
                from .fused import FusedTrainer
                self._fused = FusedTrainer(self)
            return self._fused.run(k)

    def finish_fused(self, reason: str = "unspecified") -> bool:
        """Finalize any in-flight fused block (host trees + cegb state).
        ``reason`` names the calling read API for the
        ``fused/flush/<reason>`` telemetry counters."""
        if getattr(self, "_fused", None) is None:
            return False
        return self._fused.flush(reason)

    def rollback_one_iter(self) -> None:
        """(reference: gbdt.cpp:454 RollbackOneIter)"""
        self.finish_fused("rollback_one_iter")
        if self.iter_ <= 0:
            return
        with self._cache_lock:
            for _ in range(self.num_tree_per_iteration):
                tree = self.models.pop()
                del tree
            self.iter_ -= 1
            self._bump_model_version()
        # scores must be rebuilt; mark dirty and recompute lazily
        self._rebuild_scores()

    def _rebuild_scores(self) -> None:
        # callers reach here after mutating self.models (rollback, continued
        # training preload) — invalidate any device-resident predict packs
        self._bump_model_version()
        K = self.num_tree_per_iteration

        def fresh_tracker(ds: BinnedDataset) -> ScoreTracker:
            ts = ScoreTracker(ds.num_data, K, self.init_scores)
            if ds.metadata.init_score is not None:
                base = ds.metadata.init_score
                base = base.reshape(ds.num_data, -1) if self.num_class > 1 \
                    else base.ravel()
                ts.score = ts.score + jnp.asarray(base, jnp.float32)
            return ts

        ts = fresh_tracker(self.train_set)
        for i, tree in enumerate(self.models):
            vals, leaf = self._route_tree_device(tree, self.train_set)
            ts.add(vals, leaf, i % K, K)
        self.train_score = ts
        rebuilt = []
        for name, vset, _ in self.valid_sets:
            vs = fresh_tracker(vset)
            for i, tree in enumerate(self.models):
                vals, leaf = self._route_tree_device(tree, vset)
                vs.add(vals, leaf, i % K, K)
            rebuilt.append((name, vset, vs))
        self.valid_sets = rebuilt

    # ------------------------------------------------------------------- eval
    def eval_set(self, name: str, ds: BinnedDataset, tracker: ScoreTracker,
                 feval=None) -> List[Tuple[str, str, float, bool]]:
        out = []
        score = tracker.score
        conv = np.asarray(self.objective.convert_output(score))
        md = ds.metadata
        for m in self.metrics:
            for mname, val in m.eval(conv, md.label, md.weight, md.query_boundaries):
                out.append((name, mname, float(val), m.greater_is_better))
        if feval is not None:
            res = feval(np.asarray(score), ds)
            if res:
                if isinstance(res[0], (list, tuple)):
                    for mname, val, gib in res:
                        out.append((name, mname, float(val), bool(gib)))
                else:
                    mname, val, gib = res
                    out.append((name, mname, float(val), bool(gib)))
        return out

    def eval_train(self, feval=None):
        return self.eval_set("training", self.train_set, self.train_score, feval)

    def eval_valid(self, feval=None):
        out = []
        for name, ds, tracker in self.valid_sets:
            out.extend(self.eval_set(name, ds, tracker, feval))
        return out

    # ---------------------------------------------------------------- predict
    DEVICE_PREDICT_MIN_ROWS = 512

    @property
    def model_version(self) -> int:
        """Monotonic model-content token (see __init__)."""
        return self._model_version

    def _bump_model_version(self) -> None:
        with self._cache_lock:
            self._model_version += 1

    # ------------------------------------------------------- hot swap (online)
    def adopt(self, other: "GBDT") -> tuple:
        """Atomically swap this booster's served model for ``other``'s.

        The online promotion hook: a candidate trained off the serving
        thread (refit / continued training) replaces the resident model
        under the model lock with a SINGLE version bump, so every
        concurrent PredictSession snapshot sees either the old ensemble
        or the new one whole — never a half-committed pack. Scores and
        validation trackers are NOT rebuilt (serving boosters have no
        training state to keep consistent; call _rebuild_scores yourself
        if you adopt into a live training booster).

        Returns an opaque rollback token for :meth:`restore`.
        """
        with self._cache_lock:
            snap = (list(self.models), self.init_scores.copy(), self.iter_,
                    self.best_iteration)
            self.models = list(other.models)
            self.init_scores = np.asarray(other.init_scores,
                                          np.float64).copy()
            self.iter_ = int(other.iter_)
            # the adopted model's stored early-stop cap replaces ours:
            # a booster loaded from a 6-tree publish would otherwise keep
            # best_iteration=6 forever and silently truncate every later
            # adopted model with more trees at predict time
            self.best_iteration = int(getattr(other, "best_iteration", -1))
            self._bump_model_version()
        return snap

    def restore(self, snapshot: tuple) -> None:
        """Roll back to a model captured by :meth:`adopt` (same single
        version-bump atomicity as the promotion itself)."""
        models, init_scores, it, best_it = snapshot
        with self._cache_lock:
            self.models = list(models)
            self.init_scores = np.asarray(init_scores, np.float64).copy()
            self.iter_ = int(it)
            self.best_iteration = int(best_it)
            self._bump_model_version()

    def _packed_model(self, start: int, end: int):
        """Device-resident ``PackedSplits`` for iterations [start, end).

        Cached behind the model-version token so repeat predicts pay zero
        host re-packs and zero uploads (``serve/pack_build`` vs
        ``serve/pack_hit`` counters); continued training, rollback and
        score rebuilds bump the version and naturally invalidate. All
        PredictSessions over this booster share the cache."""
        from .obs import telemetry
        from .ops.predict import pack_splits

        # the whole lookup-or-build runs under the model lock: the key
        # read, the models slice and the store must see one consistent
        # (models, version) pair or a concurrent commit tears the pack
        with self._cache_lock:
            cache = getattr(self, "_pack_cache", None)
            if cache is None or not isinstance(cache, dict):
                cache = self._pack_cache = {}
            key = (start, end, self._model_version)
            hit = cache.get(key)
            if hit is not None:
                telemetry.count("serve/pack_hit")
                return hit
            if len(cache) > 16:
                cache.clear()
            telemetry.count("serve/pack_build")
            K = self.num_tree_per_iteration
            hit = cache[key] = pack_splits(self.models[start * K:end * K],
                                           num_class=K)
            return hit

    def _predict_session(self, start: int, end: int):
        """Lazily created serving session per iteration range (the device
        predict path of ``_raw_scores_range``). Sessions hold only bucket
        warm-state; the pack itself lives in the shared version-keyed
        ``_packed_model`` cache."""
        from .serve.session import PredictSession

        with self._cache_lock:
            cache = getattr(self, "_serve_sessions", None)
            if cache is None:
                cache = self._serve_sessions = {}
            sess = cache.get((start, end))
            if sess is None:
                if len(cache) > 32:
                    cache.clear()
                sess = cache[(start, end)] = PredictSession(
                    self, start_iteration=start, num_iteration=end - start)
            return sess

    def _raw_scores(self, X: np.ndarray, start: int, end: int) -> np.ndarray:
        """Ensemble raw scores with optional prediction early stopping
        (reference: src/boosting/prediction_early_stop.cpp — rows whose
        margin exceeds pred_early_stop_margin stop accumulating trees,
        checked every pred_early_stop_freq iterations)."""
        cfg = self.config
        K = self.num_tree_per_iteration
        es = bool(cfg.pred_early_stop) and self.objective is not None \
            and (self.objective.name in ("binary",)
                 or (K > 1 and "multiclass" in self.objective.name))
        if not es:
            return self._raw_scores_range(X, start, end)
        freq = max(1, int(cfg.pred_early_stop_freq))
        margin_thr = float(cfg.pred_early_stop_margin)
        n = X.shape[0]
        score = np.zeros((n, K), dtype=np.float64)
        active = np.ones(n, dtype=bool)
        # the margin the reference thresholds is that of the FINAL score,
        # which includes boost_from_average init scores
        init = self.init_scores[None, :K]
        for b0 in range(start, end, freq):
            if not active.any():
                break
            b1 = min(end, b0 + freq)
            sub = X[active]
            score[active] += self._raw_scores_range(sub, b0, b1)
            full = score[active] + init
            if K == 1:
                margin = 2.0 * np.abs(full[:, 0])
            else:
                top2 = np.partition(full, K - 2, axis=1)[:, K - 2:]
                margin = np.max(top2, axis=1) - np.min(top2, axis=1)
            still = margin <= margin_thr
            idx = np.flatnonzero(active)
            active[idx[~still]] = False
        return score

    def _raw_scores_range(self, X: np.ndarray, start: int,
                          end: int) -> np.ndarray:
        """Ensemble raw scores (N, K) over model range [start*K, end*K).

        Large batches route on device (reference analog:
        src/application/predictor.hpp batch prediction); small batches walk
        the host trees (a device launch is not worth it for a few rows).
        """
        K = self.num_tree_per_iteration
        n = X.shape[0]
        # snapshot under the model lock: the online trainer shadow-scores
        # candidates from its worker thread while promotions mutate models
        with self._cache_lock:
            models = self.models[start * K:end * K]
        if n >= self.DEVICE_PREDICT_MIN_ROWS and models:
            return self._predict_session(start, end).raw_scores(X)
        score = np.zeros((n, K), dtype=np.float64)
        for i, t in enumerate(models):
            score[:, (start * K + i) % K] += t.predict(X)
        return score

    def predict(self, X: np.ndarray, *, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                pred_leaf: bool = False) -> np.ndarray:
        self.finish_fused("predict")
        X = np.asarray(X, dtype=np.float64)
        n = X.shape[0]
        K = self.num_tree_per_iteration
        with self._cache_lock:
            total_iters = len(self.models) // max(K, 1)
            if num_iteration is None or num_iteration <= 0:
                num_iteration = total_iters - start_iteration
            end = min(total_iters, start_iteration + num_iteration)
            leaf_models = self.models[start_iteration * K:end * K] \
                if pred_leaf else None
        if pred_leaf:
            out = np.zeros((n, (end - start_iteration) * K), dtype=np.int32)
            for i, t in enumerate(leaf_models):
                out[:, i] = t.predict_leaf_index(X)
            return out
        score = self._raw_scores(X, start_iteration, end)
        score = score + self.init_scores[None, :K]
        if not raw_score and self.objective is not None:
            score = np.asarray(self.objective.convert_output(jnp.asarray(score)))
        if K == 1:
            return score.ravel()
        return score

    # --------------------------------------------------------------- model IO
    def model_to_string(self, num_iteration: int = -1) -> str:
        """(reference: gbdt_model_text.cpp:400 SaveModelToString)"""
        self.finish_fused("model_to_string")
        cfg = self.config
        K = self.num_tree_per_iteration
        # snapshot the model list under the lock: the online trainer
        # serializes the serving booster from its worker thread (refit
        # round-trips through the model string) while promotions swap it
        with self._cache_lock:
            models = list(self.models)
            init_scores = self.init_scores.copy()
        total_iters = len(models) // max(K, 1)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = total_iters
        end = min(total_iters, num_iteration) * K
        lines = [
            "tree",
            "version=v3",
            "boosting=%s" % self.name,
            "objective=%s" % self._objective_string(),
            "num_class=%d" % self.num_class,
            "num_tree_per_iteration=%d" % K,
            "init_score=%s" % " ".join("%.17g" % v for v in init_scores),
            "max_feature_idx=%d" % (self.train_set.num_total_features - 1
                                    if self.train_set else -1),
            "feature_names=%s" % " ".join(self.train_set.feature_names
                                          if self.train_set else []),
            "best_iteration=%d" % self.best_iteration,
            "",
        ]
        for i, tree in enumerate(models[:end]):
            lines.append("Tree=%d" % i)
            lines.append(tree.to_text())
            lines.append("")
        lines.append("end of trees")
        # saved_feature_importance_type selects the importance measure
        # written into the model file (reference: gbdt_model_text.cpp:100
        # SaveModelToString -> FeatureImportance(.., type))
        itype = "gain" if int(cfg.saved_feature_importance_type) == 1 \
            else "split"
        try:
            imps = self.feature_importance(itype, num_iteration)
            names = self.train_set.feature_names if self.train_set \
                else getattr(self, "_feature_names", [])
            pairs = [(float(v), names[i] if i < len(names) else
                      "Column_%d" % i) for i, v in enumerate(imps) if v > 0]
            pairs.sort(key=lambda p: -p[0])
            lines.append("")
            lines.append("feature_importances:")
            for v, name in pairs:
                lines.append("%s=%.17g" % (name, v)
                             if itype == "gain" else "%s=%d" % (name, int(v)))
        except Exception:  # importances are informational; never block IO
            pass
        return "\n".join(lines)

    def to_if_else_cpp(self, num_iteration: int = -1) -> str:
        """Standalone C++ prediction source for the whole ensemble
        (reference: gbdt_model_text.cpp:258 ModelToIfElse; also its model-
        correctness regression harness). Emits per-tree if-else functions,
        a PredictRaw accumulator (init scores included) and extern-C
        single-row entry points so the file both drops into user code and
        compiles into a test harness."""
        self.finish_fused("to_if_else_cpp")
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // max(K, 1)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = total_iters
        end = min(total_iters, num_iteration) * K
        parts = [
            "// generated by lightgbm_tpu task=convert_model",
            "#include <cmath>",
            "#include <cstdint>",
            "#include <algorithm>",
            "",
            "static inline bool cat_in(int64_t v, const int64_t* arr, "
            "int n) {",
            "  return std::binary_search(arr, arr + n, v);",
            "}",
            "",
        ]
        for i, tree in enumerate(self.models[:end]):
            parts.append(tree.to_if_else(i))
            parts.append("")
        init = ", ".join("%.17g" % v for v in self.init_scores[:max(K, 1)])
        parts += [
            "static const int kNumClass = %d;" % max(K, 1),
            "static const int kNumTrees = %d;" % end,
            "static const double kInitScore[%d] = {%s};" % (max(K, 1), init),
            "",
            "typedef double (*TreeFn)(const double*);",
            "static const TreeFn kTrees[%d] = {%s};" % (
                max(end, 1),
                ", ".join("PredictTree%d" % i for i in range(end)) or "0"),
            "",
            "extern \"C\" void PredictRaw(const double* arr, double* out) {",
            "  for (int k = 0; k < kNumClass; ++k) out[k] = kInitScore[k];",
            "  for (int i = 0; i < kNumTrees; ++i) {",
            "    out[i % kNumClass] += kTrees[i](arr);",
            "  }",
            "}",
            "",
        ]
        obj = self.objective.name if self.objective else ""
        if obj == "binary":
            sig = self.config.sigmoid
            transform = ("  out[0] = 1.0 / (1.0 + std::exp(-%.17g * "
                         "out[0]));" % sig)
        elif obj in ("multiclassova", "ova"):
            sig = self.config.sigmoid
            transform = ("  for (int k = 0; k < kNumClass; ++k) out[k] = "
                         "1.0 / (1.0 + std::exp(-%.17g * out[k]));" % sig)
        elif obj in ("multiclass", "softmax"):
            transform = (
                "  double m = out[0];\n"
                "  for (int k = 1; k < kNumClass; ++k) m = std::max(m, "
                "out[k]);\n"
                "  double s = 0;\n"
                "  for (int k = 0; k < kNumClass; ++k) { out[k] = "
                "std::exp(out[k] - m); s += out[k]; }\n"
                "  for (int k = 0; k < kNumClass; ++k) out[k] /= s;")
        else:
            transform = "  // identity output transform"
        parts += [
            "extern \"C\" void Predict(const double* arr, double* out) {",
            "  PredictRaw(arr, out);",
            transform,
            "}",
            "",
        ]
        return "\n".join(parts)

    def _objective_string(self) -> str:
        obj = self.objective.name if self.objective else self.config.objective
        if obj in ("multiclass", "multiclassova"):
            return "%s num_class:%d" % (obj, self.num_class)
        return obj

    def save_model(self, filename: str, num_iteration: int = -1) -> None:
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration))

    @classmethod
    def model_from_string(cls, s: str, config: Optional[Config] = None) -> "GBDT":
        config = config or Config()
        header, _, rest = s.partition("Tree=")
        kv: Dict[str, str] = {}
        for line in header.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k] = v
        obj_str = kv.get("objective", "regression").split()
        config.objective = obj_str[0]
        for tok in obj_str[1:]:
            if tok.startswith("num_class:"):
                config.num_class = int(tok.split(":")[1])
        booster_cls = {"gbdt": cls, "dart": DART, "rf": RF}.get(
            kv.get("boosting", "gbdt"), cls)
        model = booster_cls.__new__(booster_cls)
        # run the full subclass constructor chain so DART/RF state
        # (_tree_weights/_drop_rng/_init_score_dev) exists for continued
        # training on a loaded model
        booster_cls.__init__(model, config, None)
        model.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", 1))
        model.num_class = int(kv.get("num_class", 1))
        init = kv.get("init_score", "0").split()
        model.init_scores = np.asarray([float(v) for v in init], dtype=np.float64)
        model.best_iteration = int(kv.get("best_iteration", -1))
        model.objective = create_objective(config)
        # default metrics follow the objective so a loaded model can
        # evaluate valid sets (reference: metric defaults from objective)
        model.metrics = create_metrics(config, model.objective.name)
        model._feature_names = kv.get("feature_names", "").split()
        body = "Tree=" + rest
        for block in body.split("Tree=")[1:]:
            block = block.split("end of trees")[0]
            lines = block.strip().splitlines()[1:]  # drop the index line remnant
            # first line of block is "<idx>\n..." — strip leading index
            model.models.append(Tree.from_text("\n".join(lines)))
        model.iter_ = len(model.models) // max(model.num_tree_per_iteration, 1)
        return model

    def dump_json(self, num_iteration: int = -1) -> str:
        self.finish_fused("dump_json")
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // max(K, 1)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = total_iters
        end = min(total_iters, num_iteration) * K
        d = {
            "name": "tree",
            "version": "v3",
            "objective": self._objective_string(),
            "num_class": self.num_class,
            "num_tree_per_iteration": K,
            "init_score": self.init_scores.tolist(),
            "tree_info": [t.to_dict() for t in self.models[:end]],
        }
        return json.dumps(d)

    @property
    def current_iteration(self) -> int:
        self.finish_fused("current_iteration")
        return self.iter_

    def num_trees(self) -> int:
        self.finish_fused("num_trees")
        return len(self.models)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        """(reference: GBDT::FeatureImportance, gbdt.cpp)"""
        self.finish_fused("feature_importance")
        with self._cache_lock:
            models = list(self.models)
        nf = self.train_set.num_total_features if self.train_set else (
            max((t.split_feature.max() for t in models
                 if t.num_leaves > 1), default=-1) + 1)
        imp = np.zeros(nf, dtype=np.float64)
        K = self.num_tree_per_iteration
        end = len(models) if iteration <= 0 else min(
            len(models), iteration * K)
        for t in models[:end]:
            if t.num_leaves <= 1:
                continue
            for r in range(t.num_internal):
                if importance_type == "split":
                    imp[t.split_feature[r]] += 1
                else:
                    imp[t.split_feature[r]] += max(0.0, float(t.split_gain[r]))
        return imp


class DART(GBDT):
    """Dropout boosting (reference: src/boosting/dart.hpp)."""

    name = "dart"

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 comm_axis: Optional[str] = None) -> None:
        super().__init__(config, train_set, comm_axis)
        self._tree_weights: List[float] = []
        self._drop_rng = np.random.RandomState(config.drop_seed)

    def train_one_iter(self, grad=None, hess=None) -> bool:
        cfg = self.config
        K = self.num_tree_per_iteration
        # ---- select and subtract the drop set (dart.hpp:97 DroppingTrees) ----
        drop: List[int] = []
        if self._drop_rng.rand() >= cfg.skip_drop and self.iter_ > 0:
            n_iters = self.iter_
            if cfg.uniform_drop:
                sel = self._drop_rng.rand(n_iters) < cfg.drop_rate
                drop = list(np.flatnonzero(sel))
            else:
                p = min(1.0, cfg.drop_rate)
                k_drop = min(cfg.max_drop, np.random.RandomState(
                    cfg.drop_seed + self.iter_).binomial(n_iters, p))
                if k_drop > 0:
                    drop = list(self._drop_rng.choice(n_iters, size=k_drop,
                                                      replace=False))
        for it_idx in drop:
            for k in range(K):
                tree = self.models[it_idx * K + k]
                self._apply_tree_delta(tree, k, -1.0)
        k_cnt = len(drop)
        # ---- train on the reduced score ----
        stop = super().train_one_iter(grad, hess)
        if stop:
            # restore the dropped trees untouched so score trackers stay
            # consistent when no tree could be grown
            for it_idx in drop:
                for k in range(K):
                    self._apply_tree_delta(self.models[it_idx * K + k], k, 1.0)
            return stop
        # ---- normalize (dart.hpp:65 Normalize) ----
        if not stop:
            norm = 1.0 / (k_cnt + 1.0)
            if cfg.xgboost_dart_mode:
                norm = cfg.learning_rate / (k_cnt + cfg.learning_rate)
            # normalization mutates committed trees in place AFTER the
            # super() bump — run it (and the re-bump) under the model
            # lock so a concurrent pack never captures half-rescaled
            # leaf values, then bump so stale packs invalidate
            with self._cache_lock:
                for k in range(K):
                    tree = self.models[-K + k]
                    # remove the freshly-added (unnormalized)
                    # contribution, rescale
                    self._apply_tree_delta(tree, k, norm - 1.0)
                    tree.apply_shrinkage(norm)
                if k_cnt > 0:
                    factor = k_cnt / (k_cnt + 1.0)
                    if cfg.xgboost_dart_mode:
                        factor = k_cnt / (k_cnt + cfg.learning_rate)
                    for it_idx in drop:
                        for k in range(K):
                            tree = self.models[it_idx * K + k]
                            self._apply_tree_delta(tree, k, factor)
                            tree.apply_shrinkage(factor)
                self._bump_model_version()
        return stop

    def _shrinkage_rate(self, log: TreeLog) -> float:
        # DART applies learning_rate at train time, normalization after
        return float(self.config.learning_rate)

    def _apply_tree_delta(self, tree: Tree, class_id: int, scale: float) -> None:
        """Add ``scale`` × tree's contribution to train/valid scores."""
        vals, leaf = self._route_tree_device(tree, self.train_set)
        self.train_score.add(vals, leaf, class_id,
                             self.num_tree_per_iteration, scale=scale)
        for _, vset, vscore in self.valid_sets:
            vvals, vleaf = self._route_tree_device(tree, vset)
            vscore.add(vvals, vleaf, class_id,
                       self.num_tree_per_iteration, scale=scale)


class RF(GBDT):
    """Random forest mode (reference: src/boosting/rf.hpp): bagging is
    mandatory, no shrinkage, scores are the average of tree outputs, and
    gradients are always computed at the (constant) init score."""

    name = "rf"

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 comm_axis: Optional[str] = None) -> None:
        super().__init__(config, train_set, comm_axis)
        self._init_score_dev = None
        if train_set is not None:
            self._init_score_dev = self.train_score.score

    def train_one_iter(self, grad=None, hess=None) -> bool:
        if grad is None:
            g, h = self._grad_fn(self._init_score_dev, jnp.int32(self.iter_))
        else:
            g, h = jnp.asarray(grad, jnp.float32), jnp.asarray(hess, jnp.float32)
        it = self.iter_
        self._bagging(it, g, h)
        fmask = self._feature_mask(it)
        any_ok = False
        for k in range(self.num_tree_per_iteration):
            ghc = self._tree_channels(g, h, k)
            key = jax.random.fold_in(self._key, it * 131 + k)
            log = self.learner.train(ghc, fmask, key,
                                     jnp.asarray(self._cegb_used))
            tree = self.learner.log_to_tree(log)
            # averaged score: rescale previous sum then add (ref rf.hpp)
            with self._cache_lock:
                self.models.append(tree)
            self._note_used_features(tree)
            self._accumulate_avg(tree, log, k)
            if tree.num_leaves > 1:
                any_ok = True
        with self._cache_lock:
            self.iter_ += 1
            self._bump_model_version()
        return not any_ok

    def _accumulate_avg(self, tree: Tree, log: TreeLog, class_id: int) -> None:
        it = self.iter_  # completed iterations before this one
        K = self.num_tree_per_iteration
        # running average over iterations: new_avg = (old*it + tree)/(it+1)
        if self.num_class > 1:
            init_col = self.init_scores[class_id]
            old = self.train_score.score[:, class_id] - init_col
            lv = jnp.asarray(tree.leaf_value, jnp.float32)
            new = (old * it + leaf_values_by_row(lv, log.row_leaf, lv.shape[0])) \
                / (it + 1)
            self.train_score.score = self.train_score.score.at[:, class_id].set(
                new + init_col)
        else:
            old = self.train_score.score - self.init_scores[0]
            lv = jnp.asarray(tree.leaf_value, jnp.float32)
            new = (old * it + leaf_values_by_row(lv, log.row_leaf, lv.shape[0])) \
                / (it + 1)
            self.train_score.score = new + self.init_scores[0]
        for _, vset, vscore in self.valid_sets:
            vleaf = assign_leaves(
                self._valid_bins(vset), log,
                has_categorical=self.learner.hp.has_categorical,
                bundle=self.learner.bundle)
            lv = jnp.asarray(tree.leaf_value, jnp.float32)
            vals = leaf_values_by_row(lv, vleaf, lv.shape[0])
            if self.num_class > 1:
                init_col = self.init_scores[class_id]
                old = vscore.score[:, class_id] - init_col
                vscore.score = vscore.score.at[:, class_id].set(
                    (old * it + vals) / (it + 1) + init_col)
            else:
                old = vscore.score - self.init_scores[0]
                vscore.score = (old * it + vals) / (it + 1) + self.init_scores[0]

    def predict(self, X, *, raw_score=False, start_iteration=0,
                num_iteration=-1, pred_leaf=False):
        X = np.asarray(X, dtype=np.float64)
        n = X.shape[0]
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // max(K, 1)
        if num_iteration is None or num_iteration <= 0:
            num_iteration = total_iters - start_iteration
        end = min(total_iters, start_iteration + num_iteration)
        if pred_leaf:
            return super().predict(X, raw_score=raw_score,
                                   start_iteration=start_iteration,
                                   num_iteration=num_iteration, pred_leaf=True)
        cnt = max(1, end - start_iteration)
        score = self._raw_scores(X, start_iteration, end) / cnt
        score = score + self.init_scores[None, :K]
        if not raw_score and self.objective is not None:
            score = np.asarray(self.objective.convert_output(jnp.asarray(score)))
        return score.ravel() if K == 1 else score


def create_boosting(config: Config, train_set: Optional[BinnedDataset],
                    comm_axis: Optional[str] = None) -> GBDT:
    """Factory (reference: src/boosting/boosting.cpp:35 CreateBoosting)."""
    kind = config.boosting
    if kind in ("gbdt", "gbrt", "goss"):
        return GBDT(config, train_set, comm_axis)
    if kind == "dart":
        return DART(config, train_set, comm_axis)
    if kind in ("rf", "random_forest"):
        return RF(config, train_set, comm_axis)
    Log.fatal("Unknown boosting type: %s", kind)
