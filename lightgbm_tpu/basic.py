"""User-facing Dataset and Booster.

Equivalent of the reference python package's ctypes layer
(reference: python-package/lightgbm/basic.py:1035 Dataset, :2142 Booster) —
except there is no C ABI to cross: the "native" side here is the jitted
JAX/XLA program, so Dataset wraps BinnedDataset construction lazily and
Booster wraps the boosting driver directly.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config, resolve_aliases
from .dataset import BinnedDataset, construct_dataset
from .boosting import GBDT, create_boosting
from .obs import TimerMark, host_phase, telemetry
from .utils.log import Log, LightGBMError


# part of the dataset_construct record -> the timer of its host phase
_CONSTRUCT_PARTS = {"total_s": "construct/total", "copy_s": "construct/copy",
                    "find_bins_s": "construct/find_bins",
                    "bundle_s": "construct/bundle",
                    "bin_rows_s": "construct/bin_rows"}


def _to_2d(data) -> np.ndarray:
    if hasattr(data, "toarray"):  # scipy sparse
        data = data.toarray()
    data = _frame_values(data)
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


def _frame_values(data):
    """pandas DataFrame -> float matrix; category columns become their codes
    (reference: python-package/lightgbm/basic.py _data_from_pandas)."""
    if hasattr(data, "dtypes") and hasattr(data, "columns") \
            and not isinstance(data, np.ndarray):
        import pandas as pd
        out = np.empty((len(data), data.shape[1]), dtype=np.float64)
        for j, col in enumerate(data.columns):
            c = data[col]
            if isinstance(c.dtype, pd.CategoricalDtype):
                codes = c.cat.codes.to_numpy().astype(np.float64)
                codes[codes < 0] = np.nan
                out[:, j] = codes
            else:
                out[:, j] = pd.to_numeric(c, errors="coerce").to_numpy(
                    dtype=np.float64)
        return out
    if hasattr(data, "values") and not isinstance(data, np.ndarray):
        return data.values
    return data


def _pandas_categorical_columns(data):
    """Indices of pandas category-dtype columns (categorical_feature='auto'
    semantics of the reference python package)."""
    if hasattr(data, "dtypes") and hasattr(data, "columns") \
            and not isinstance(data, np.ndarray):
        import pandas as pd
        return [j for j, col in enumerate(data.columns)
                if isinstance(data[col].dtype, pd.CategoricalDtype)]
    return []


def _to_1d(data) -> Optional[np.ndarray]:
    if data is None:
        return None
    if hasattr(data, "values") and not isinstance(data, np.ndarray):
        data = data.values
    return np.asarray(data).ravel()


class Dataset:
    """Lazily-constructed training dataset (reference: basic.py:1035).
    Binning happens at ``construct()`` (inside ``train``), so parameters set
    afterwards still apply — mirroring the reference's lazy ``_lazy_init``."""

    def __init__(self, data, label=None, *, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = False) -> None:
        self.data = data
        self.label = _to_1d(label)
        self.weight = _to_1d(weight)
        self.group = _to_1d(group)
        self.init_score = None if init_score is None else np.asarray(init_score)
        self.reference = reference
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._constructed: Optional[BinnedDataset] = None
        self._used_params: Optional[Dict[str, Any]] = None

    # -- setters mirroring the reference API --
    def set_label(self, label) -> "Dataset":
        self.label = _to_1d(label)
        if self._constructed is not None:
            self._constructed.metadata.label = np.ascontiguousarray(
                self.label, dtype=np.float32)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = _to_1d(weight)
        if self._constructed is not None:
            self._constructed.metadata.weight = None if weight is None else \
                np.ascontiguousarray(self.weight, dtype=np.float32)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = _to_1d(group)
        self._constructed = None
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = None if init_score is None else np.asarray(init_score)
        self._constructed = None
        return self

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        if self._constructed is not None and \
                self._constructed.metadata.query_boundaries is not None:
            return np.diff(self._constructed.metadata.query_boundaries)
        return self.group

    def get_init_score(self):
        return self.init_score

    def num_data(self) -> int:
        if self._constructed is not None:
            return self._constructed.num_data
        return _to_2d(self.data).shape[0]

    def num_feature(self) -> int:
        if self._constructed is not None:
            return self._constructed.num_total_features
        return _to_2d(self.data).shape[1]

    def construct(self, params: Optional[Dict[str, Any]] = None) -> BinnedDataset:
        if self._constructed is not None and self.data is None:
            # externally constructed (two-round loader): binning is fixed
            return self._constructed
        merged = dict(self.params)
        if params:
            merged.update(params)
        if self._constructed is not None and self._used_params == merged:
            return self._constructed
        cfg = Config.from_params(merged)
        if isinstance(self.data, str):
            # binary dataset cache (reference: LoadFromBinFile,
            # dataset_loader.cpp:314); explicitly-passed metadata overrides
            # the cached copy
            from .dataset import Metadata as _Meta
            from .dataset import load_binned
            ds = load_binned(self.data)
            if any(v is not None for v in
                   (self.label, self.weight, self.group, self.init_score)):
                md = _Meta(ds.num_data, _to_1d(self.label),
                           _to_1d(self.weight), _to_1d(self.group),
                           self.init_score)
                for f in ("label", "weight", "init_score",
                          "query_boundaries", "query_id"):
                    v = getattr(md, f)
                    if v is not None:
                        setattr(ds.metadata, f, v)
            if self.reference is not None:
                Log.warning("reference= is ignored for binary-cache "
                            "datasets (binning is already fixed)")
            self._constructed = ds
            self._used_params = merged
            return self._constructed
        # the reference's own construction is a record of its own
        ref_binned = self.reference.construct(params) if self.reference else None
        mark = TimerMark(_CONSTRUCT_PARTS)
        with host_phase("lgbtpu/construct"):
            if hasattr(self.data, "tocsc"):     # scipy sparse: stays O(nnz)
                X = self.data
            else:
                with host_phase("lgbtpu/construct_copy"):
                    X = _to_2d(self.data)
            feature_names = None
            if isinstance(self.feature_name, (list, tuple)):
                feature_names = list(self.feature_name)
            elif hasattr(self.data, "columns"):
                feature_names = [str(c) for c in self.data.columns]
            cat = self.categorical_feature
            if cat == "auto":
                auto_cats = _pandas_categorical_columns(self.data)
                cat = auto_cats if auto_cats else None
            self._constructed = construct_dataset(
                X, cfg, label=self.label, weight=self.weight,
                group=self.group, init_score=self.init_score,
                feature_names=feature_names, categorical_feature=cat,
                reference=ref_binned)
        parts = mark.grown()
        total = parts.pop("total_s")
        ds = self._constructed
        telemetry.record("dataset_construct", total_s=total,
                         other_s=total - sum(parts.values()),
                         rows=X.shape[0], features=X.shape[1],
                         groups=ds.num_groups,
                         bundled_features=ds.bundled_features,
                         sample_conflicts=ds.efb_sample_conflicts,
                         conflict_rows=ds.efb_conflict_rows,
                         cat_other_bin_rows=ds.cat_other_bin_rows, **parts)
        self._used_params = merged
        if self.free_raw_data:
            self.data = None
        return self._constructed

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params)

    def save_binary(self, filename: str) -> "Dataset":
        """Cache the fully-constructed binned dataset (reference:
        Dataset::SaveBinaryFile, dataset.h:441); ``Dataset(path)`` loads it
        back without re-parsing or re-binning."""
        from .dataset import save_binned
        save_binned(self.construct(), filename)
        return self


class Booster:
    """Training-capable model handle (reference: basic.py:2142)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 comm_axis: Optional[str] = None) -> None:
        params = params or {}
        self.params = params
        self.train_dataset = train_set
        self._valid_names: List[str] = []
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("train_set must be a Dataset")
            binned = train_set.construct(params)
            self.config = Config.from_params(params)
            self.inner: GBDT = create_boosting(self.config, binned, comm_axis)
        elif model_file is not None:
            with open(model_file) as f:
                self.inner = GBDT.model_from_string(f.read())
            self.config = self.inner.config
        elif model_str is not None:
            self.inner = GBDT.model_from_string(model_str)
            self.config = self.inner.config
        else:
            raise LightGBMError("Need train_set, model_file or model_str")
        # span tracing is process-global: only an EXPLICIT trace_spans
        # param flips it, so a second Booster built with defaults cannot
        # silently turn off a tracer something else switched on
        if "trace_spans" in params:
            from .obs_trace import tracer
            tracer.configure(str(params["trace_spans"]),
                             int(params.get("trace_buffer_events", 0)) or None)
        # loaded models keep their stored best_iteration so predict()
        # defaults to the early-stopped tree count like the reference
        self.best_iteration = self.inner.best_iteration if train_set is None else -1
        self.best_score: Dict[str, Dict[str, float]] = {}

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if data.reference is None:
            data.reference = self.train_dataset
        binned = data.construct(self.params)
        self.inner.add_valid(name, binned)
        self._valid_names.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if stopped
        (reference: basic.py:2565 update / __boost)."""
        if train_set is not None:
            raise LightGBMError("Resetting train_set is not supported yet")
        if fobj is not None:
            grad, hess = fobj(np.asarray(self.inner.train_score.score),
                              self.train_dataset)
            return self.inner.train_one_iter(np.asarray(grad), np.asarray(hess))
        return self.inner.train_one_iter()

    def rollback_one_iter(self) -> "Booster":
        self.inner.rollback_one_iter()
        return self

    @property
    def current_iteration(self) -> int:
        return self.inner.current_iteration

    def num_trees(self) -> int:
        return self.inner.num_trees()

    def num_model_per_iteration(self) -> int:
        return self.inner.num_tree_per_iteration

    def telemetry(self) -> Dict[str, Any]:
        """JSON-serializable snapshot of the process-global telemetry
        registry (phase timers, dataset device-cache hit/miss counts,
        fused-pipeline dispatch/flush counters, per-tree growth stats and
        ``auto`` knob resolutions). See :mod:`lightgbm_tpu.obs`."""
        from .obs import telemetry
        return telemetry.snapshot()

    def dump_trace(self, path: str) -> int:
        """Write the span flight recorder as Chrome trace-event JSON —
        load the file in Perfetto (ui.perfetto.dev) or chrome://tracing.
        Spans only record while ``trace_spans=on|serve_only``; returns
        the number of trace events written. See
        :mod:`lightgbm_tpu.obs_trace`."""
        from .obs_trace import tracer
        return tracer.dump(path)

    def eval_train(self, feval=None):
        return self.inner.eval_train(feval)

    def eval_valid(self, feval=None):
        return self.inner.eval_valid(feval)

    def predict(self, data, *, raw_score: bool = False, start_iteration: int = 0,
                num_iteration: Optional[int] = None, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        X = _to_2d(data)
        expected = self.num_feature()
        if expected > 0 and X.shape[1] != expected \
                and not self.config.predict_disable_shape_check:
            from .utils.log import Log
            Log.fatal(
                "The number of features in data (%d) is not the same as in "
                "the model (%d). Set predict_disable_shape_check=true to "
                "bypass (reference: LGBM_BoosterPredict shape check).",
                X.shape[1], expected)
        if num_iteration is None:
            # early stopping: default to the best iteration like the
            # reference python package (basic.py Booster.predict)
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if pred_contrib:
            return self._predict_contrib(X, num_iteration)
        ni = num_iteration
        return self.inner.predict(X, raw_score=raw_score,
                                  start_iteration=start_iteration,
                                  num_iteration=ni, pred_leaf=pred_leaf)

    def _predict_contrib(self, X: np.ndarray, num_iteration) -> np.ndarray:
        """SHAP-style contributions via path-attribution on each tree
        (reference: TreeSHAP in src/io/tree.cpp). Round-1 implementation:
        exact SHAP for each tree computed on host."""
        from .shap import tree_shap_contribs
        return tree_shap_contribs(self.inner, X, num_iteration)

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        ni = -1 if num_iteration is None else num_iteration
        with self.inner._cache_lock:
            self.inner.best_iteration = self.best_iteration
        self.inner.save_model(filename, ni)
        return self

    def model_to_string(self, num_iteration: Optional[int] = None) -> str:
        ni = -1 if num_iteration is None else num_iteration
        return self.inner.model_to_string(ni)

    def dump_model(self, num_iteration: Optional[int] = None) -> Dict[str, Any]:
        import json
        ni = -1 if num_iteration is None else num_iteration
        return json.loads(self.inner.dump_json(ni))

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        it = -1 if iteration is None else iteration
        return self.inner.feature_importance(importance_type, it)

    def feature_name(self) -> List[str]:
        if self.inner.train_set is not None:
            return self.inner.train_set.feature_names
        return getattr(self.inner, "_feature_names", [])

    def num_feature(self) -> int:
        """Number of features the model was trained on (reference:
        LGBM_BoosterGetNumFeature); -1 when unknown (featureless model)."""
        if self.inner.train_set is not None:
            return self.inner.train_set.num_total_features
        names = getattr(self.inner, "_feature_names", None)
        if names:
            return len(names)
        return -1

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """(reference: Booster::ResetConfig path, gbdt.cpp:684)"""
        self.params.update(params)
        self.config.set(params)
        inner = self.inner
        # refresh learner hyperparameters that affect future trees,
        # PRESERVING the learner class: a Data/Feature/Voting mesh learner
        # must not silently downgrade to SerialTreeLearner mid-training
        # under the model lock: serving threads may read the learner while
        # we swap it
        with inner._cache_lock:
            if inner.learner is not None:
                from .parallel.mesh import _MeshTreeLearner, \
                    create_tree_learner
                mesh = inner.learner.mesh \
                    if isinstance(inner.learner, _MeshTreeLearner) else None
                inner.learner = create_tree_learner(
                    self.config, inner.train_set, mesh)
        # drop cached state derived from the old config (samplers, column
        # masks, fused block functions)
        for attr in ("_sampler_fn", "_fmask_fn"):
            if hasattr(inner, attr):
                delattr(inner, attr)
        inner._fused = None
        return self

    def refit(self, data, label, decay_rate: Optional[float] = None,
              weight=None, group=None, **kwargs):
        """Refit leaf values on new data (reference: GBDT::RefitTree,
        gbdt.cpp:285; python Booster.refit).

        ``weight``/``group`` carry the new data's metadata — ranking and
        weighted objectives need them to form correct gradients (a bare
        label stub would crash lambdarank or silently mis-weight)."""
        decay = self.config.refit_decay_rate if decay_rate is None else decay_rate
        X = _to_2d(data)
        y = _to_1d(label)
        new_booster = Booster(model_str=self.model_to_string())
        K = new_booster.inner.num_tree_per_iteration
        score = np.zeros((X.shape[0], K))
        score += new_booster.inner.init_scores[None, :K]
        from .dataset import Metadata
        meta = Metadata(num_data=len(y), label=np.asarray(y, np.float32),
                        weight=None if weight is None else _to_1d(weight),
                        group=None if group is None else _to_1d(group))
        obj = new_booster.inner.objective
        if obj.is_ranking and meta.query_boundaries is None:
            from .utils.log import Log
            Log.fatal("refit with a ranking objective requires group=")
        obj.init(meta)
        # the candidate is private to this call, but refit also runs on the
        # OnlineTrainer worker thread — rewrite its leaves under its model
        # lock so the leaf-value mutations and the final version bump land
        # as one committed model for any session handed the candidate
        with new_booster.inner._cache_lock:
            for i, tree in enumerate(new_booster.inner.models):
                leaf_idx = tree.predict_leaf_index(X)
                # grad at current score for this class
                import jax.numpy as jnp
                s = jnp.asarray(score if K > 1 else score.ravel(), jnp.float32)
                g, h = obj.get_gradients(s)
                g = np.asarray(g).reshape(len(y), -1)[:, i % K]
                h = np.asarray(h).reshape(len(y), -1)[:, i % K]
                lam = new_booster.config.lambda_l2
                for l in range(tree.num_leaves):
                    m = leaf_idx == l
                    if np.any(m):
                        new_val = -g[m].sum() / (h[m].sum() + lam)
                        tree.leaf_value[l] = decay * tree.leaf_value[l] + \
                            (1 - decay) * new_val * tree.shrinkage
                        if getattr(tree, "is_linear", False):
                            # linear leaves OUTPUT leaf_const (+ coeffs);
                            # decay it the same way or refit would only
                            # move the NaN-fallback value
                            tree.leaf_const[l] = \
                                decay * tree.leaf_const[l] + \
                                (1 - decay) * new_val * tree.shrinkage
                score[:, i % K] += tree.predict(X)
            # leaf values were rewritten in place on the fresh booster's trees
            new_booster.inner._bump_model_version()
        return new_booster

    def adopt(self, other: "Booster") -> tuple:
        """Atomically swap this booster's served model for ``other``'s
        (online promotion: single version bump under the model lock, so
        concurrent PredictSessions see old-or-new, never a mix). Returns
        a rollback token for :meth:`restore`."""
        token = self.inner.adopt(getattr(other, "inner", other))
        # keep the wrapper's predict-default cap in step with the swap
        with self.inner._cache_lock:
            self.best_iteration = self.inner.best_iteration
        return token

    def restore(self, snapshot: tuple) -> "Booster":
        """Roll back to a model captured by :meth:`adopt`."""
        self.inner.restore(snapshot)
        with self.inner._cache_lock:
            self.best_iteration = self.inner.best_iteration
        return self


def register_logger(logger) -> None:
    """Redirect framework logging to a python logging.Logger
    (reference: basic.py register_logger)."""
    Log.reset_callback(lambda msg: logger.info(msg.rstrip("\n")))
