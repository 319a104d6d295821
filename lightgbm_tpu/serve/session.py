"""Device-resident prediction session with a shape-bucket ladder.

The training-path device predict (boosting._raw_scores_range) used to
re-pack the ensemble on host per call and retrace ``predict_raw`` for
every distinct row count. A :class:`PredictSession` fixes both:

- the packed ensemble is fetched through the booster's version-keyed
  ``_packed_model`` cache (device-resident ``PackedSplits``; the
  ``device_resident_planes`` pattern applied to inference) and refreshed
  only when the model-version token moves;
- row counts are rounded UP to a fixed bucket ladder, the batch is padded
  to the bucket and the result sliced back, so the bucketed predict
  compiles once per rung instead of once per distinct N. Row routing is
  row-independent, so padding never changes real rows' scores.

A pre-binned fast path (:meth:`predict_binned`) routes in BIN space via
``tree_to_bin_log``/``assign_leaves`` when the caller holds a constructed
``Dataset`` — no raw-threshold comparisons, reusing the training router.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import telemetry, track_jit
from ..obs_trace import tracer
from ..ops.predict import predict_raw_impl
from ..utils.log import LightGBMError

#: Default bucket ladder. Rungs are ~4x apart: at most ~25% of a dispatch
#: is padding in the worst case, and a full warmup compiles 5 programs.
DEFAULT_BUCKETS = (256, 1024, 4096, 16384, 65536)

# one process-wide jit shared by every session: packs come from the
# per-booster _packed_model cache, so two sessions over the same booster
# (or a session recreated after restart-free model reloads) hit the same
# compiled executables
_predict_bucket = track_jit("serve/predict_bucket", jax.jit(
    predict_raw_impl,
    static_argnames=("num_class", "has_cat", "has_linear", "tree_batch")))


class PredictSession:
    """Serving handle over a trained booster (``lgb.Booster`` or inner
    ``GBDT``): device-resident pack + shape-bucketed compiled predict.

    Thread-safe for concurrent ``predict``/``raw_scores`` calls; pair with
    :class:`~lightgbm_tpu.serve.batcher.MicroBatcher` to coalesce many
    small requests into one dispatch.
    """

    def __init__(self, model, *, start_iteration: int = 0,
                 num_iteration: int = -1,
                 buckets: Optional[Sequence[int]] = None) -> None:
        self._gbdt = getattr(model, "inner", model)
        if start_iteration < 0:
            raise LightGBMError("start_iteration must be >= 0")
        self._start = int(start_iteration)
        self._num = int(num_iteration)
        rungs = tuple(sorted({int(b) for b in (buckets or DEFAULT_BUCKETS)}))
        if not rungs or rungs[0] < 1:
            raise LightGBMError("serve buckets must be positive ints")
        self.buckets = rungs
        self._lock = threading.Lock()
        self._pack = None
        self._has_cat = False
        self._has_linear = False
        self._K = max(1, int(self._gbdt.num_tree_per_iteration))
        self._version = -1
        self._range = (0, 0)
        self._warm: set = set()

    # ------------------------------------------------------------ resolution
    def num_features(self) -> int:
        """Feature count for warmup batches (train_set, loaded feature
        names, or max split feature as a last resort)."""
        g = self._gbdt
        if g.train_set is not None:
            return int(g.train_set.num_total_features)
        names = getattr(g, "_feature_names", None)
        if names:
            return len(names)
        mx = -1
        for t in g.models:
            if t.num_leaves > 1:
                mx = max(mx, int(t.split_feature[:t.num_internal].max()))
        return mx + 1

    def bucket_for(self, rows: int) -> int:
        """Smallest ladder rung covering ``rows`` (the top rung for counts
        beyond the ladder — larger batches dispatch in top-rung chunks)."""
        for b in self.buckets:
            if rows <= b:
                return b
        return self.buckets[-1]

    def _resolve_range(self) -> Tuple[int, int]:
        g = self._gbdt
        total = len(g.models) // self._K
        end = total if self._num <= 0 else min(total, self._start + self._num)
        return self._start, max(self._start, end)

    def _ensure_pack(self):
        """Refresh the device-resident pack iff the model version (or the
        resolved iteration range) moved; returns (pack, has_cat,
        has_linear)."""
        g = self._gbdt
        # lock order is session -> booster (nothing takes them the other
        # way round). Holding the booster's model lock across the
        # version read, range resolution and pack build pins one
        # (models, version) pair — a concurrent training commit lands
        # wholly before or wholly after this snapshot, never inside it.
        with self._lock, g._cache_lock:
            ver = g.model_version
            rng = self._resolve_range()
            if self._pack is None or ver != self._version \
                    or rng != self._range:
                self._pack, self._has_cat, self._has_linear = \
                    g._packed_model(*rng)
                self._version, self._range = ver, rng
                # pack shapes may have changed -> compiled rungs are stale
                self._warm.clear()
            return self._pack, self._has_cat, self._has_linear

    def version(self) -> int:
        """Model-version token of the currently-resident pack (-1 before
        the first dispatch). The online promotion gate's observable: a
        promoted candidate moves it, a rejected one must not."""
        with self._lock:
            return self._version

    def pack_fingerprint(self) -> str:
        """Content hash (sha256 hex) over every array of the resident
        pack. Test/debug hook for the online promotion contract: after a
        REJECTED candidate the serving pack must be byte-identical, after
        a promotion it must differ. Pulls the pack to host — never call
        on the hot path."""
        import hashlib

        pack, _, _ = self._ensure_pack()
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(pack):
            arr = np.asarray(leaf)  # graftlint: disable=host-sync
            h.update(str(arr.dtype).encode())
            h.update(str(arr.shape).encode())
            h.update(arr.tobytes())
        return h.hexdigest()

    # -------------------------------------------------------------- dispatch
    def dispatch(self, X) -> List[Tuple[jax.Array, int]]:
        """Bucketed device dispatch; returns [(device scores, real rows)].

        No device->host sync happens here — callers (raw_scores, the
        MicroBatcher) pull results when delivering them. N beyond the top
        rung is chunked; each chunk pads up to its covering bucket.
        """
        pack, has_cat, has_linear = self._ensure_pack()
        X = np.ascontiguousarray(np.asarray(X), dtype=np.float32)
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2:
            raise LightGBMError("predict expects a 2-D (rows, features) "
                                "array, got ndim=%d" % X.ndim)
        n = X.shape[0]
        pieces: List[Tuple[jax.Array, int]] = []
        if n == 0:
            return pieces
        nf = X.shape[1]
        top = self.buckets[-1]
        telemetry.count("serve/dispatches")
        # async dispatch only — the span ends when every chunk is queued,
        # not when the device finishes (that wait is serve/slice_back)
        with tracer.span("serve/session_dispatch", domain="serve", rows=n):
            for lo in range(0, n, top):
                chunk = X[lo:lo + top]
                rows = chunk.shape[0]
                b = self.bucket_for(rows)
                with self._lock:
                    warm = b in self._warm
                    self._warm.add(b)
                telemetry.count(
                    "serve/bucket_hit" if warm else "serve/bucket_miss")
                if b > rows:
                    telemetry.count("serve/pad_rows", b - rows)
                    chunk = np.concatenate(
                        [chunk, np.zeros((b - rows, nf), chunk.dtype)])
                score = _predict_bucket(jnp.asarray(chunk), pack,
                                        num_class=self._K, has_cat=has_cat,
                                        has_linear=has_linear)
                pieces.append((score, rows))
        return pieces

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> "PredictSession":
        """Pre-compile the bucketed predict for the given row counts (the
        full ladder by default). Each count warms its covering rung, so a
        warmed rung costs at most one compile."""
        nf = max(1, self.num_features())
        for b in sorted({self.bucket_for(int(v))
                         for v in (buckets or self.buckets)}):
            self.dispatch(np.zeros((b, nf), np.float32))
            # warm the output transform at the rung shape too — finalize
            # evaluates convert_output at bucket shapes (see below), so a
            # warmed rung pays zero compiles end to end
            self.finalize(np.zeros((b, self._K), np.float64))
        return self

    # --------------------------------------------------------------- results
    def raw_scores(self, X) -> np.ndarray:
        """(n, F) raw rows -> (n, K) float64 raw ensemble sums (no init
        score, no output transform) — the boosting _raw_scores_range
        contract."""
        pieces = self.dispatch(X)
        if not pieces:
            return np.zeros((0, self._K), np.float64)
        outs = [np.asarray(s, np.float64)[:r] for s, r in pieces]
        raw = outs[0] if len(outs) == 1 else np.concatenate(outs)
        return raw.reshape(len(raw), -1) if raw.ndim == 1 else raw

    def finalize(self, raw: np.ndarray, *, raw_score: bool = False) -> np.ndarray:
        """Raw ensemble sums -> final predictions: RF averaging, init
        scores, objective output transform, (n,) squeeze for K == 1."""
        g = self._gbdt
        score = np.asarray(raw, np.float64)
        score = score.reshape(len(score), -1)
        if g.name == "rf":
            start, end = self._range if self._pack is not None \
                else self._resolve_range()
            score = score / max(1, end - start)
        score = score + g.init_scores[None, :self._K]
        if not raw_score and g.objective is not None:
            # evaluate the (row-independent) output transform at the
            # covering bucket shape: convert_output is eager jax, which
            # compiles per distinct shape — without padding every new
            # coalesced batch size would pay a compile at delivery time
            n = len(score)
            b = self.bucket_for(n)
            if 0 < n < b:
                score = np.concatenate(
                    [score, np.zeros((b - n, score.shape[1]), np.float64)])
            score = np.asarray(
                g.objective.convert_output(jnp.asarray(score)),
                np.float64)[:n]
        return score.ravel() if self._K == 1 else score

    def predict(self, X, *, raw_score: bool = False) -> np.ndarray:
        """Full prediction for raw feature rows (pads to the covering
        bucket, slices back; parity with ``Booster.predict``)."""
        X = np.asarray(X, np.float64)
        if X.ndim == 1:
            X = X[None, :]
        telemetry.count("serve/requests")
        telemetry.count("serve/rows", X.shape[0])
        return self.finalize(self.raw_scores(X), raw_score=raw_score)

    def predict_binned(self, dataset, *, raw_score: bool = False) -> np.ndarray:
        """Pre-binned fast path: route a constructed ``Dataset`` in BIN
        space via ``tree_to_bin_log`` + the training router — no raw
        thresholds, and the per-tree bin logs are cached per (tree,
        dataset) like DART score replay."""
        from ..boosting import ScoreTracker

        g = self._gbdt
        binned = dataset.construct() if hasattr(dataset, "construct") \
            else dataset
        start, end = self._resolve_range()
        K = self._K
        n = binned.num_data
        telemetry.count("serve/requests")
        telemetry.count("serve/rows", n)
        telemetry.count("serve/binned_requests")
        ts = ScoreTracker(n, K, np.zeros(K, np.float64))
        linear_extra = None
        for i, tree in enumerate(g.models[start * K:end * K]):
            vals, leaf = g._route_tree_device(tree, binned)
            if getattr(tree, "is_linear", False) \
                    and binned.raw_numeric is not None:
                # linear leaves need raw feature values; the router
                # returns to_split_arrays SLOTS — map to LEAF ids for the
                # coefficient lookup (boosting._linear_score_updates)
                leaf_of_slot = tree.to_split_arrays()["leaf_of_slot"]
                rv = tree.linear_predict(
                    binned.raw_numeric.astype(np.float64),
                    leaf_of_slot[np.asarray(leaf)])  # graftlint: disable=host-sync
                if linear_extra is None:
                    linear_extra = np.zeros((n, K), np.float64)
                linear_extra[:, i % K] += rv
                continue
            ts.add(vals, leaf, i % K, K)
        raw = np.asarray(ts.np(), np.float64).reshape(n, -1)
        if linear_extra is not None:
            raw = raw + linear_extra
        return self.finalize(raw, raw_score=raw_score)
