"""Objective functions: gradients/hessians on device.

TPU-native equivalent of the reference objective zoo (reference:
src/objective/objective_function.cpp:15 factory; regression_objective.hpp,
binary_objective.hpp, multiclass_objective.hpp, rank_objective.hpp,
xentropy_objective.hpp). All gradient math is pure jnp — elementwise O(N)
fused by XLA; ranking objectives vectorize the reference's per-query pair
loops (rank_objective.hpp:54) into padded (query, doc) arrays with the
truncation-level cap expressed as a top-k slice instead of a loop bound.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config, OBJECTIVE_ALIASES
from .dataset import Metadata
from .obs import telemetry, trace_phase
from .utils.log import Log


def _weighted(grad, hess, weight):
    if weight is None:
        return grad, hess
    return grad * weight, hess * weight


def _percentile_weighted(values: np.ndarray, weights: Optional[np.ndarray],
                         alpha: float) -> float:
    """Weighted alpha-percentile with the reference's interpolation semantics
    (reference: regression_objective.hpp:18 PercentileFun, :50
    WeightedPercentileFun — including its boundary quirks)."""
    n = len(values)
    if n == 0:
        return 0.0
    if n <= 1:
        return float(values[0])
    if weights is None:
        v = np.sort(values)
        float_pos = (1.0 - alpha) * n
        pos = int(float_pos)
        if pos < 1:
            return float(v[-1])
        if pos >= n:
            return float(v[0])
        bias = float_pos - pos
        v1 = v[n - pos]          # pos-th largest
        v2 = v[n - 1 - pos]      # (pos+1)-th largest
        return float(v1 - (v1 - v2) * bias)
    order = np.argsort(values, kind="stable")
    v = values[order]
    cw = np.cumsum(weights[order].astype(np.float64))
    threshold = alpha * cw[-1]
    pos = int(np.searchsorted(cw, threshold, side="right"))
    pos = min(pos, n - 1)
    if pos == 0 or pos == n - 1:
        return float(v[pos])
    v1, v2 = float(v[pos - 1]), float(v[pos])
    if cw[pos + 1] - cw[pos] >= 1.0:
        return (threshold - cw[pos]) / (cw[pos + 1] - cw[pos]) * (v2 - v1) + v1
    return v2


class ObjectiveFunction:
    """Interface (reference: include/LightGBM/objective_function.h:19)."""

    name = "custom"
    num_model_per_iteration = 1
    is_constant_hessian = False
    need_renew = False
    is_ranking = False

    # attributes that only mirror device operands (or derive from
    # already-fingerprinted config/metadata): the fused-block fingerprint
    # skips hashing their N-sized contents
    fp_skip_attrs = frozenset({"_label_host", "_weight_host"})

    def __init__(self, config: Config) -> None:
        self.config = config
        self.label: Optional[jax.Array] = None
        self.weight: Optional[jax.Array] = None
        self.num_data = 0

    def init(self, metadata: Metadata) -> None:
        self.num_data = metadata.num_data
        self.label = metadata.device_label()
        self.weight = metadata.device_weight()
        # host mirrors: _label_np/_weight_np must not round-trip through
        # the device (an N-sized device_get per use).
        # Defensive float32 COPIES: aliasing the user's buffer would let a
        # post-construction mutation change results, and float64 mirrors
        # would see different precision than the f32 device arrays
        self._label_host = None if metadata.label is None \
            else np.array(metadata.label, np.float32)
        self._weight_host = None if metadata.weight is None \
            else np.array(metadata.weight, np.float32)

    # objectives that draw per-iteration randomness take a traced iteration
    # index in get_gradients (see RankXENDCG)
    needs_iter = False
    # True for an objective whose get_gradients names its own device phases
    # (obs.PHASES); every other one runs under ``lgbtpu/objective``
    names_own_phases = False

    def get_gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def gradients(self, score: jax.Array, it) -> Tuple[jax.Array, jax.Array]:
        """``get_gradients`` as the training loops call it (fused block and
        eager jit): the iteration index where the objective takes one, and
        the device phase the time is booked to."""
        args = (score, it) if self.needs_iter else (score,)
        if self.names_own_phases:
            return self.get_gradients(*args)
        with trace_phase("lgbtpu/objective"):
            return self.get_gradients(*args)

    def boost_from_score(self, class_id: int = 0) -> float:
        """Initial raw score (reference: BoostFromScore, used when
        boost_from_average=true, gbdt.cpp:333)."""
        return 0.0

    def convert_output(self, score):
        """Raw score -> prediction space (reference: ConvertOutput)."""
        return score

    def renew_leaf_values(self, leaf_assign: np.ndarray, num_leaves: int,
                          score_before: np.ndarray) -> Optional[np.ndarray]:
        return None

    # host mirrors for metric/renew paths
    def _label_np(self) -> np.ndarray:
        if getattr(self, "_label_host", None) is not None:
            return self._label_host
        return np.asarray(self.label)

    def _weight_np(self) -> Optional[np.ndarray]:
        if getattr(self, "_weight_host", None) is not None:
            return self._weight_host
        return None if self.weight is None else np.asarray(self.weight)


# ---------------------------------------------------------------- regression

class RegressionL2(ObjectiveFunction):
    """L2 loss (reference: regression_objective.hpp RegressionL2loss).
    Supports reg_sqrt: fit sqrt(|label|)·sign(label)."""
    name = "regression"
    is_constant_hessian = True

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        if self.config.reg_sqrt:
            lab = self._label_np()
            trans = (np.sign(lab) * np.sqrt(np.abs(lab))).astype(np.float32)
            self.label = jnp.asarray(trans)
            self._label_host = trans  # keep the host mirror in sync

    def get_gradients(self, score):
        g = score - self.label
        h = jnp.ones_like(score)
        return _weighted(g, h, self.weight)

    def boost_from_score(self, class_id: int = 0) -> float:
        lab, w = self._label_np(), self._weight_np()
        return float(np.average(lab, weights=w))

    def convert_output(self, score):
        if self.config.reg_sqrt:
            return jnp.sign(score) * score * score
        return score


class RegressionL1(RegressionL2):
    """L1 loss with leaf renewal by residual median
    (reference: RegressionL1loss::RenewTreeOutput)."""
    name = "regression_l1"
    need_renew = True

    def get_gradients(self, score):
        diff = score - self.label
        g = jnp.sign(diff)
        h = jnp.ones_like(score)
        return _weighted(g, h, self.weight)

    def boost_from_score(self, class_id: int = 0) -> float:
        return _percentile_weighted(self._label_np(), self._weight_np(), 0.5)

    def renew_leaf_values(self, leaf_assign, num_leaves, score_before):
        lab, w = self._label_np(), self._weight_np()
        resid = lab - score_before
        out = np.zeros(num_leaves)
        for l in range(num_leaves):
            m = leaf_assign == l
            if np.any(m):
                out[l] = _percentile_weighted(resid[m], None if w is None else w[m], 0.5)
        return out


class RegressionHuber(RegressionL2):
    """Huber loss (reference: RegressionHuberLoss), alpha = transition point."""
    name = "huber"

    def get_gradients(self, score):
        a = self.config.alpha
        diff = score - self.label
        g = jnp.where(jnp.abs(diff) <= a, diff, a * jnp.sign(diff))
        h = jnp.ones_like(score)
        return _weighted(g, h, self.weight)


class RegressionFair(RegressionL2):
    """Fair loss (reference: RegressionFairLoss), c = fair_c."""
    name = "fair"
    is_constant_hessian = False

    def get_gradients(self, score):
        c = self.config.fair_c
        diff = score - self.label
        g = c * diff / (jnp.abs(diff) + c)
        h = c * c / ((jnp.abs(diff) + c) ** 2)
        return _weighted(g, h, self.weight)


class RegressionPoisson(RegressionL2):
    """Poisson with log link (reference: RegressionPoissonLoss)."""
    name = "poisson"
    is_constant_hessian = False

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        if np.any(self._label_np() < 0):
            Log.fatal("[poisson]: labels must be non-negative")

    def get_gradients(self, score):
        g = jnp.exp(score) - self.label
        h = jnp.exp(score + self.config.poisson_max_delta_step)
        return _weighted(g, h, self.weight)

    def boost_from_score(self, class_id: int = 0) -> float:
        lab, w = self._label_np(), self._weight_np()
        return float(np.log(max(np.average(lab, weights=w), 1e-20)))

    def convert_output(self, score):
        return jnp.exp(score)


class RegressionQuantile(RegressionL2):
    """Pinball/quantile loss with renewal (reference: RegressionQuantileloss)."""
    name = "quantile"
    need_renew = True

    def get_gradients(self, score):
        a = self.config.alpha
        g = jnp.where(score < self.label, -a, 1.0 - a)
        h = jnp.ones_like(score)
        return _weighted(g, h, self.weight)

    def boost_from_score(self, class_id: int = 0) -> float:
        return _percentile_weighted(self._label_np(), self._weight_np(), self.config.alpha)

    def renew_leaf_values(self, leaf_assign, num_leaves, score_before):
        lab, w = self._label_np(), self._weight_np()
        resid = lab - score_before
        out = np.zeros(num_leaves)
        for l in range(num_leaves):
            m = leaf_assign == l
            if np.any(m):
                out[l] = _percentile_weighted(resid[m], None if w is None else w[m],
                                              self.config.alpha)
        return out


class RegressionMAPE(RegressionL2):
    """MAPE: L1 with 1/|label| weights and weighted-median renewal
    (reference: RegressionMAPELOSS)."""
    name = "mape"
    need_renew = True

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        lab = self._label_np()
        lw = 1.0 / np.maximum(1.0, np.abs(lab))
        w = self._weight_np()
        self._label_weight = lw if w is None else lw * w
        self.weight = None  # folded into label_weight
        self._weight_host = None  # mirror must track self.weight

    def get_gradients(self, score):
        lw = jnp.asarray(self._label_weight, jnp.float32)
        diff = score - self.label
        g = jnp.sign(diff) * lw
        h = lw
        return g, h

    def boost_from_score(self, class_id: int = 0) -> float:
        return _percentile_weighted(self._label_np(), self._label_weight, 0.5)

    def renew_leaf_values(self, leaf_assign, num_leaves, score_before):
        lab = self._label_np()
        resid = lab - score_before
        out = np.zeros(num_leaves)
        for l in range(num_leaves):
            m = leaf_assign == l
            if np.any(m):
                out[l] = _percentile_weighted(resid[m], self._label_weight[m], 0.5)
        return out


class RegressionGamma(RegressionPoisson):
    """Gamma deviance with log link (reference: RegressionGammaLoss)."""
    name = "gamma"

    def get_gradients(self, score):
        g = 1.0 - self.label * jnp.exp(-score)
        h = self.label * jnp.exp(-score)
        return _weighted(g, h, self.weight)


class RegressionTweedie(RegressionPoisson):
    """Tweedie with log link (reference: RegressionTweedieLoss)."""
    name = "tweedie"

    def get_gradients(self, score):
        rho = self.config.tweedie_variance_power
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        g = -self.label * e1 + e2
        h = -self.label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return _weighted(g, h, self.weight)


# -------------------------------------------------------------------- binary

class BinaryLogloss(ObjectiveFunction):
    """Sigmoid binary cross-entropy (reference: binary_objective.hpp),
    with is_unbalance / scale_pos_weight label weighting."""
    name = "binary"

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        lab = self._label_np()
        uniq = np.unique(lab)
        if not np.all(np.isin(uniq, [0, 1])):
            Log.fatal("[binary]: labels must be 0 or 1, got %s", uniq[:5])
        w = self._weight_np()
        cnt_pos = float(np.sum((lab > 0) * (w if w is not None else 1.0)))
        cnt_neg = float(np.sum((lab <= 0) * (w if w is not None else 1.0)))
        self._pavg = cnt_pos / max(cnt_pos + cnt_neg, 1e-10)
        pos_w, neg_w = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                neg_w = cnt_pos / cnt_neg
            else:
                pos_w = cnt_neg / cnt_pos
        pos_w *= self.config.scale_pos_weight
        self._label_sign = jnp.asarray(np.where(lab > 0, 1.0, -1.0), jnp.float32)
        self._label_w = jnp.asarray(np.where(lab > 0, pos_w, neg_w), jnp.float32)

    def get_gradients(self, score):
        sig = self.config.sigmoid
        y = self._label_sign
        response = -y * sig / (1.0 + jnp.exp(y * sig * score))
        absr = jnp.abs(response)
        g = response * self._label_w
        h = absr * (sig - absr) * self._label_w
        return _weighted(g, h, self.weight)

    def boost_from_score(self, class_id: int = 0) -> float:
        p = np.clip(self._pavg, 1e-15, 1 - 1e-15)
        init = float(np.log(p / (1 - p)) / self.config.sigmoid)
        return init

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * score))


# ---------------------------------------------------------------- multiclass

class MulticlassSoftmax(ObjectiveFunction):
    """Softmax, K trees per iteration
    (reference: multiclass_objective.hpp MulticlassSoftmax)."""
    name = "multiclass"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.num_model_per_iteration = self.num_class

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        lab = self._label_np().astype(np.int32)
        if lab.min() < 0 or lab.max() >= self.num_class:
            Log.fatal("[multiclass]: labels must be in [0, num_class)")
        self._onehot = jnp.asarray(np.eye(self.num_class, dtype=np.float32)[lab])
        self._class_p = np.bincount(lab, minlength=self.num_class) / len(lab)

    def get_gradients(self, score):
        p = jax.nn.softmax(score, axis=1)
        g = p - self._onehot
        # hessian upper-bound factor K/(K-1) (reference:
        # multiclass_objective.hpp:31 factor_)
        factor = self.num_class / max(self.num_class - 1, 1)
        h = factor * p * (1.0 - p)
        if self.weight is not None:
            g = g * self.weight[:, None]
            h = h * self.weight[:, None]
        return g, h

    def boost_from_score(self, class_id: int = 0) -> float:
        # reference inits multiclass scores at 0 (no average boost)
        return 0.0

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=1)


class MulticlassOVA(ObjectiveFunction):
    """K one-vs-all binary objectives (reference: MulticlassOVA)."""
    name = "multiclassova"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.num_class = int(config.num_class)
        self.num_model_per_iteration = self.num_class

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        lab = self._label_np().astype(np.int32)
        self._sign = jnp.asarray(np.where(
            np.eye(self.num_class, dtype=np.float32)[lab] > 0, 1.0, -1.0), jnp.float32)

    def get_gradients(self, score):
        sig = self.config.sigmoid
        y = self._sign
        response = -y * sig / (1.0 + jnp.exp(y * sig * score))
        absr = jnp.abs(response)
        g, h = response, absr * (sig - absr)
        if self.weight is not None:
            g = g * self.weight[:, None]
            h = h * self.weight[:, None]
        return g, h

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * score))


# ------------------------------------------------------------- cross entropy

class CrossEntropy(ObjectiveFunction):
    """Cross-entropy with probabilistic labels in [0,1]
    (reference: xentropy_objective.hpp CrossEntropy), identity sigmoid=1 link."""
    name = "cross_entropy"

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        lab = self._label_np()
        if lab.min() < 0 or lab.max() > 1:
            Log.fatal("[cross_entropy]: labels must be in [0, 1]")

    def get_gradients(self, score):
        p = 1.0 / (1.0 + jnp.exp(-score))
        g = p - self.label
        h = p * (1.0 - p)
        return _weighted(g, h, self.weight)

    def boost_from_score(self, class_id: int = 0) -> float:
        lab, w = self._label_np(), self._weight_np()
        p = np.clip(np.average(lab, weights=w), 1e-15, 1 - 1e-15)
        return float(np.log(p / (1 - p)))

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-score))


class CrossEntropyLambda(ObjectiveFunction):
    """Alternative-parameterization cross-entropy
    (reference: CrossEntropyLambda — log1p(exp) link with weights folded into
    the link)."""
    name = "cross_entropy_lambda"

    def get_gradients(self, score):
        w = self.weight if self.weight is not None else 1.0
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - self.label + self.label * jnp.exp(w * hhat)
        enf = jnp.exp(-score)
        g = (1.0 - self.label / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - (1.0 - 1e-12) / z)
        h = w * epf / ((1.0 + epf) ** 2) * (1.0 + w * epf / (1.0 + epf) *
                                            (1.0 - 1.0 / jnp.maximum(c, 1e-12)))
        h = jnp.abs(h) + 1e-6
        return g, h

    def boost_from_score(self, class_id: int = 0) -> float:
        lab, w = self._label_np(), self._weight_np()
        p = np.clip(np.average(lab, weights=w), 1e-15, 1 - 1e-15)
        return float(np.log(np.expm1(p)) if p > 0 else 0.0)

    def convert_output(self, score):
        return jnp.log1p(jnp.exp(score))


# ------------------------------------------------------------------- ranking

def _pad_queries(qb: np.ndarray) -> Tuple[np.ndarray, int]:
    """(Q+1,) boundaries -> (Q, P) row-index matrix padded with -1."""
    sizes = np.diff(qb)
    P = int(sizes.max()) if len(sizes) else 1
    Q = len(sizes)
    idx = np.full((Q, P), -1, dtype=np.int32)
    for q in range(Q):
        idx[q, : sizes[q]] = np.arange(qb[q], qb[q + 1], dtype=np.int32)
    return idx, P


# padded query lengths quantize to this ladder: one compiled (Q, K, P)
# lambda kernel per distinct rung. Padding every query to the GLOBAL max
# (the round-3 design) wasted ~1.9x tensor volume at MSLR-like length
# spreads; the ladder caps waste at ~25% for a handful of compilations.
# The slots of all buckets, concatenated in ladder order, are the layout
# the gradient is computed in: a row's score goes to its slot by one gather
# (``safe_idx``) and its gradient comes back by one (``slot_of_row``).
_BUCKET_LADDER = (8, 16, 24, 32, 48, 64, 96, 128, 160, 192, 224, 256,
                  320, 384, 448, 512, 640, 768, 1024)

# a bucket of padded length p_b ranks its documents by COUNTING (two fused
# (Q, P, P) passes: P compares and P selects a slot) up to this length and
# by sorting above it: counting is quadratic in the query, the sorted path is
# not. The choice is a function of the static p_b alone. On a v5e 262,144
# slots cost the sorted path 15.2 ms at every length and the counted one
# 3.2 ms at 1,280 documents, 8.6 at 8,192, 16.4 at 16,384: level at 15.3K
# (PERF.md section 6, PR 31).
_COUNT_MAX_P = 15360


def _bucket_queries(qb: np.ndarray):
    """(Q+1,) boundaries -> list of (P_b, query_index_array) buckets."""
    sizes = np.diff(qb)
    ladder = np.asarray(_BUCKET_LADDER)
    out = []
    for p_b in _BUCKET_LADDER:
        lo = 0 if p_b == _BUCKET_LADDER[0] else ladder[ladder < p_b].max()
        sel = np.where((sizes > lo) & (sizes <= p_b))[0]
        if len(sel):
            out.append((p_b, sel))
    big = np.where(sizes > _BUCKET_LADDER[-1])[0]
    if len(big):
        # beyond the ladder: one bucket per 256-multiple
        pmax = int(sizes[big].max())
        for p_b in range(_BUCKET_LADDER[-1] + 256, pmax + 256, 256):
            sel = big[(sizes[big] > p_b - 256) & (sizes[big] <= p_b)]
            if len(sel):
                out.append((p_b, sel))
    return out


def _rank_by_counting(s):
    """(Q, P) scores -> (Q, P) int32 rank of every slot among its query's,
    highest score first, ties to the lower slot: the position a stable
    ``argsort(-s)`` gives it, as a count of the slots ahead of it. The
    (Q, P, P) compare fuses into its reduction and is never stored."""
    p = s.shape[1]
    slot = jnp.arange(p, dtype=jnp.int32)
    sk, sj = s[:, :, None], s[:, None, :]                  # k ahead of j?
    ahead = (sk > sj) | ((sk == sj) & (slot[:, None] < slot[None, :]))
    return jnp.sum(ahead, axis=1, dtype=jnp.int32)


def _table_at_rank(table, rank):
    """``table[rank]`` for a (P,) table and (Q, P) ranks below P, as a
    one-hot select fused into its sum like the count above: exact (one
    non-zero term), and no gather of every slot. The chip's own
    ``1 / log2(rank + 2)`` is 600 ulp off the float64-made table."""
    r = jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(rank[:, None, :] == r[None, :, None],
                             table[None, :, None], 0.0), axis=1)


class LambdarankNDCG(ObjectiveFunction):
    """LambdaRank with NDCG lambda gradients (reference: rank_objective.hpp:100
    LambdarankNDCG): per-query pairwise lambdas weighted by |ΔNDCG|,
    truncation_level caps the high-ranked side of each pair, optional
    lambdarank_norm. Vectorized as (query-chunk, trunc, P) tensors instead of
    the reference's per-query double loop, and computed in SLOT order (the
    padded (Q, P) layout of a length bucket): a document's rank is a count,
    the top-``trunc`` rows are picked by it, and nothing is sorted or moved
    by a computed index but the score on its way in and the gradient on its
    way out. Buckets longer than ``_COUNT_MAX_P`` sort instead."""
    name = "lambdarank"
    is_ranking = True
    names_own_phases = True     # lgbtpu/rank_gather, _sort, _pairs, _scatter
    # _gains_np derives from label + label_gain (both fingerprinted); the
    # bucket tables it feeds ride as jit operands
    fp_skip_attrs = ObjectiveFunction.fp_skip_attrs | {"_gains_np"}

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        if metadata.query_boundaries is None:
            Log.fatal("[lambdarank]: query data (group) required")
        cfg = self.config
        label_gain = cfg.label_gain or [float(2 ** i - 1) for i in range(31)]
        lab = self._label_np().astype(np.int32)
        if lab.max() >= len(label_gain):
            Log.fatal("[lambdarank]: label %d exceeds label_gain size", lab.max())
        self._gains_np = np.asarray(label_gain, np.float64)[lab].astype(np.float32)
        qb = metadata.query_boundaries
        sizes = np.diff(qb)
        self.P = int(sizes.max()) if len(sizes) else 1
        self.trunc = min(int(cfg.lambdarank_truncation_level), self.P)
        # queries bucketed by padded length (_BUCKET_LADDER): the all-pairs
        # lambda tensors are (Q_b, K, P_b) per bucket instead of one
        # max-padded (Q, K, P) — at MSLR-like length spreads that is ~1.9x
        # less tensor volume (reference per-query loop:
        # rank_objective.hpp:54 GetGradients / :124 inverse_max_dcgs_)
        buckets = _bucket_queries(qb)
        p_max = max((p_b for p_b, _ in buckets), default=1)
        disc_np = 1.0 / np.log2(np.arange(p_max) + 2.0)
        self.bucket_shapes = []   # python-static (Q_b, P_b, K_b)
        self.bucket_arrays = []   # device tables, passed as jit operands
        slot_of_row = np.zeros(self.num_data, np.int32)
        slots = counted = 0
        for p_b, qsel in buckets:
            q_b = len(qsel)
            idx = np.full((q_b, p_b), -1, dtype=np.int32)
            for row, q in enumerate(qsel):
                idx[row, : sizes[q]] = np.arange(qb[q], qb[q + 1],
                                                 dtype=np.int32)
            valid = idx >= 0
            safe = np.maximum(idx, 0)
            slot_of_row[idx[valid]] = slots + np.flatnonzero(valid)
            slots += q_b * p_b
            counted += q_b * p_b * (p_b <= _COUNT_MAX_P)
            gains = np.where(valid, self._gains_np[safe], 0.0)
            g_sorted = -np.sort(-gains, axis=1)
            max_dcg = (g_sorted * disc_np[None, :p_b]).sum(axis=1)
            inv = np.where(max_dcg > 0, 1.0 / np.maximum(max_dcg, 1e-20),
                           0.0)
            self.bucket_shapes.append((q_b, p_b, min(self.trunc, p_b)))
            self.bucket_arrays.append({
                "safe_idx": jnp.asarray(safe),
                "valid": jnp.asarray(valid),
                "gains": jnp.asarray(gains, jnp.float32),
                "inv_max_dcg": jnp.asarray(inv, jnp.float32),
            })
        # every row owns exactly one slot of the concatenated buckets
        self.slot_of_row = jnp.asarray(slot_of_row)
        self.discount = jnp.asarray(disc_np, jnp.float32)
        self.sigmoid_ = float(cfg.sigmoid)
        self.norm = bool(cfg.lambdarank_norm)
        telemetry.gauge("rank/buckets", len(buckets))
        telemetry.gauge("rank/slots", slots)
        telemetry.gauge("rank/slots_counted_share",
                        counted / slots if slots else 1.0)

    def _bucket_lambdas(self, s, arrs, K: int):
        """Per-bucket (Q_b, P_b) scores (-inf on padding slots) -> grad/hess,
        all in slot order, via padded pairwise lambdas."""
        if s.shape[1] <= _COUNT_MAX_P:
            return self._counted_lambdas(s, arrs, K)
        return self._sorted_lambdas(s, arrs, K)

    def _counted_lambdas(self, s, arrs, K: int):
        """Slot order throughout: each slot's rank by counting, the top-K
        rows by a one-hot select on the rank, their row sums back to their
        slots by the same one-hot."""
        valid, gains = arrs["valid"], arrs["gains"]
        with trace_phase("lgbtpu/rank_sort"):
            rank = _rank_by_counting(s)                        # (Q, P)
            disc = _table_at_rank(self.discount[:s.shape[1]], rank)
        with trace_phase("lgbtpu/rank_pairs"):
            # exactly one slot of a query holds rank r, so each sum has one
            # non-zero term and is exact (an invalid slot's score is -inf)
            top = rank[:, None, :] == jnp.arange(K)[None, :, None]
            si = jnp.sum(jnp.where(top, s[:, None, :], 0.0), axis=2)
            gi = jnp.sum(jnp.where(top, gains[:, None, :], 0.0), axis=2)
            vi = jnp.any(top & valid[:, None, :], axis=2)
            # the rows' values stay (Q, K) buffers: left free, XLA:TPU turns
            # each reduce-then-broadcast into a (Q, K, P) reduce-window and
            # keeps that tensor in HBM (126 MB at the cell's 96 rung)
            si, gi, vi = jax.lax.optimization_barrier((si, gi, vi))
            lam_i, hess_i, grad, hess = self._pair_lambdas(
                si, gi, vi, s, gains, valid, rank, disc, arrs["inv_max_dcg"])
            lam_i, hess_i = jax.lax.optimization_barrier((lam_i, hess_i))
            grad = grad + jnp.sum(
                jnp.where(top, lam_i[:, :, None], 0.0), axis=1)
            hess = hess + jnp.sum(
                jnp.where(top, hess_i[:, :, None], 0.0), axis=1)
            return self._normalized(grad, hess)

    def _sorted_lambdas(self, s, arrs, K: int):
        """Rank order between two sorts: for buckets too long to count."""
        p_b = s.shape[1]
        with trace_phase("lgbtpu/rank_sort"):
            order = jnp.argsort(-s, axis=1)                    # rank -> slot
            s_sorted = jnp.take_along_axis(s, order, axis=1)
            g_sorted = jnp.take_along_axis(arrs["gains"], order, axis=1)
            valid_sorted = jnp.take_along_axis(arrs["valid"], order, axis=1)
        with trace_phase("lgbtpu/rank_pairs"):
            lam_i, hess_i, grad, hess = self._pair_lambdas(
                s_sorted[:, :K], g_sorted[:, :K], valid_sorted[:, :K],
                s_sorted, g_sorted, valid_sorted,
                jnp.arange(p_b, dtype=jnp.int32)[None, :],
                self.discount[None, :p_b], arrs["inv_max_dcg"])
            grad_sorted, hess_sorted = self._normalized(
                grad.at[:, :K].add(lam_i), hess.at[:, :K].add(hess_i))
        with trace_phase("lgbtpu/rank_sort"):
            # unsort ranks back to slots
            inv = jnp.argsort(order, axis=1)
            grad_q = jnp.take_along_axis(grad_sorted, inv, axis=1)
            hess_q = jnp.take_along_axis(hess_sorted, inv, axis=1)
        return grad_q, hess_q

    def _pair_lambdas(self, si, gi, vi, s, g, valid, rank, disc,
                      inv_max_dcg):
        """The (Q, K, P) pairwise tensors: i the document of rank r < K
        (``si``, ``gi``, ``vi``: (Q, K) score, gain, validity) x j every
        document, in whatever order ``s``, ``g``, ``valid``, ``rank``,
        ``disc`` (its rank's discount) list them. Returns the rows' sums
        ``lam_i``, ``hess_i`` (Q, K) and the columns' ``lam_j``, ``hess_j``
        (Q, P)."""
        K = si.shape[1]
        di = self.discount[:K]
        delta_s = si[:, :, None] - s[:, None, :]               # (Q, K, P)
        worse = (gi[:, :, None] > g[:, None, :])
        better = (gi[:, :, None] < g[:, None, :])
        # pairs: i in top-K ranks x j in all ranks; j > i counted once
        once = rank[:, None, :] > jnp.arange(K)[None, :, None]
        pair_mask = (worse | better) & vi[:, :, None] & valid[:, None, :] \
            & once
        # |delta NDCG| of swapping ranks i<->j
        dd = jnp.abs(di[None, :, None] - disc[:, None, :])
        dgain = jnp.abs(gi[:, :, None] - g[:, None, :])
        delta_ndcg = dd * dgain * inv_max_dcg[:, None, None]
        # orient each pair so "hi" is the better-labelled doc
        sgn = jnp.where(worse, 1.0, -1.0)
        d = sgn * delta_s                                      # s_hi - s_lo
        sig = self.sigmoid_
        p = 1.0 / (1.0 + jnp.exp(sig * d))                     # misorder prob
        lam = jnp.where(pair_mask, -sig * p * delta_ndcg, 0.0)
        hess = jnp.where(pair_mask, sig * sig * p * (1.0 - p) * delta_ndcg,
                         0.0)
        lam_i = jnp.sum(lam * sgn, axis=2)                     # (Q, K)
        hess_i = jnp.sum(hess, axis=2)
        return lam_i, hess_i, jnp.sum(-lam * sgn, axis=1), \
            jnp.sum(hess, axis=1)

    def _normalized(self, grad, hess):
        if not self.norm:
            return grad, hess
        norm = jnp.sum(jnp.abs(grad), axis=1, keepdims=True)
        scale = jnp.where(norm > 0,
                          jnp.log2(1 + norm) / jnp.maximum(norm, 1e-20), 1.0)
        return grad * scale, hess * scale

    def get_gradients(self, score):
        """(N,) score -> (N,) grad/hess. Every slot of a bucket reads its
        row's score (one gather a bucket; a padding slot reads row 0 and is
        masked); one padded pairwise-lambda kernel per length bucket leaves
        its (Q_b, P_b) slots in place; every row reads its own slot of the
        concatenated buckets back (a permutation: one gather, no add).
        Both ways move PAIRS of floats: XLA's gather of (n, 2) rows runs at
        5 ns an index on a v5e, its gather of scalars at 7-15 (PERF.md
        section 6, PR 31)."""
        with trace_phase("lgbtpu/rank_gather"):
            score2 = jnp.stack([score, score], axis=1)
        parts = []
        for (_, _, k_b), arrs in zip(self.bucket_shapes, self.bucket_arrays):
            with trace_phase("lgbtpu/rank_gather"):
                s = jnp.where(arrs["valid"], score2[arrs["safe_idx"]][..., 0],
                              -jnp.inf)                        # (Q, P)
            grad_q, hess_q = self._bucket_lambdas(s, arrs, k_b)
            with trace_phase("lgbtpu/rank_scatter"):
                parts.append(
                    jnp.stack([grad_q, hess_q], axis=-1).reshape(-1, 2))
        with trace_phase("lgbtpu/rank_scatter"):
            gh = jnp.concatenate(parts)[self.slot_of_row]
            return _weighted(gh[:, 0], jnp.maximum(gh[:, 1], 1e-20),
                             self.weight)


class RankXENDCG(ObjectiveFunction):
    """XE-NDCG listwise surrogate (reference: rank_objective.hpp RankXENDCG,
    per Bruch et al.): cross-entropy between a sampled Gumbel-perturbed label
    distribution and the score softmax, per query."""
    name = "rank_xendcg"
    is_ranking = True
    needs_iter = True
    # _doc_idx_np mirrors the doc_idx jit operand (derives from the
    # fingerprinted query boundaries)
    fp_skip_attrs = ObjectiveFunction.fp_skip_attrs | {"_doc_idx_np"}

    def init(self, metadata: Metadata) -> None:
        super().init(metadata)
        if metadata.query_boundaries is None:
            Log.fatal("[rank_xendcg]: query data (group) required")
        lab = self._label_np()
        self._doc_idx_np, self.P = _pad_queries(metadata.query_boundaries)
        self.doc_idx = jnp.asarray(self._doc_idx_np)
        self.doc_valid = self.doc_idx >= 0
        self.safe_idx = jnp.maximum(self.doc_idx, 0)
        phi = (2.0 ** lab - 1.0)
        self.q_phi = jnp.where(self.doc_valid,
                               jnp.asarray(phi, jnp.float32)[self.safe_idx], 0.0)
        self.key = jax.random.PRNGKey(int(self.config.objective_seed or 5))

    def get_gradients(self, score, it=0):
        # ``it`` is a traced iteration index threaded by the boosting loop so
        # each iteration draws a fresh Gumbel perturbation even under jit
        # (a host-side counter would be baked in at trace time)
        key = jax.random.fold_in(self.key, jnp.asarray(it, jnp.int32))
        s = jnp.where(self.doc_valid, score[self.safe_idx], -jnp.inf)
        # sampled relevance distribution: softmax(phi + gumbel)
        gumbel = jax.random.gumbel(key, s.shape)
        phi_pert = jnp.where(self.doc_valid, self.q_phi + gumbel, -jnp.inf)
        target = jax.nn.softmax(phi_pert, axis=1)
        rho = jax.nn.softmax(s, axis=1)
        grad_q = rho - target
        hess_q = rho * (1.0 - rho)
        n = score.shape[0]
        flat_idx = self.safe_idx.reshape(-1)
        vmask = self.doc_valid.reshape(-1)
        grad = jnp.zeros((n,), jnp.float32).at[flat_idx].add(
            jnp.where(vmask, grad_q.reshape(-1), 0.0))
        hess = jnp.zeros((n,), jnp.float32).at[flat_idx].add(
            jnp.where(vmask, hess_q.reshape(-1), 0.0))
        hess = jnp.maximum(hess, 1e-20)
        return grad, hess


class NoneObjective(ObjectiveFunction):
    """Custom objective placeholder: gradients supplied by the caller
    (reference: USE_CUSTOM_OBJECTIVE path, TrainOneIter(grad, hess))."""
    name = "none"

    def get_gradients(self, score):
        Log.fatal("custom objective: gradients must be passed to update()")


_REGISTRY = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
    "rank_xendcg": RankXENDCG,
    "none": NoneObjective,
}


def create_objective(config: Config) -> ObjectiveFunction:
    """Factory (reference: src/objective/objective_function.cpp:15)."""
    name = OBJECTIVE_ALIASES.get(config.objective, config.objective)
    if name not in _REGISTRY:
        Log.fatal("Unknown objective: %s", config.objective)
    return _REGISTRY[name](config)
