"""Plain reference for a tweedie job trained on a bag of rows and a subset
of columns: float64 numpy; imports nothing from the program.

(a) ``gradients``: the tweedie loss of a log-link score s at variance power
    rho, per row: g = -y e^{(1-rho)s} + e^{(2-rho)s},
    h = -y (1-rho) e^{(1-rho)s} + (2-rho) e^{(2-rho)s}. With
    ``boost_from_average`` false the first tree sees s = 0 on every row.
(b) ``bag`` and ``feature_subset``: iteration i's rows in the bag and tree
    i's columns, recomputed from the seeds by the rule the program documents
    (``fused.make_sampler`` and ``make_feature_mask_fn``): a uniform float32
    of ``jax.random`` a row (a used feature) from the key
    ``PRNGKey(seed & 0x7FFFFFFF)`` folded with ``9000 + i // bagging_freq``
    (``555 + i``); a row is in the bag where its draw is under
    ``bagging_fraction``, and the ``ceil(feature_fraction x F)`` features
    of the smallest draws are the tree's. THIS IS THE ONE THING THE
    REFERENCE SHARES WITH THE PROGRAM: the random stream, not its use.
(c) ``best_split``: the best split of the rows of the bag over the tree's
    columns, from float64 histograms of (rows, g, h) by bin. A numerical
    column: every ``bin <= t``, the NaN bin's mass tried on both sides
    (``reference/missing.py``'s rule: left only where the node holds a NaN
    row, and only by a larger gain). A categorical column:
    ``reference/categorical_split.py``'s rule, with each bin's own hessian
    sum where that file has the constant hessian of a binary job's first
    tree. An out-of-bag row counts in nothing: not in a bin's rows (so not
    in ``min_data_in_leaf`` or ``min_data_per_group``), not in its sums.
(d) ``node_histograms``: every internal node's histograms of a tree, from
    its leaves' rows up, for the splits past the root; ``program_split``
    reads a node of the model text as the search names a split, and
    ``judge`` holds it to the reference's best there.
(e) ``rmse`` and ``deviance`` of the predictions e^s.

The bins are the program's data layer's, as ``binary_root`` takes them:
each used feature's upper bounds and NaN bin, or its bin -> category table.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import model_text
from .categorical_split import bins_of as categorical_bins
from .missing import feature_bins
from .model_text import THREADS

NUMERICAL, ONE_VS_REST, MANY_VS_MANY = "numerical", "one_vs_rest", "many_vs_many"
RANK = {NUMERICAL: 0, ONE_VS_REST: 1, MANY_VS_MANY: 2}
BAG_STREAM, FEATURE_STREAM = 9000, 555


def gradients(y, score, rho):
    y = np.asarray(y, np.float64)
    s = np.asarray(score, np.float64)
    e1, e2 = np.exp((1.0 - rho) * s), np.exp((2.0 - rho) * s)
    return -y * e1 + e2, -y * (1.0 - rho) * e1 + (2.0 - rho) * e2


def _uniform(seed, stream, n):
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), stream)
    return np.asarray(jax.random.uniform(key, (n,)))


def bag(n, params, iteration):
    """(n,) bool: the rows of iteration ``iteration``'s bag."""
    freq = max(1, int(params.get("bagging_freq", 0)))
    u = _uniform(params.get("bagging_seed", 3), BAG_STREAM + iteration // freq, n)
    return u < np.float32(params.get("bagging_fraction", 1.0))


def feature_subset(num_features, params, iteration):
    """(F,) bool over the used features: tree ``iteration``'s columns."""
    ff = float(params.get("feature_fraction", 1.0))
    if ff >= 1.0:
        return np.ones(num_features, bool)
    k = max(1, int(np.ceil(ff * num_features)))
    u = _uniform(params.get("feature_fraction_seed", 2), FEATURE_STREAM + iteration,
                 num_features)
    rank = np.empty(num_features, np.int64)
    rank[np.argsort(u, kind="stable")] = np.arange(num_features)
    return rank < k


def columns_of_program(binned):
    """Per used feature: its source column, kind, bins, and its bounds and
    NaN bin or its bin -> category table, from the program's data layer."""
    out = []
    for col, m in zip(binned.used_feature_indices, binned.bin_mappers):
        c = {"column": int(col), "num_bins": int(m.num_bins)}
        if int(m.bin_type) == 1:
            c.update(kind="categorical", categories=np.asarray(m.categories, np.int64))
        else:
            c.update(kind=NUMERICAL, bounds=np.asarray(m.upper_bounds, np.float64),
                     nan_bin=m.num_bins - 1 if m.missing_type == 2 else None)
        out.append(c)
    return out


def bin_rows(X, rows, cols):
    """(len(rows), len(cols)) bins of ``rows`` of ``X`` in each of ``cols``."""
    out = np.empty((len(rows), len(cols)), np.uint16)

    def one(j):
        c = cols[j]
        x = np.ascontiguousarray(X[rows, c["column"]])
        if c["kind"] == NUMERICAL:
            out[:, j] = feature_bins(x, c["bounds"], c["num_bins"], c["nan_bin"])
        else:
            out[:, j] = categorical_bins(x[:, None], [{"categories": c["categories"]}])[0]
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(one, range(len(cols))))
    return out


class Search:
    """One node's totals and the rule's parameters."""

    def __init__(self, n, G, H, params):
        self.n, self.G, self.H = float(n), float(G), float(H)
        get = params.get
        self.l2 = float(get("lambda_l2", 0.0))
        self.cat_l2 = float(get("cat_l2", 10.0))
        self.cat_smooth = float(get("cat_smooth", 10.0))
        self.max_cat_threshold = int(get("max_cat_threshold", 32))
        self.max_cat_to_onehot = int(get("max_cat_to_onehot", 4))
        self.min_data_per_group = float(get("min_data_per_group", 100))
        self.min_data = float(get("min_data_in_leaf", 20))
        self.min_hess = float(get("min_sum_hessian_in_leaf", 1e-3))
        for k in ("lambda_l1", "max_delta_step", "path_smooth", "min_gain_to_split"):
            if float(get(k, 0.0)) != 0.0:
                raise ValueError("the plain reference does not know %s" % k)

    def gain(self, cl, gl, hl, extra=0.0):
        cl, gl, hl = (np.asarray(a, np.float64) for a in (cl, gl, hl))
        cr, gr, hr = self.n - cl, self.G - gl, self.H - hl
        ok = ((cl >= self.min_data) & (cr >= self.min_data)
              & (hl >= self.min_hess) & (hr >= self.min_hess))
        l2 = self.l2 + extra
        with np.errstate(divide="ignore", invalid="ignore"):
            g = gl * gl / (hl + l2) + gr * gr / (hr + l2) - self.G * self.G / (self.H + self.l2)
        return np.where(ok, g, -np.inf)

    def scale(self, gain):
        """The size a gain distance is a share of: |gain| + G^2 / (H + l2)."""
        return abs(gain) + self.G * self.G / (self.H + self.l2)

    def thresholds(self, col, cnt, g, h):
        """A numerical column's every ``bin <= t``: -> (gain, default_left,
        left rows), an array each over t; the NaN bin's mass goes left only
        where there is some and it gains more there."""
        nb, nan = col["num_bins"], col["nan_bin"]
        mc = mg = mh = 0.0
        if nan is not None:                            # the last bin, left out of the scan
            mc, mg, mh = cnt[nan], g[nan], h[nan]
        cl, gl, hl = np.cumsum(cnt[:nb - 1]), np.cumsum(g[:nb - 1]), np.cumsum(h[:nb - 1])
        dr = self.gain(cl, gl, hl)
        dl = self.gain(cl + mc, gl + mg, hl + mh) if mc > 0 else np.full_like(dr, -np.inf)
        left = dl > dr
        return np.maximum(dr, dl), left, cl + np.where(left, mc, 0.0)

    def candidates(self, col, cnt, g, h):
        """-> [(gain, kind, left bins as a sorted tuple, default_left, left rows)]."""
        nb = col["num_bins"]
        out = []
        if col["kind"] == NUMERICAL:
            best, left, left_rows = self.thresholds(col, cnt, g, h)
            return [(float(best[t]), NUMERICAL, t, bool(left[t]), int(left_rows[t]))
                    for t in range(nb - 1) if np.isfinite(best[t])]
        cats = nb - 1                                   # the last bin is no candidate
        if cats <= self.max_cat_to_onehot:
            gain = self.gain(cnt[:cats], g[:cats], h[:cats], self.cat_l2)
            return [(float(gain[b]), ONE_VS_REST, (b,), False, int(cnt[b]))
                    for b in range(cats) if cnt[b] > 0 and np.isfinite(gain[b])]
        used = np.flatnonzero(cnt[:cats] >= self.min_data_per_group)
        key = g[used] / (h[used] + self.cat_smooth)
        most = min(self.max_cat_threshold, len(used) - 1)
        for order in (used[np.argsort(key, kind="stable")],
                      used[np.argsort(-key, kind="stable")]):
            cl, gl, hl = np.cumsum(cnt[order]), np.cumsum(g[order]), np.cumsum(h[order])
            gain = self.gain(cl[:most], gl[:most], hl[:most], self.cat_l2)
            for k in range(max(most, 0)):
                if np.isfinite(gain[k]):
                    out.append((float(gain[k]), MANY_VS_MANY,
                                tuple(sorted(int(b) for b in order[:k + 1])), False,
                                int(cl[k])))
        return out

    def gain_of(self, col, cnt, g, h, left, default_left):
        """The reference's gain and left rows for a split somebody else
        chose: ``left`` a threshold bin or a tuple of bins."""
        if col["kind"] == NUMERICAL:
            m = np.arange(col["num_bins"]) <= left
            if col["nan_bin"] is not None:
                m[col["nan_bin"]] = bool(default_left)
            extra = 0.0
        else:
            m = np.zeros(col["num_bins"], bool)
            m[list(left)] = True
            extra = self.cat_l2
        return float(self.gain(cnt[m].sum(), g[m].sum(), h[m].sum(), extra)), int(cnt[m].sum())


def histograms(fb, g, h, cols):
    """Per used feature: (rows, g, h) by bin, float64."""
    def one(j):
        b, nb = fb[:, j], cols[j]["num_bins"]
        return (np.bincount(b, minlength=nb).astype(np.float64),
                np.bincount(b, weights=g, minlength=nb),
                np.bincount(b, weights=h, minlength=nb))
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(one, range(len(cols))))


def best_split(hists, n, G, H, cols, subset, params):
    """-> (search, best): ``best`` None where no split is allowed, else a
    dict ``feature``, ``kind``, ``left``, ``default_left``, ``gain``,
    ``left_rows``. Equal gains go to the first candidate in the order
    numerical < one against the rest < many against many, then feature,
    then place in the scan (the program's argmax over its stacked planes)."""
    s = Search(n, G, H, params)
    best = None
    for f, col in enumerate(cols):
        if not subset[f]:
            continue
        if col["kind"] == NUMERICAL:             # the first of the best thresholds
            gains, dls, rows = s.thresholds(col, *hists[f])
            t = int(np.argmax(gains))
            found = [(float(gains[t]), NUMERICAL, t, bool(dls[t]), int(rows[t]))]
        else:
            found = s.candidates(col, *hists[f])
        for i, (gain, kind, left, dl, left_rows) in enumerate(found):
            at = (-gain, RANK[kind], f, i)
            if gain > 0.0 and (best is None or at < best[0]):
                best = (at, {"feature": f, "kind": kind, "left": left, "default_left": dl,
                             "gain": gain, "left_rows": left_rows})
    return s, (best[1] if best else None)


def subset_histograms(fb, g, h, cols, subset):
    """``histograms`` of the tree's columns alone, in a list over every used
    feature (None where a column is not the tree's); ``fb`` holds the bins
    of the subset's columns, in order."""
    feats = np.flatnonzero(subset)
    out = [None] * len(cols)
    for f, hist in zip(feats, histograms(fb, g, h, [cols[j] for j in feats])):
        out[f] = hist
    return out


def program_split(t, k, cols):
    """Node ``k`` of a parsed tree (``reference/model_text.py``) as the
    search names a split: the used feature, its threshold bin or the sorted
    tuple of its left bins, ``default_left``, the recorded gain, and the
    recorded rows of the node and of its left child."""
    col = int(t["split_feature"][k])
    j = next(i for i, c in enumerate(cols) if c["column"] == col)

    def rows_of(child):
        return int(t["internal_count"][child] if child >= 0 else t["leaf_count"][~child])
    if t["is_categorical"][k]:
        cats = cols[j]["categories"]
        left = tuple(sorted(int(np.flatnonzero(cats == v)[0]) for v in t["cat_sets"][k]))
    else:
        left = int(np.searchsorted(cols[j]["bounds"][:-1], float(t["threshold"][k]),
                                   side="left"))
    return dict(feature=j, left=left, default_left=bool(t["default_left"][k]),
                gain=float(t["split_gain"][k]), rows=rows_of(k),
                left_rows=rows_of(int(t["left_child"][k])))


def node_histograms(t, leaf, fb, g, h, cols, subset):
    """Every internal node's histograms of a tree, float64: each leaf's
    (rows, g, h) by bin from its rows (``leaf``: each row's leaf in a walk of
    the tree; ``fb``, g, h as ``subset_histograms`` takes them), each node's
    the sum of its children's (a child is split after its parent, so its
    index is larger). -> node k -> (histograms as ``subset_histograms``
    lists them, rows, G, H)."""
    feats = np.flatnonzero(subset)
    widths = [cols[j]["num_bins"] for j in feats]
    at = np.r_[0, np.cumsum(widths)]
    L = t["num_leaves"]
    leaves = np.zeros((3, L, at[-1]))
    for j, nb in enumerate(widths):
        key = leaf * nb + fb[:, j]
        for q, w in enumerate((None, g, h)):
            leaves[q, :, at[j]:at[j + 1]] = np.bincount(
                key, weights=w, minlength=L * nb).reshape(L, nb)
    nodes = np.zeros((3, L - 1, at[-1]))

    def side(child):
        return nodes[:, child] if child >= 0 else leaves[:, ~child]
    for k in range(L - 2, -1, -1):
        nodes[:, k] = side(int(t["left_child"][k])) + side(int(t["right_child"][k]))

    def of(k):
        out = [None] * len(cols)
        for j, f in enumerate(feats):
            out[f] = tuple(nodes[q, k, at[j]:at[j + 1]] for q in range(3))
        return out, nodes[0, k, :at[1]].sum(), nodes[1, k, :at[1]].sum(), nodes[2, k, :at[1]].sum()
    return of


def judge(prog, s, hists, best, subset, rtol, cols):
    """-> (ok, distance, words): the program's split against the reference's
    ``best`` at one node whose search is ``s``. Feature, bin or left set and
    default_left exact, or a tie: the reference's own gain for the
    program's split within ``rtol`` of its best (the gap); the program's
    recorded gain within ``rtol`` of the reference's for that split (the
    gain distance); the node's and the left child's rows exact. The
    distance returned is the larger of the two, inf where the split is not
    allowed. A gain is a difference of terms G^2 / (H + l2), each of which
    float32 sums leave about as far off as its own size times their
    rounding, and a deep node's gain is a small difference of large terms;
    so both are shares of ``s.scale``, the gain plus the node's own term,
    and not of the gain alone."""
    f = prog["feature"]
    if not subset[f]:
        return False, float("inf"), "program feature %d is not among the tree's columns" % f
    gain, left_rows = s.gain_of(cols[f], *hists[f], prog["left"], prog["default_left"])
    if not np.isfinite(gain):
        return False, float("inf"), ("program feature %d left %s: a split the rules do not "
                                     "allow (min_data_in_leaf, min_sum_hessian_in_leaf)"
                                     % (f, prog["left"]))
    # where no split gains, the best is to stay a leaf, at gain 0
    best = best or {"feature": None, "left": None, "default_left": None, "gain": 0.0}
    tie = ""
    gap = 0.0
    if (f, prog["left"], prog["default_left"]) != (best["feature"], best["left"],
                                                   best["default_left"]):
        gap = abs(gain - best["gain"]) / s.scale(best["gain"])
        tie = " tie: the reference's best is feature %s left %s default_left %s at %.9g (gap %.3g)" % (
            best["feature"], best["left"], best["default_left"], best["gain"], gap)
    dist = abs(gain - prog["gain"]) / s.scale(gain)
    ok = max(dist, gap) <= rtol and prog["left_rows"] == left_rows and prog["rows"] == int(s.n)
    return ok, max(dist, gap), (
        "%d rows (program %d), feature %d (%s) left %s default_left %s, gain %.6g against %.6g "
        "(off %.3g), left rows %d against %d%s" % (
            int(s.n), prog["rows"], f, cols[f]["kind"], prog["left"], prog["default_left"],
            prog["gain"], gain, dist, prog["left_rows"], left_rows, tie))


def tree_inputs(c, i):
    """What the search of tree ``i`` sees, from a check's context ``c``:
    iteration i's bag (``inbag``), tree i's columns (``subset``), the bag's
    bins in those columns (``fb``), and its g, h at the scores that the
    trees before i give the bag's rows in a walk of the model text. Kept in
    ``c``, which every check of one run shares."""
    memo = c.setdefault("tweedie_inputs", {})
    if i not in memo:
        params = c["params"]
        cols = columns_of_program(c["binned"])
        inbag = np.flatnonzero(bag(c["rows"], params, i))
        subset = feature_subset(len(cols), params, i)
        score = model_text.raw_score(c["header"], c["trees"][:i], c["X"][inbag]) if i else 0.0
        g, h = gradients(c["label"][inbag], score, float(params["tweedie_variance_power"]))
        fb = bin_rows(c["X"], inbag, [cols[j] for j in np.flatnonzero(subset)])
        memo[i] = dict(cols=cols, inbag=inbag, subset=subset, g=g, h=h, fb=fb)
    return memo[i]


def rmse(y, raw):
    return float(np.sqrt(np.mean((np.exp(raw) - y) ** 2)))


def deviance(y, raw, rho):
    """Mean tweedie deviance of the predictions e^raw."""
    y = np.asarray(y, np.float64)
    mu = np.exp(raw)
    a = np.where(y > 0, np.power(np.maximum(y, 1e-300), 2.0 - rho), 0.0) / ((1.0 - rho) * (2.0 - rho))
    b = y * np.power(mu, 1.0 - rho) / (1.0 - rho)
    c = np.power(mu, 2.0 - rho) / (2.0 - rho)
    return float(np.mean(2.0 * (a - b + c)))
