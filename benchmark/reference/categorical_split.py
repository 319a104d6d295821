"""Plain reference for the split search of a binary log-loss job on a table
that holds categorical columns: the best split of ONE node from float64
histograms of that node's own rows over every column. numpy only; imports
nothing from the program, and is written from the rule, not from its code.

The rule (upstream's docs/Advanced-Topics.rst "Categorical Feature Support"
and the parameters ``max_cat_to_onehot``, ``max_cat_threshold``, ``cat_l2``,
``cat_smooth``, ``min_data_per_group`` of docs/Parameters.rst, as the
configuration's ``assumed`` states it):

- a numerical column: every ``bin <= t`` split, as ``binary_root`` scans it;
- a categorical column of at most ``max_cat_to_onehot`` categories: each
  category that holds a row, alone against the rest;
- any other categorical column: the bins that hold at least
  ``min_data_per_group`` rows of the node, sorted by ``g / (h + cat_smooth)``
  (a stable sort, ties by bin); the left side is a prefix of that order of at
  most ``max_cat_threshold`` bins, taken from either end, and never all of
  them. Every bin outside the prefix goes right: the unused ones, and the
  shared LAST bin (categories beyond the bins the data layer gave, unseen,
  negative and NaN values), which is no candidate at all;
- a categorical split's gain adds ``cat_l2`` to ``lambda_l2`` in both
  children (the parent's term does not); both leaf minimums hold for every
  kind; the best gain over the columns wins, and it must be positive.

Gradients are those of the first tree: every row scores the log-odds of the
label's mean p, so g = p - y and h = p (1 - p), the same for every row.

The data layer's output is taken from the program, as ``binary_root`` takes
the bounds: a numerical column's bin upper bounds and a categorical column's
bin -> category table (``columns`` below). This reference checks the
learner's search, not the bin finder."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .model_text import THREADS, floor32

NUMERICAL, ONE_VS_REST, MANY_VS_MANY = "numerical", "one_vs_rest", "many_vs_many"


def bins_of(X, columns):
    """The bin of every row in every column, one uint8/uint16 array a column.
    ``columns[f]`` is ``{"bounds": upper bounds}`` or ``{"categories": bin ->
    category value}``; a categorical column has one more bin than categories,
    the last, for everything else."""
    def one(f):
        col, x = columns[f], np.ascontiguousarray(X[:, f])
        if "bounds" in col:
            ub = floor32(col["bounds"][:-1]) if X.dtype == np.float32 \
                else np.asarray(col["bounds"][:-1], np.float64)
            return np.searchsorted(ub, x, side="left").astype(np.uint16)
        cats = np.asarray(col["categories"], np.int64)
        order = np.argsort(cats, kind="stable")
        x = x.astype(np.float64)
        whole = np.isfinite(x) & (x >= 0) & (x < 2.0 ** 31)
        value = np.where(whole, np.trunc(np.where(whole, x, 0.0)), -1).astype(np.int64)
        at = np.minimum(np.searchsorted(cats[order], value), len(cats) - 1)
        hit = cats[order][at] == value
        return np.where(hit, order[at], len(cats)).astype(np.uint16)
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(one, range(len(columns))))


def num_bins(col):
    return len(col["bounds"]) if "bounds" in col else len(col["categories"]) + 1


def histograms(bins, rows, y, columns):
    """-> per column (count, sum of y) of the node's rows by bin, float64.
    ``rows`` is an index array, or None for every row."""
    ys = y if rows is None else y[rows]

    def one(f):
        b = bins[f] if rows is None else bins[f][rows]
        nb = num_bins(columns[f])
        return (np.bincount(b, minlength=nb).astype(np.float64),
                np.bincount(b, weights=ys, minlength=nb))
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(one, range(len(columns))))


class Search:
    """One node's totals and the rule's parameters; ``gain(cl, gl, extra)`` is
    the gain of sending ``cl`` rows with gradient sum ``gl`` left."""

    def __init__(self, n, sum_y, p, params):
        self.n, self.p, self.hess = float(n), p, p * (1.0 - p)
        self.G, self.H = p * n - sum_y, p * (1.0 - p) * n
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

    def gain(self, cl, gl, extra):
        cl, gl = np.asarray(cl, np.float64), np.asarray(gl, np.float64)
        hl = cl * self.hess
        cr, gr, hr = self.n - cl, self.G - gl, self.H - hl
        ok = ((cl >= self.min_data) & (cr >= self.min_data)
              & (hl >= self.min_hess) & (hr >= self.min_hess))
        l2 = self.l2 + extra
        with np.errstate(divide="ignore", invalid="ignore"):
            g = gl * gl / (hl + l2) + gr * gr / (hr + l2) - self.G * self.G / (self.H + self.l2)
        return np.where(ok, g, -np.inf)

    def kind_of(self, col):
        if "bounds" in col:
            return NUMERICAL
        return ONE_VS_REST if len(col["categories"]) <= self.max_cat_to_onehot else MANY_VS_MANY

    def column(self, col, cnt, sy):
        """-> candidates of one column as (gain, kind, left bins, left rows)."""
        g = self.p * cnt - sy                      # sum of (p - y) by bin
        if "bounds" in col:
            cl, gl = np.cumsum(cnt)[:-1], np.cumsum(g)[:-1]
            gain = self.gain(cl, gl, 0.0)
            return [(float(gain[t]), NUMERICAL, t, int(cl[t])) for t in range(len(gain))
                    if np.isfinite(gain[t])]
        cats = len(col["categories"])              # the last bin is no candidate
        out = []
        if cats <= self.max_cat_to_onehot:
            gain = self.gain(cnt[:cats], g[:cats], self.cat_l2)
            for b in range(cats):
                if cnt[b] > 0 and np.isfinite(gain[b]):
                    out.append((float(gain[b]), ONE_VS_REST, (b,), int(cnt[b])))
            return out
        used = np.flatnonzero(cnt[:cats] >= self.min_data_per_group)
        key = g[used] / (cnt[used] * self.hess + self.cat_smooth)
        most = min(self.max_cat_threshold, len(used) - 1)
        for order in (used[np.argsort(key, kind="stable")],
                      used[np.argsort(-key, kind="stable")]):
            cl, gl = np.cumsum(cnt[order]), np.cumsum(g[order])
            gain = self.gain(cl[:most], gl[:most], self.cat_l2)
            for k in range(max(most, 0)):
                if np.isfinite(gain[k]):
                    out.append((float(gain[k]), MANY_VS_MANY,
                                tuple(sorted(int(b) for b in order[:k + 1])), int(cl[k])))
        return out

    def gain_of(self, col, cnt, sy, left):
        """The reference's own gain and left rows for a split somebody else
        chose: ``left`` is a threshold bin or a tuple of bins."""
        g = self.p * cnt - sy
        if "bounds" in col:
            cl, gl, extra = cnt[:left + 1].sum(), g[:left + 1].sum(), 0.0
        else:
            at = np.asarray(left, np.int64)
            cl, gl, extra = cnt[at].sum(), g[at].sum(), self.cat_l2
        return float(self.gain(cl, gl, extra)), int(cl)


def best_split(hists, n, sum_y, p, columns, params):
    """-> (search, best): ``best`` is None where no split is allowed, else a
    dict ``feature``, ``kind``, ``left`` (threshold bin, or sorted tuple of
    bins), ``gain``, ``left_rows``. Equal gains go to the first candidate in
    the order numerical < one against the rest < many against many, then
    column, then bin."""
    s = Search(n, sum_y, p, params)
    rank = {NUMERICAL: 0, ONE_VS_REST: 1, MANY_VS_MANY: 2}
    best = None
    for f, (col, (cnt, sy)) in enumerate(zip(columns, hists)):
        for i, (gain, kind, left, left_rows) in enumerate(s.column(col, cnt, sy)):
            at = (-gain, rank[kind], f, i)
            if gain > 0.0 and (best is None or at < best[0]):
                best = (at, {"feature": f, "kind": kind, "left": left, "gain": gain,
                             "left_rows": left_rows})
    return s, (best[1] if best else None)
