"""Plain reference for the SECOND level of the first tree of a binary log-loss
job: given the root's split, the best split of each of its two children over
every column. float64, numpy only; imports nothing from the program.

The root's split is one histogram over all rows. A child's is the first the
program makes from a SEGMENT of partitioned rows (the smaller child) and the
first it makes by subtraction from the parent's pooled histogram (the larger
one): the two mechanisms whose cost and memory follow the table's width. Here
both children are binned and counted directly from their own rows.

As ``binary_root`` does, it takes the bin upper bounds the program's data layer
chose and bins the raw float32 values against them itself."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .model_text import THREADS, floor32

LEFT, RIGHT = 0, 1


def _gains(cnt, g, hess, n, G, H, min_data_in_leaf, min_sum_hessian):
    """Gain of every ``bin <= t`` split of one node from its per-bin row
    counts and gradient sums (the hessian is the same for every row)."""
    cl, gl = np.cumsum(cnt)[:-1], np.cumsum(g)[:-1]
    hl = cl * hess
    cr, gr, hr = n - cl, G - gl, H - hl
    ok = ((cl >= min_data_in_leaf) & (cr >= min_data_in_leaf)
          & (hl >= min_sum_hessian) & (hr >= min_sum_hessian))
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(ok, gl * gl / hl + gr * gr / hr - G * G / H, -np.inf), cl


def child_splits(X, label, upper_bounds, root_feature, root_bin,
                 min_data_in_leaf=20, min_sum_hessian=1e-3, probe=()):
    """-> ([left, right], probed): each child a dict with its ``rows`` and its
    best split ``feature``, ``bin``, ``gain``, ``left_rows`` (None where no
    split is allowed); ``probed[(side, feature, bin)]`` = (gain, left rows) of
    that split, for the caller's ties. Rows with root-feature bin <=
    ``root_bin`` are the left child."""
    p = float(np.mean(label, dtype=np.float64))    # boost_from_average
    hess = p * (1.0 - p)                           # the same for every row
    y = label.astype(np.float64)

    def bins_of(f):
        ub = floor32(upper_bounds[f][:-1]) if X.dtype == np.float32 \
            else np.asarray(upper_bounds[f][:-1], np.float64)
        return np.searchsorted(ub, np.ascontiguousarray(X[:, f]), side="left"), len(ub) + 1

    in_left = bins_of(root_feature)[0] <= root_bin
    rows = [np.flatnonzero(in_left), np.flatnonzero(~in_left)]
    n = [len(r) for r in rows]
    ys = [y[r] for r in rows]
    G = [p * n[s] - float(ys[s].sum()) for s in (LEFT, RIGHT)]  # sum of (p - y)
    H = [hess * n[s] for s in (LEFT, RIGHT)]
    want = {}
    for side, f, b in probe:
        want.setdefault(int(f), []).append((int(side), int(b)))

    def best_of(f):
        bins, nb = bins_of(f)
        out, seen = [], {}
        for s in (LEFT, RIGHT):
            bs = bins[rows[s]]
            cnt = np.bincount(bs, minlength=nb).astype(np.float64)
            g = p * cnt - np.bincount(bs, weights=ys[s], minlength=nb)
            gain, cl = _gains(cnt, g, hess, n[s], G[s], H[s],
                              min_data_in_leaf, min_sum_hessian)
            b = int(np.argmax(gain)) if len(gain) else 0
            out.append((float(gain[b]) if len(gain) else -np.inf, -f, b,
                        int(cl[b]) if len(gain) else 0))
            for side, pb in want.get(f, ()):
                if side == s and 0 <= pb < len(gain):
                    seen[(s, f, pb)] = (float(gain[pb]), int(cl[pb]))
        return out, seen

    with ThreadPoolExecutor(THREADS) as pool:
        found = list(pool.map(best_of, range(X.shape[1])))
    children, probed = [], {}
    for s in (LEFT, RIGHT):
        gain, neg_f, b, left_rows = max(out[s] for out, _ in found)
        ok = np.isfinite(gain)
        children.append({"rows": n[s], "feature": -neg_f if ok else None,
                         "bin": b if ok else None, "gain": gain if ok else None,
                         "left_rows": left_rows if ok else None})
    for _, seen in found:
        probed.update(seen)
    return children, probed
