"""Plain reference for the first split of a binary log-loss job: gradients at
the initial score, one histogram per feature, one scan. float64, numpy only.

It takes the bin upper bounds the program's data layer chose (binning is the
host data layer's output and the tree learner's input; this reference checks
the learner, objective and kernels, not the bin finder) and bins the raw
float32 values against them itself."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .model_text import THREADS, floor32


def root_split(X, label, upper_bounds, min_data_in_leaf=20,
               min_sum_hessian=1e-3):
    """-> (feature, bin, gain): the best ``bin <= t`` split of all rows."""
    p = float(np.mean(label, dtype=np.float64))    # boost_from_average
    hess = p * (1.0 - p)                           # the same for every row
    n = len(label)
    G, H = p * n - float(np.sum(label, dtype=np.float64)), hess * n
    y = label.astype(np.float64)

    def best_of(f):
        ub = floor32(upper_bounds[f][:-1]) if X.dtype == np.float32 \
            else np.asarray(upper_bounds[f][:-1], np.float64)
        bins = np.searchsorted(ub, np.ascontiguousarray(X[:, f]), side="left")
        cnt = np.bincount(bins, minlength=len(ub) + 1).astype(np.float64)
        g = p * cnt - np.bincount(bins, weights=y, minlength=len(ub) + 1)  # sum of (p - y)
        cl, gl = np.cumsum(cnt)[:-1], np.cumsum(g)[:-1]
        hl = cl * hess
        cr, gr, hr = n - cl, G - gl, H - hl
        ok = ((cl >= min_data_in_leaf) & (cr >= min_data_in_leaf)
              & (hl >= min_sum_hessian) & (hr >= min_sum_hessian))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = np.where(ok, gl * gl / hl + gr * gr / hr - G * G / H, -np.inf)
        b = int(np.argmax(gain))
        return float(gain[b]), -f, b

    with ThreadPoolExecutor(THREADS) as pool:
        gain, neg_f, b = max(pool.map(best_of, range(X.shape[1])))
    return -neg_f, b, gain
