"""A one-hot table in CSR: a few categorical source columns, each spread over
one 0/1 column per category, and a few numeric columns; ``nonzeros`` stored
entries a row (one per source column), float32, indices sorted within a row.
The label is a noisy score over every one-hot column and both numeric ones,
cut at the quantile that leaves ``positive_share`` of the rows positive.

``args["columns"]`` lists the categorical source columns in column order:
``{"name", "categories", "zipf"}``; category k (0-based) is drawn with
probability proportional to ``(k + 1) ** -zipf`` (0 = uniform), so the hot
categories come first in each block. ``args["numeric"]`` lists the numeric
columns, which follow the one-hot blocks: ``{"name", "kind"}`` with kind
``minute_of_day`` (uniform over the 240 six-minute marks 6..1440) or ``miles``
(lognormal, median 600, sigma 0.8, cut into 200 equally likely levels, each at
its own median); both have fewer distinct values than ``max_bin``, all of
them in any sample the program takes, so its bin finder gives each value a bin
of its own and the bounds are the same on every seed (continuous values, or
whole minutes, got bounds and even bin counts by the luck of the sample, and
the widest feature's bin count is a static shape of the compiled block), and
never zero, so every row stores every source column.

Made in row chunks on a few threads; chunk i has its own stream drawn from
``--seed``, so the data do not depend on the thread count. ``--seed`` draws
the rows (every column), the noise and so the labels, as in the accepted
generator ``linear_score.py``; the weights of the score (``weights_seed``,
``weights_power``) and the categories' frequencies are constants of the
configuration, so every seed samples the same problem. What the program
derives from its sample (10 device columns, their bin widths, 700 used
features) is the same on every seed (chip runs, PR 28).

``weights_power`` is what keeps the WORK the same too. A one-hot
split peels one category off its parent and the rest is visited again, so a
tree costs between 15 and 100 row visits a row by which splits win. With every
category's weight drawn alike (power 0), the rare airports' effects are some
300 near-equal split gains once the calendar and the hubs are fitted, every
sample orders them its own way, and ``train_ms_per_iter`` spread 1.55 % over
six seeds (``PERF.md``, section 6). A category's weight is therefore its
normal draw times ``(its frequency / its column's hottest) ** weights_power``:
at power 1 the hubs carry the effects and a rare airport sits near the mean,
the order of the gains is the problem's and not the sample's, and every seed
grows trees of the same cost."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp
from scipy.special import ndtri

CHUNK_ROWS = 1 << 18
THREADS = 8
QUANTILE_SAMPLE = 200_000
# 200 equally likely levels of a lognormal distance (median 600, sigma 0.8)
MILES = (600.0 * np.exp(0.8 * ndtri((np.arange(200) + 0.5) / 200))).astype(np.float32)


def layout(args):
    """-> (first column of each categorical block, first numeric column, width)."""
    sizes = [int(c["categories"]) for c in args["columns"]]
    starts = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    return starts[:-1], int(starts[-1]), int(starts[-1]) + len(args["numeric"])


def category_cdf(categories, zipf):
    p = np.arange(1, int(categories) + 1, dtype=np.float64) ** -float(zipf)
    return np.cumsum(p / p.sum())


def score_weights(args):
    """One weight per one-hot column: the PROBLEM, a constant of the file.
    Category k of a column is ``(k + 1) ** -zipf`` as frequent as its hottest,
    and weighs that share to the power ``weights_power`` of its draw."""
    starts, first_numeric, _ = layout(args)
    w = np.random.default_rng([int(args["weights_seed"]), 0]).standard_normal(first_numeric)
    power = float(args["weights_power"])
    for start, c in zip(starts, args["columns"]):
        rank = np.arange(1, int(c["categories"]) + 1, dtype=np.float64)
        w[start:start + len(rank)] *= rank ** (-float(c["zipf"]) * power)
    return w.astype(np.float32)


def make(shape, args, seed):
    n = int(shape["rows"])
    starts, first_numeric, width = layout(args)
    if width != int(shape["source_features"]):
        raise ValueError("columns of the generator (%d) are not the configuration's "
                         "source_features (%s)" % (width, shape["source_features"]))
    cdfs = [category_cdf(c["categories"], c["zipf"]) for c in args["columns"]]
    w = score_weights(args)
    k, m = len(cdfs), len(args["numeric"])
    indices = np.empty((n, k + m), dtype=np.int32)
    values = np.ones((n, k + m), dtype=np.float32)
    indices[:, k:] = first_numeric + np.arange(m, dtype=np.int32)
    score = np.empty(n, dtype=np.float32)

    def fill(i):
        rows = slice(i * CHUNK_ROWS, min(n, (i + 1) * CHUNK_ROWS))
        size = rows.stop - rows.start
        rng = np.random.default_rng([int(seed), 1, i])
        s = np.zeros(size, dtype=np.float32)
        for j, cdf in enumerate(cdfs):
            cat = np.minimum(np.searchsorted(cdf, rng.random(size)), len(cdf) - 1)
            indices[rows, j] = starts[j] + cat
            s += w[starts[j] + cat]
        for j, col in enumerate(args["numeric"]):
            if col["kind"] == "minute_of_day":
                v = np.float32(6.0) * rng.integers(1, 241, size=size).astype(np.float32)
                s += np.float32(0.5) * np.sin(v * np.float32(2 * np.pi / 1440))
            elif col["kind"] == "miles":
                v = MILES[rng.integers(0, len(MILES), size=size)]
                s += np.float32(0.3) * np.log(v / np.float32(600.0))
            else:
                raise KeyError(col["kind"])
            values[rows, k + j] = v
        s += np.float32(args["noise"]) * rng.standard_normal(size, dtype=np.float32)
        score[rows] = s

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-n // CHUNK_ROWS))))
    cut = np.quantile(score[:: max(1, n // QUANTILE_SAMPLE)],
                      1.0 - float(args["positive_share"]))
    X = sp.csr_matrix((values.reshape(-1), indices.reshape(-1),
                       np.arange(0, (k + m) * n + 1, k + m, dtype=np.int64 if
                                 (k + m) * n >= 2 ** 31 else np.int32)),
                      shape=(n, width))
    X.has_sorted_indices = True
    return {"X": X, "label": (score > cut).astype(np.float32), "group": None}
