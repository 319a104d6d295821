"""The table of ``onehot_csr.py`` coded the other way: each categorical source
column ONE dense column of non-negative integer codes (what upstream's
docs/Advanced-Topics.rst, "Categorical Feature Support", tells users to pass
with ``categorical_feature``), followed by the numeric columns. Dense
float32 ``(N, columns + numeric)``.

It takes the arguments of ``onehot_csr.py`` (``columns``, ``numeric``,
``weights_seed``, ``weights_power``, ``noise``, ``positive_share``) and draws
THE SAME STREAMS: ``default_rng([seed, 1, i])`` for chunk i of ``1 << 18``
rows, the same draws in the same order, the same float32 score and the same
quantile cut. So for one ``--seed`` row r's code in column j is the one-hot
column ``onehot_csr`` sets in block j less that block's first column, the
numeric values are equal and so are the labels: the same rows and the same
problem, coded as categories instead of 700 indicator columns. Code k of a
column is its k-th most likely category (the hot ones first), as the blocks
of the one-hot table are laid out.

What the program derives from its sample is the same on every seed: 8 device
columns of 13, 32, 8, 23, 255, 255, 240 and 200 bins (a categorical column
holds a bin a category, the 254 most frequent of the sample at most, + the
shared last bin), so nothing static follows ``--seed``.
``benchmark/tests/test_categorical_harness.py`` holds both statements.

The generator is written out again rather than imported: a benchmark file
stands alone, and ``onehot_csr.py`` may not be edited to share its pieces."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.special import ndtri

CHUNK_ROWS = 1 << 18
THREADS = 8
QUANTILE_SAMPLE = 200_000
# 200 equally likely levels of a lognormal distance (median 600, sigma 0.8)
MILES = (600.0 * np.exp(0.8 * ndtri((np.arange(200) + 0.5) / 200))).astype(np.float32)


def category_cdf(categories, zipf):
    p = np.arange(1, int(categories) + 1, dtype=np.float64) ** -float(zipf)
    return np.cumsum(p / p.sum())


def score_weights(args):
    """One weight per category of every column, in column order: the PROBLEM,
    a constant of the configuration (``onehot_csr.score_weights``)."""
    sizes = [int(c["categories"]) for c in args["columns"]]
    starts = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    w = np.random.default_rng([int(args["weights_seed"]), 0]).standard_normal(int(starts[-1]))
    power = float(args["weights_power"])
    for start, c in zip(starts, args["columns"]):
        rank = np.arange(1, int(c["categories"]) + 1, dtype=np.float64)
        w[start:start + len(rank)] *= rank ** (-float(c["zipf"]) * power)
    return starts[:-1], w.astype(np.float32)


def make(shape, args, seed):
    n = int(shape["rows"])
    k, m = len(args["columns"]), len(args["numeric"])
    if k + m != int(shape["features"]):
        raise ValueError("columns of the generator (%d) are not the configuration's "
                         "features (%s)" % (k + m, shape["features"]))
    cdfs = [category_cdf(c["categories"], c["zipf"]) for c in args["columns"]]
    starts, w = score_weights(args)
    X = np.empty((n, k + m), dtype=np.float32)
    score = np.empty(n, dtype=np.float32)

    def fill(i):
        rows = slice(i * CHUNK_ROWS, min(n, (i + 1) * CHUNK_ROWS))
        size = rows.stop - rows.start
        rng = np.random.default_rng([int(seed), 1, i])
        s = np.zeros(size, dtype=np.float32)
        for j, cdf in enumerate(cdfs):
            cat = np.minimum(np.searchsorted(cdf, rng.random(size)), len(cdf) - 1)
            X[rows, j] = cat
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
            X[rows, k + j] = v
        s += np.float32(args["noise"]) * rng.standard_normal(size, dtype=np.float32)
        score[rows] = s

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-n // CHUNK_ROWS))))
    cut = np.quantile(score[:: max(1, n // QUANTILE_SAMPLE)],
                      1.0 - float(args["positive_share"]))
    return {"X": X, "label": (score > cut).astype(np.float32), "group": None}
