"""Dense float32 rows whose label comes from a noisy linear score over every
column (plus one interaction term): thresholded at 0 (``binary``) or cut into
grades at the configuration's label shares and grouped into queries
(``graded``).

Made in bulk on the host in float32 (the program bins on the host from
numpy), in row chunks on a few threads; chunk i has its own stream drawn from
``--seed``, so the data do not depend on the thread count. ``--seed`` draws
the rows and the noise; the weights of the score come from
``args["weights_seed"]``, so every seed samples the same problem.

For a ranking job the list of query sizes is part of the CONFIGURATION: it is
drawn from ``args["query_sizes"]["rng_seed"]``, never from ``--seed``, because
the program makes each query-length bucket a static shape of its compiled
block. Features and labels come from ``--seed``."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 18
THREADS = 8


def query_sizes(n, spec):
    """Heavy-tailed sizes: lognormal (``median``, ``sigma``) clipped to the
    published range [``min``, ``max``], after one query at each end of that
    range, drawn until they cover n rows; the last query takes what is left."""
    rng = np.random.default_rng(int(spec["rng_seed"]))
    lo, hi = int(spec["min"]), int(spec["max"])
    draw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=2 * n // lo + 16)
    sizes = np.concatenate([[lo, hi], np.clip(np.rint(draw), lo, hi)]).astype(np.int64)
    k = int(np.searchsorted(np.cumsum(sizes), n, side="left"))
    sizes = sizes[: k + 1].copy()
    sizes[k] -= sizes.sum() - n
    if sizes.sum() != n or sizes.min() <= 0:
        raise ValueError("query sizes %r do not tile %d rows" % (spec, n))
    return sizes


def make(shape, args, seed):
    n, f = int(shape["rows"]), int(shape["features"])
    # the weights are the PROBLEM and belong to the configuration: drawn from
    # --seed they changed the work by ~2 % from seed to seed (chip run, PR 24)
    w = np.random.default_rng([int(args["weights_seed"]), 0]).standard_normal(
        f, dtype=np.float32)
    w /= np.float32(np.sqrt(f))
    X = np.empty((n, f), dtype=np.float32)
    score = np.empty(n, dtype=np.float32)

    def fill(i):
        rows = slice(i * CHUNK_ROWS, min(n, (i + 1) * CHUNK_ROWS))
        rng = np.random.default_rng([int(seed), 1, i])
        x = X[rows]
        rng.standard_normal(out=x, dtype=np.float32)
        s = x @ w
        s += np.float32(args.get("interaction", 0.0)) * np.sin(2 * x[:, 0]) * x[:, 1]
        s += np.float32(args["noise"]) * rng.standard_normal(len(s), dtype=np.float32)
        score[rows] = s

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-n // CHUNK_ROWS))))
    if args["label"] == "binary":
        return {"X": X, "label": (score > 0).astype(np.float32), "group": None}
    edges = np.quantile(score[:: max(1, n // 200_000)], np.cumsum(args["label_shares"])[:-1])
    return {"X": X, "label": np.digitize(score, edges).astype(np.float32),
            "group": query_sizes(n, args["query_sizes"])}
