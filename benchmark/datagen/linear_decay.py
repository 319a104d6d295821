"""Dense float32 rows whose binary label is the sign of a noisy linear score in
which the columns do NOT weigh alike: the k-th strongest column's weight falls
as ``(k + 1) ** -weights_power``, its sign and which column is k-th come from
``args["weights_seed"]``. ``linear_score`` gives every column one size of
weight; at 2,000 columns that leaves each with 1/2000 of the score, every node
with 2,000 near-ties, and a best gain that is the small difference of two large
sums (PERF.md, PR 32). Here a few hundred columns carry the score on every
sample, as the strong features of a real wide table do.

``--seed`` draws the rows and the noise, in row chunks on a few threads (chunk i
has its own stream, so the data do not depend on the thread count); the weights,
their order and their decay are constants of the configuration."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 16
THREADS = 8


def weights(features, args):
    """(features,) float32, unit norm: magnitude by rank, rank and sign by
    ``weights_seed``."""
    rng = np.random.default_rng([int(args["weights_seed"]), 0])
    rank = rng.permutation(features)
    sign = rng.choice(np.array([-1.0, 1.0]), size=features)
    w = sign * (rank + 1.0) ** -float(args["weights_power"])
    return (w / np.sqrt(np.sum(w * w))).astype(np.float32)


def make(shape, args, seed):
    n, f = int(shape["rows"]), int(shape["features"])
    if args["label"] != "binary":
        raise ValueError("linear_decay makes binary labels only")
    w = weights(f, args)
    X = np.empty((n, f), dtype=np.float32)
    label = np.empty(n, dtype=np.float32)

    def fill(i):
        rows = slice(i * CHUNK_ROWS, min(n, (i + 1) * CHUNK_ROWS))
        rng = np.random.default_rng([int(seed), 1, i])
        x = X[rows]
        rng.standard_normal(out=x, dtype=np.float32)
        s = x @ w
        s += np.float32(args["noise"]) * rng.standard_normal(len(s), dtype=np.float32)
        label[rows] = s > 0

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-n // CHUNK_ROWS))))
    return {"X": X, "label": label, "group": None}
