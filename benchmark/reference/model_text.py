"""Plain reading of the model the program writes (``model_to_string``): parse
the text, walk the trees in numpy. Imports nothing from the program."""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8
ROUTE_CHUNK = 1 << 20
_INT = ("split_feature", "left_child", "right_child", "leaf_count",
        "internal_count", "decision_type")
_FLOAT = ("leaf_value", "threshold", "split_gain")


def parse(text):
    """-> (header dict of strings, list of trees as dicts of numpy arrays)."""
    head, *blocks = text.split("\nTree=")
    header = dict(l.split("=", 1) for l in head.splitlines() if "=" in l)
    trees = []
    for b in blocks:
        kv = dict(l.split("=", 1) for l in b.split("\n\n")[0].splitlines()[1:]
                  if "=" in l)
        t = {"num_leaves": int(kv["num_leaves"])}
        for k in _INT:
            t[k] = np.array(kv.get(k, "").split(), dtype=np.int64)
        for k in _FLOAT:
            t[k] = np.array(kv.get(k, "").split(), dtype=np.float64)
        if int(kv.get("num_cat", 0)) or np.any(t["decision_type"] != 0):
            raise ValueError("reference walks plain numerical splits only "
                             "(no categorical, no missing-value routing)")
        trees.append(t)
    return header, trees


def floor32(bounds):
    """Largest float32 <= each float64 bound: for a float32 x,
    ``x <= bound`` in float64 (how the program bins) is ``x <= floor32(bound)``
    in float32, exactly."""
    b = np.asarray(bounds, np.float64)
    f = b.astype(np.float32)
    return np.where(f.astype(np.float64) > b, np.nextafter(f, np.float32(-np.inf)), f)


def _route_rows(tree, X, nearest32):
    flat, width = X.reshape(-1), X.shape[1]
    feature, left, right = tree["split_feature"], tree["left_child"], tree["right_child"]
    if nearest32:
        threshold = tree["threshold"].astype(np.float32)
    else:
        threshold = floor32(tree["threshold"]) if X.dtype == np.float32 else tree["threshold"]
    node = np.zeros(X.shape[0], dtype=np.int64)
    active = np.arange(X.shape[0])
    while active.size:
        nd = node[active]
        go_left = flat.take(active * width + feature[nd]) <= threshold[nd]
        nxt = np.where(go_left, left[nd], right[nd])
        node[active] = nxt
        active = active[nxt >= 0]
    return ~node


def route(tree, X, nearest32=False):
    """Leaf index of each row of X (C-contiguous): ``x <= threshold`` goes
    left, compared exactly (as training bins, in float64), or, with
    ``nearest32``, against the threshold rounded to the nearest float32, which
    can lie above it. Row chunks on a few threads; numpy releases the GIL in
    the gathers."""
    if tree["num_leaves"] <= 1:
        return np.zeros(X.shape[0], dtype=np.int64)
    bounds = range(0, X.shape[0], ROUTE_CHUNK)
    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(lambda a: _route_rows(tree, X[a:a + ROUTE_CHUNK], nearest32), bounds))
    return np.concatenate(parts)


def leaf_counts(tree, X):
    return np.bincount(route(tree, X), minlength=tree["num_leaves"])


def raw_score(header, trees, X, nearest32=False):
    """Sum of leaf values in float64, tree by tree (the program sums in
    float32 in another order; the caller's tolerance allows for it). The
    initial score is the header's ``init_score``."""
    out = np.full(X.shape[0], float(header.get("init_score", 0.0)))
    for t in trees:
        out += t["leaf_value"][route(t, X, nearest32)]
    return out


def split_rows(tree):
    """Per split: rows of the parent, of the left and of the right child,
    from the counts the program recorded."""
    def count(child):
        return np.where(child >= 0, tree["internal_count"][np.maximum(child, 0)],
                        tree["leaf_count"][np.maximum(~child, 0)])
    return tree["internal_count"], count(tree["left_child"]), count(tree["right_child"])
