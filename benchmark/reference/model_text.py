"""Plain reading of the model the program writes (``model_to_string``): parse
the text, walk the trees in numpy. Imports nothing from the program.

The walk follows the two decisions of upstream's ``tree.h``:

``Tree::NumericalDecision``. The node's missing type is NaN and x is NaN ->
the node's default side. The missing type is zero and |x| <= 1e-35 -> the
default side. A NaN under any other missing type is walked as 0 (so under
the missing type zero it takes the default side too). Otherwise ``x <=
threshold`` goes left.

``Tree::CategoricalDecision``. NaN -> right. Otherwise x is truncated toward
zero to an integer, as ``static_cast<int>`` does; a negative one -> right; one
that is in the node's set -> left; any other (a category the training rows
never held, or one the binning sent to the other bin) -> right. A value that
is no finite 32-bit integer goes right.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8
ROUTE_CHUNK = 1 << 20
ZERO_THRESHOLD = 1e-35          # upstream's kZeroThreshold
MISSING_ZERO, MISSING_NAN = 1, 2  # decision_type bits 2-3; 0 is none
_INT = ("split_feature", "left_child", "right_child", "leaf_count",
        "internal_count", "decision_type")
_FLOAT = ("leaf_value", "threshold", "split_gain")


def _cat_sets(kv, is_categorical, threshold):
    """{categorical node: sorted int64 category values that go left}, from
    the program's form (``cat_threshold=node:v1,v2;node:...``) or upstream's
    (``cat_boundaries`` + 32-bit bitset words in ``cat_threshold``, a node's
    ``threshold`` holding the index of its pair of boundaries)."""
    nodes = np.flatnonzero(is_categorical)
    text = kv.get("cat_threshold", "")
    if "cat_boundaries" not in kv:
        sets = {int(n): np.zeros(0, np.int64) for n in nodes}
        for item in filter(None, text.split(";")):
            node, values = item.split(":")
            sets[int(node)] = np.unique(np.array(
                [v for v in values.split(",") if v], dtype=np.int64))
        if sorted(sets) != nodes.tolist():
            raise ValueError("cat_threshold names nodes %s, decision_type marks %s as categorical"
                             % (sorted(sets), nodes.tolist()))
        return sets
    bounds = np.array(kv["cat_boundaries"].split(), dtype=np.int64)
    words = np.array(text.split(), dtype=np.uint64)
    sets = {}
    for n in nodes:
        i = int(threshold[n])
        w = words[bounds[i]:bounds[i + 1]]
        bits = (w[:, None] >> np.arange(32, dtype=np.uint64)) & np.uint64(1)
        sets[int(n)] = np.flatnonzero(bits.reshape(-1)).astype(np.int64)
    return sets


def parse(text):
    """-> (header dict of strings, list of trees as dicts of numpy arrays).

    Per tree, beside the text's own arrays: ``num_cat``; ``is_categorical``,
    ``default_left`` and ``missing_type`` (0 none, 1 zero, 2 NaN), unpacked
    from ``decision_type`` (bit 0, bit 1, bits 2-3: upstream's ``tree.h``);
    ``cat_sets``, see ``_cat_sets``. A linear tree is refused: the walk has
    no ``leaf_coeff``."""
    head, *blocks = text.split("\nTree=")
    header = dict(l.split("=", 1) for l in head.splitlines() if "=" in l)
    trees = []
    for b in blocks:
        kv = dict(l.split("=", 1) for l in b.split("\n\n")[0].splitlines()[1:]
                  if "=" in l)
        if int(kv.get("is_linear", 0)):
            raise ValueError("reference walks constant leaves only (is_linear=1: "
                             "no leaf_coeff, leaf_features, leaf_const)")
        t = {"num_leaves": int(kv["num_leaves"])}
        for k in _INT:
            t[k] = np.array(kv.get(k, "").split(), dtype=np.int64)
        for k in _FLOAT:
            t[k] = np.array(kv.get(k, "").split(), dtype=np.float64)
        kind = t["decision_type"]
        t["num_cat"] = int(kv.get("num_cat", 0))
        t["is_categorical"] = (kind & 1).astype(bool)
        t["default_left"] = (kind & 2).astype(bool)
        t["missing_type"] = (kind >> 2) & 3
        t["cat_sets"] = _cat_sets(kv, t["is_categorical"], t["threshold"])
        if t["num_cat"] != len(t["cat_sets"]):
            raise ValueError("num_cat=%d, decision_type marks %d categorical nodes"
                             % (t["num_cat"], len(t["cat_sets"])))
        trees.append(t)
    return header, trees


def trees_per_iteration(header):
    """K: tree i of the text adds to column i mod K of the raw score."""
    return int(header.get("num_tree_per_iteration", 1))


def window(header, trees, first, iterations):
    """-> (the trees of ``iterations`` iterations from iteration ``first`` on,
    K an iteration; how many of those iterations failed: one of its K trees
    is not there, or split nothing)."""
    K = trees_per_iteration(header)
    mine = trees[K * first:K * (first + iterations)]
    grown = [t["num_leaves"] > 1 for t in mine] + [False] * (K * iterations - len(mine))
    return mine, iterations - sum(all(grown[i:i + K]) for i in range(0, K * iterations, K))


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


def _cat_keys(tree):
    """Every (categorical node, category that goes left) as one sorted int64
    key ``node * span + category``, so that a chunk of rows at many nodes is
    looked up at once; a first key of -1 that no category makes keeps the
    array from being empty. -> (keys, span)."""
    sets = tree["cat_sets"]
    span = 1 + max((int(s[-1]) for s in sets.values() if s.size), default=0)
    keys = [n * span + s for n, s in sets.items()]
    return np.sort(np.concatenate([[-1]] + keys).astype(np.int64)), span


def _route_rows_decided(tree, X, nearest32):
    """The walk of a tree that holds a categorical split, a default side or a
    missing type: both decisions of the module's docstring, in float64 (a
    float32 x is exact there, so is the threshold's nearest float32)."""
    cat_keys, cat_span = _cat_keys(tree)
    flat, width = X.reshape(-1), X.shape[1]
    feature, left, right = tree["split_feature"], tree["left_child"], tree["right_child"]
    threshold = tree["threshold"]
    if nearest32:
        threshold = threshold.astype(np.float32).astype(np.float64)
    is_cat, default_left, missing = tree["is_categorical"], tree["default_left"], tree["missing_type"]
    node = np.zeros(X.shape[0], dtype=np.int64)
    active = np.arange(X.shape[0])
    while active.size:
        nd = node[active]
        x = flat.take(active * width + feature[nd]).astype(np.float64)
        nan = np.isnan(x)
        x0 = np.where(nan, 0.0, x)
        kind = missing[nd]
        to_default = np.where(kind == MISSING_NAN, nan,
                              (kind == MISSING_ZERO) & (np.abs(x0) <= ZERO_THRESHOLD))
        go_left = np.where(to_default, default_left[nd], x0 <= threshold[nd])
        cat = is_cat[nd]
        if cat.any():
            xc = x[cat]
            whole = np.isfinite(xc) & (np.abs(xc) < 2.0 ** 31)
            value = np.trunc(np.where(whole, xc, -1.0)).astype(np.int64)
            key = nd[cat] * cat_span + value
            at = np.minimum(np.searchsorted(cat_keys, key), cat_keys.size - 1)
            go_left[cat] = (cat_keys[at] == key) & (value >= 0) & (value < cat_span)
        nxt = np.where(go_left, left[nd], right[nd])
        node[active] = nxt
        active = active[nxt >= 0]
    return ~node


def route(tree, X, nearest32=False):
    """Leaf index of each row of X (C-contiguous): ``x <= threshold`` goes
    left, compared exactly (as training bins, in float64), or, with
    ``nearest32``, against the threshold rounded to the nearest float32, which
    can lie above it. A tree whose ``decision_type`` is all zero (plain
    numerical splits, no missing type, no default side) is walked by that
    comparison alone, which is the decision on every row but a NaN: the
    comparison sends it right, the decision walks it as 0. Such a tree comes
    from columns that held no NaN (or from ``use_missing=false``); give it
    rows that hold one only with that in mind. Any other tree is walked by
    the two decisions of the module's docstring. Row chunks on a few threads;
    numpy releases the GIL in the gathers."""
    if tree["num_leaves"] <= 1:
        return np.zeros(X.shape[0], dtype=np.int64)
    walk = _route_rows_decided if tree["decision_type"].any() else _route_rows
    bounds = range(0, X.shape[0], ROUTE_CHUNK)
    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(lambda a: walk(tree, X[a:a + ROUTE_CHUNK], nearest32), bounds))
    return np.concatenate(parts)


def leaf_counts(tree, X):
    return np.bincount(route(tree, X), minlength=tree["num_leaves"])


def raw_score(header, trees, X, nearest32=False):
    """Sum of leaf values in float64, tree by tree (the program sums in
    float32 in another order; the caller's tolerance allows for it). The
    initial score is the header's ``init_score``, one number a column. With
    K trees an iteration (``trees_per_iteration``) the score is (N, K) and
    tree i adds to column i mod K; with K = 1 it is (N,)."""
    K = trees_per_iteration(header)
    init = np.array(header.get("init_score", "").split() or [0.0], dtype=np.float64)
    out = np.zeros((X.shape[0], K)) + init
    for i, t in enumerate(trees):
        out[:, i % K] += t["leaf_value"][route(t, X, nearest32)]
    return out[:, 0] if K == 1 else out


def split_rows(tree):
    """Per split: rows of the parent, of the left and of the right child,
    from the counts the program recorded."""
    def count(child):
        return np.where(child >= 0, tree["internal_count"][np.maximum(child, 0)],
                        tree["leaf_count"][np.maximum(~child, 0)])
    return tree["internal_count"], count(tree["left_child"]), count(tree["right_child"])
