"""Best-split scan vs exhaustive naive search."""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.split import (
    FeatureMeta, SplitHyper, find_best_split, leaf_objective_value)


def _meta(num_bins, nan_missing=None, is_cat=None):
    f = len(num_bins)
    nb = np.asarray(num_bins, np.int32)
    nanm = np.zeros(f, bool) if nan_missing is None else np.asarray(nan_missing)
    cat = np.zeros(f, bool) if is_cat is None else np.asarray(is_cat)
    return FeatureMeta(
        num_bins=jnp.asarray(nb),
        movable_missing=jnp.asarray(nanm),
        missing_bin=jnp.asarray(np.where(nanm, nb - 1, 0).astype(np.int32)),
        is_categorical=jnp.asarray(cat),
        monotone=jnp.zeros(f, jnp.int8),
        penalty=jnp.ones(f, jnp.float32),
        cegb_coupled=jnp.zeros(f, jnp.float32),
    )


def _naive_best(hist, parent, num_bins, hp):
    """Exhaustive numerical threshold search, default-right only, no missing."""
    def gain(g, h):
        if h + hp.lambda_l2 <= 0:
            return 0.0
        tl1 = np.sign(g) * max(abs(g) - hp.lambda_l1, 0)
        return tl1 ** 2 / (h + hp.lambda_l2)
    pg = gain(parent[0], parent[1])
    best = (-np.inf, -1, -1)
    for f in range(hist.shape[0]):
        for t in range(num_bins[f] - 1):
            left = hist[f, : t + 1].sum(axis=0)
            right = parent - left
            if left[2] < hp.min_data_in_leaf or right[2] < hp.min_data_in_leaf:
                continue
            if left[1] < hp.min_sum_hessian_in_leaf or right[1] < hp.min_sum_hessian_in_leaf:
                continue
            imp = gain(left[0], left[1]) + gain(right[0], right[1]) - pg
            if imp > best[0]:
                best = (imp, f, t)
    return best


def test_matches_naive_numerical(rng):
    f, b = 5, 16
    num_bins = [16, 12, 8, 16, 5]
    hist = np.zeros((f, b, 3), np.float32)
    for i in range(f):
        nb = num_bins[i]
        hist[i, :nb, 0] = rng.randn(nb) * 3
        hist[i, :nb, 1] = rng.rand(nb) + 0.1
        hist[i, :nb, 2] = rng.randint(1, 50, nb)
    # make per-feature totals consistent with a shared parent
    parent = hist[0].sum(axis=0)
    for i in range(1, f):
        s = hist[i].sum(axis=0)
        hist[i] *= (parent / np.maximum(s, 1e-10))[None, :]
    hp = SplitHyper(min_data_in_leaf=3.0, lambda_l2=0.5)
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                           _meta(num_bins), jnp.ones(f, bool), hp)
    exp_gain, exp_f, exp_t = _naive_best(hist, parent, num_bins, hp)
    assert abs(float(info.gain) - exp_gain) < 1e-2 * max(1, abs(exp_gain))
    assert int(info.feature) == exp_f
    assert int(info.bin) == exp_t


def test_min_data_blocks_split():
    f, b = 1, 4
    hist = np.zeros((f, b, 3), np.float32)
    hist[0, :, 0] = [5, -5, 4, -4]
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 5
    parent = hist[0].sum(axis=0)
    hp = SplitHyper(min_data_in_leaf=100.0)
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                           _meta([4]), jnp.ones(1, bool), hp)
    assert float(info.gain) == -np.inf


def test_missing_direction():
    """NaN bin mass should be routed to whichever side improves gain."""
    f, b = 1, 5
    hist = np.zeros((f, b, 3), np.float32)
    # value bins 0..3, missing bin 4; negatives left, positives right,
    # missing gradient aligned with LEFT side
    hist[0, :, 0] = [-10, -8, 9, 8, -6]
    hist[0, :, 1] = [2, 2, 2, 2, 2]
    hist[0, :, 2] = [10, 10, 10, 10, 10]
    parent = hist[0].sum(axis=0)
    hp = SplitHyper(min_data_in_leaf=1.0)
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                           _meta([5], nan_missing=[True]), jnp.ones(1, bool), hp)
    assert bool(info.default_left)
    tbl = np.asarray(info.go_left)
    assert tbl[4]  # missing goes left
    assert tbl[0] and tbl[1] and not tbl[2]


def test_feature_mask_respected():
    f, b = 2, 4
    hist = np.zeros((f, b, 3), np.float32)
    hist[:, :, 0] = [[9, -9, 9, -9], [1, -1, 1, -1]]
    hist[:, :, 1] = 1.0
    hist[:, :, 2] = 25.0
    parent = hist[0].sum(axis=0)
    hp = SplitHyper(min_data_in_leaf=1.0)
    mask = jnp.asarray([False, True])
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                           _meta([4, 4]), mask, hp)
    assert int(info.feature) == 1


def test_categorical_onehot():
    f, b = 1, 4  # 3 categories + other bin
    hist = np.zeros((f, b, 3), np.float32)
    hist[0, :, 0] = [20, -10, -10, 0]
    hist[0, :, 1] = [5, 5, 5, 0.001]
    hist[0, :, 2] = [30, 30, 30, 1]
    parent = hist[0].sum(axis=0)
    hp = SplitHyper(min_data_in_leaf=1.0, min_sum_hessian_in_leaf=0.0,
                    has_categorical=True, max_cat_to_onehot=4)
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                           _meta([4], is_cat=[True]), jnp.ones(1, bool), hp)
    assert int(info.kind) == 1
    assert int(info.bin) == 0  # category 0 isolated
    tbl = np.asarray(info.go_left)
    assert tbl[0] and not tbl[1] and not tbl[2]


def test_categorical_many_vs_many():
    f, b = 1, 9  # 8 categories + other
    hist = np.zeros((f, b, 3), np.float32)
    g = np.asarray([5, -5, 4, -4, 3, -3, 2, -2], np.float32)
    hist[0, :8, 0] = g
    hist[0, :8, 1] = 2.0
    hist[0, :8, 2] = 20.0
    parent = hist[0].sum(axis=0)
    hp = SplitHyper(min_data_in_leaf=1.0, min_data_per_group=1.0,
                    has_categorical=True, max_cat_to_onehot=2, cat_smooth=0.0,
                    cat_l2=0.0)
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                           _meta([9], is_cat=[True]), jnp.ones(1, bool), hp)
    assert int(info.kind) in (2, 3)
    tbl = np.asarray(info.go_left)
    # optimal grouping separates positive-gradient from negative-gradient cats
    side_neg = set(np.flatnonzero(tbl))
    assert side_neg in ({1, 3, 5, 7}, {0, 2, 4, 6})


def test_monotone_constraint_blocks():
    f, b = 1, 4
    hist = np.zeros((f, b, 3), np.float32)
    # increasing feature with DECREASING response: +1 constraint must block
    hist[0, :, 0] = [-10, -5, 5, 10]   # grad = pred-target => left wants +, right -
    hist[0, :, 1] = 2.0
    hist[0, :, 2] = 20.0
    parent = hist[0].sum(axis=0)
    hp = SplitHyper(min_data_in_leaf=1.0, has_monotone=True)
    meta = _meta([4])._replace(monotone=jnp.asarray([1], jnp.int8))
    info = find_best_split(jnp.asarray(hist), jnp.asarray(parent), meta,
                           jnp.ones(1, bool), hp)
    assert float(info.gain) == -np.inf


# ---- the categorical order by counting (PR 35) against the sorts it replaced

def _cat_hist(rng, num_bins, ties):
    """Per-bin (g, h, count) of a few categorical columns and a numerical one;
    with ``ties`` several bins of a column share one g / h, so that only the
    stable order (ties by bin index) tells them apart."""
    f, b = len(num_bins), max(num_bins)
    hist = np.zeros((f, b, 3), np.float32)
    for i, nb in enumerate(num_bins):
        cnt = rng.randint(0, 400, nb).astype(np.float32)   # some under min_data_per_group
        hist[i, :nb, 2] = cnt
        hist[i, :nb, 1] = 0.25 * cnt
        ratio = rng.randn(nb)
        if ties:
            ratio = np.round(ratio)                         # -2 .. 2: many equal keys
        hist[i, :nb, 0] = ratio * (hist[i, :nb, 1] + 10.0) if ties \
            else ratio * hist[i, :nb, 1]
    parent = hist[0].sum(axis=0)
    for i in range(1, f):       # one parent for all columns: the rest in bin 0
        hist[i, 0] += parent - hist[i].sum(axis=0)
    return hist, parent



@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_rank_by_count_is_the_stable_sorts_order(rng, ties):
    from lightgbm_tpu.ops import split
    keys = rng.randn(2, 5, 64).astype(np.float32)
    if ties:
        keys = np.round(keys)
    keys[:, :, 50:] = np.inf                  # bins that are no group sort last
    keys[0, 0, 3] = np.nan                    # ranks with them, as +inf
    want = np.argsort(np.argsort(np.where(np.isnan(keys), np.inf, keys),
                                 axis=-1, kind="stable"), axis=-1, kind="stable")
    got = split._stable_rank(jnp.asarray(keys), jnp.arange(64, dtype=jnp.int32))
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_count_form_gives_the_sorted_forms_split_bit_for_bit(rng, ties, monkeypatch):
    """The same SplitInfo, every field and every bit, whether a bin's place
    in its column's order is counted or sorted: the count is the stable
    sort's order, the one-hot select picks what the gather picked, and the
    winner's table is a compare where the sorted form scatters."""
    from lightgbm_tpu.ops import split
    num_bins = [40, 64, 13, 30, 64]
    meta = _meta(num_bins, is_cat=[True, True, True, True, False])
    hp = SplitHyper(min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3,
                    has_categorical=True, min_data_per_group=50.0)
    found = set()
    for _ in range(6):
        hist, parent = _cat_hist(rng, num_bins, ties)
        mask = jnp.asarray(rng.rand(5) < 0.8)
        infos = []
        for limit in (split._COUNT_MAX_CELLS, 0):
            monkeypatch.setattr(split, "_COUNT_MAX_CELLS", limit)
            infos.append(find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                                         meta, mask, hp))
        for a, b in zip(*infos):
            assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
        found.add(int(infos[0].kind))
    assert found & {2, 3}, found          # many-against-many winners among them


def test_count_form_trains_the_sorted_forms_trees(rng, monkeypatch):
    """Through ``lgb.train``: the model text is the same, byte for byte."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import fused
    from lightgbm_tpu.ops import split
    n = 4000
    X = rng.randn(n, 4)
    X[:, 0] = rng.randint(0, 40, n)
    X[:, 1] = np.minimum(rng.zipf(1.4, n), 90) - 1
    w0, w1 = rng.randn(40), rng.randn(90)
    y = (w0[X[:, 0].astype(int)] + w1[X[:, 1].astype(int)] + X[:, 2]
         + 0.3 * rng.randn(n) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "min_data_in_leaf": 5, "min_data_per_group": 20}
    texts = []
    for limit in (split._COUNT_MAX_CELLS, 0):
        monkeypatch.setattr(split, "_COUNT_MAX_CELLS", limit)
        jax.clear_caches()
        fused._BLOCK_CACHE.clear()
        ds = lgb.Dataset(X, label=y, params=dict(params),
                         categorical_feature=[0, 1])
        texts.append(lgb.train(dict(params), ds,
                               num_boost_round=5).model_to_string())
    jax.clear_caches()
    fused._BLOCK_CACHE.clear()
    assert texts[0] == texts[1]
    assert "cat_threshold=" in texts[0]
