"""The split loop's histogram shape since PR 36: channel-major (3, F, Bp),
bins on the lanes (``ops/histogram.py`` ``hist_bins``), from the kernel's
last step through the learner's pool to the scan. Each piece against the
(F, B, 3) contract it replaced."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.learner import bundle_feature_view, pool_read, pool_write
from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops import split as S
from lightgbm_tpu.ops.histogram import (
    build_histogram_np, hist16_segment_planes, hist_bins, hist_fb3,
    hist_pallas_segment_planes, hist_planes, planes_kernel_params)
from lightgbm_tpu.ops.split import (
    FeatureMeta, SplitHyper, find_best_split, find_best_split_planes)

CH = 256


def _meta(num_bins, nan_missing=None, is_cat=None):
    f = len(num_bins)
    nb = np.asarray(num_bins, np.int32)
    nanm = np.zeros(f, bool) if nan_missing is None else np.asarray(nan_missing)
    cat = np.zeros(f, bool) if is_cat is None else np.asarray(is_cat)
    return FeatureMeta(
        num_bins=jnp.asarray(nb), movable_missing=jnp.asarray(nanm),
        missing_bin=jnp.asarray(np.where(nanm, nb - 1, 0).astype(np.int32)),
        is_categorical=jnp.asarray(cat), monotone=jnp.zeros(f, jnp.int8),
        penalty=jnp.ones(f, jnp.float32),
        cegb_coupled=jnp.zeros(f, jnp.float32))


def _hist(rng, num_bins, b):
    """(F, b, 3) with every feature's bins summing to one shared parent."""
    f = len(num_bins)
    hist = np.zeros((f, b, 3), np.float32)
    for i, nb in enumerate(num_bins):
        hist[i, :nb, 0] = rng.randn(nb) * 3
        hist[i, :nb, 1] = rng.rand(nb) + 0.1
        hist[i, :nb, 2] = rng.randint(20, 200, nb)
    parent = hist[0].sum(axis=0)
    for i in range(1, f):
        hist[i, 0] += parent - hist[i].sum(axis=0)
    return hist, parent


def _same_bits(a: S.SplitInfo, b: S.SplitInfo):
    for name, x, y in zip(a._fields, a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert x.tobytes() == y.tobytes(), name


@pytest.mark.parametrize("case", ["numerical", "missing_direction",
                                  "categorical_counted",
                                  "categorical_sorted", "bundled"])
def test_planes_scan_is_the_fb3_scan_bit_for_bit(rng, case, monkeypatch):
    """``find_best_split_planes`` over the (3, F, B) planes returns the
    ``SplitInfo`` that ``find_best_split`` returns over the same cells as
    (F, B, 3), every field bit for bit: the wrapper moves the axis once and
    nothing else, whatever the search (thresholds, both missing directions,
    one-against-the-rest and sorted category prefixes in both forms of the
    order, a bundled table's view)."""
    b = 24
    hp = SplitHyper(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
    kw = {}
    if case == "bundled":
        # three bundles of one-hot columns + one numeric column alone, as
        # BinnedDataset.bundle_maps lays them out; the view of the (3, G, Bp)
        # histogram is what the scan is handed
        import scipy.sparse as sp
        import lightgbm_tpu as lgb
        n = 3000
        cols = []
        for k in (5, 9, 4):
            c = rng.randint(0, k, size=n)
            m = np.zeros((n, k))
            m[np.arange(n), c] = 1
            cols.append(m)
        X = np.hstack(cols + [rng.normal(size=(n, 1))])
        ds = lgb.Dataset(sp.csr_matrix(X), label=(X[:, 0] > 0).astype(float),
                         params={"verbosity": -1, "min_data_in_leaf": 5})
        binned = ds.construct()
        assert binned.has_bundles
        G, bm = binned.num_groups, int(binned.group_num_bins().max())
        ghc = np.stack([rng.normal(size=n), rng.uniform(0.1, 1.0, n),
                        np.ones(n)], axis=1).astype(np.float32)
        hg = build_histogram_np(binned.binned, ghc, bm)           # (G, Bm, 3)
        parent = jnp.asarray(ghc.astype(np.float64).sum(axis=0), jnp.float32)
        maps = {k: jnp.asarray(v) for k, v in binned.bundle_maps().items()}
        planes = bundle_feature_view(hist_planes(jnp.asarray(hg)), parent,
                                     maps, bm)
        b = planes.shape[2]
        meta = _meta([m.num_bins for m in
                      (binned.bin_mappers[j] for j in
                       binned.used_feature_indices)])
        assert planes.shape == (3, len(meta.num_bins), b)
        fb3 = hist_fb3(planes, b)
    else:
        num_bins = [24, 12, 8, 24, 5, 17]
        hist, parent = _hist(rng, num_bins, b)
        parent = jnp.asarray(parent)
        if case == "missing_direction":
            meta = _meta(num_bins, nan_missing=[True, False, True, True,
                                                False, False])
        elif case.startswith("categorical"):
            meta = _meta(num_bins, is_cat=[True, False, True, False, True,
                                           False])
            hp = hp._replace(has_categorical=True, max_cat_to_onehot=6,
                             min_data_per_group=10, cat_smooth=1.0)
            if case == "categorical_sorted":
                monkeypatch.setattr(S, "_COUNT_MAX_CELLS", 0)
        else:
            meta = _meta(num_bins)
        fb3 = jnp.asarray(hist)
        planes = jnp.moveaxis(fb3, -1, 0)
    mask = jnp.ones(len(meta.num_bins), bool)
    a = find_best_split_planes(planes, parent, meta, mask, hp, **kw)
    w = find_best_split(fb3, parent, meta, mask, hp, **kw)
    assert np.isfinite(float(a.gain))
    _same_bits(a, w)
    # and under jit + vmap over a pair of nodes, as the split loop calls it
    pair = jax.jit(jax.vmap(
        lambda h: find_best_split_planes(h, parent, meta, mask, hp)))(
            jnp.stack([planes, planes]))
    _same_bits(jax.tree.map(lambda x: x[1], pair), a)


def _hilo(g):
    hi = np.asarray(jnp.asarray(g).astype(jnp.bfloat16).astype(jnp.float32))
    lo = np.asarray(jnp.asarray(g - hi).astype(jnp.bfloat16)
                    .astype(jnp.float32))
    return hi.astype(np.float64) + lo.astype(np.float64)


@pytest.mark.parametrize("f,num_bin", [(8, 256), (28, 256), (137, 256),
                                       (13, 256), (13, 100), (5, 64)])
def test_kernel_writes_the_channel_major_planes(rng, f, num_bin, monkeypatch):
    """``hist_pallas_segment_planes`` under the interpreter hands back the
    (3, F, Bp) planes themselves, no epilogue behind it: equal to the XLA
    twin ``hist16_segment_planes`` and to ``build_histogram_np`` moved to
    that shape, counts exactly, sums to 1e-6 of a cell's sum of |terms|
    (``tests/test_histogram.py``'s tolerance), zeros in the pad bins; at a
    whole 8-feature block, the cells' widths, and an odd F whose last MXU
    group holds one feature (13: a tail block of five features); 100 and
    64 bins take 4 and 8 features an MXU pass and one lane tile of bins."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    n, start, cnt = 1200, 57, 1000
    chunk = planes_kernel_params(f, num_bin, 0, 256)[3]
    guard = chunk + 2 * P.PLANE_ALIGN
    bins = jnp.asarray(rng.randint(0, num_bin, (n, f)).astype(np.uint8))
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[:, 2] = 1.0
    ghc = jnp.asarray(ghc)
    npad = P.planes_npad(n, guard, "pallas")
    _, w_pl = P.work_spec(f, False, "pallas", CH, CH, layout="planes")
    work, _ = P.pack_planes_fold_root(
        jnp.zeros((2, w_pl, npad), jnp.uint8), bins, ghc, guard,
        num_bins=num_bin, exact=True, chunk=CH)
    a = (jnp.int32(0), jnp.int32(guard + start), jnp.int32(cnt))
    got, _ = hist_pallas_segment_planes(work, *a, num_bins=num_bin,
                                        num_feat=f, chunk=chunk)
    ref = hist16_segment_planes(work, *a, num_bins=num_bin, num_feat=f,
                                chunk=CH)
    bp = hist_bins(num_bin)
    assert got.shape == ref.shape == (3, f, bp) and bp % 128 == 0
    got, ref = np.asarray(got), np.asarray(ref)
    assert not got[..., num_bin:].any() and not ref[..., num_bin:].any()
    seg = slice(start, start + cnt)
    terms = np.asarray(ghc)[seg].astype(np.float64)
    terms[:, :2] = _hilo(np.asarray(ghc)[seg, :2])
    b = np.asarray(bins)[seg]
    want = np.asarray(hist_planes(jnp.asarray(
        build_histogram_np(b, terms, num_bin)))).astype(np.float64)
    room = np.asarray(hist_planes(jnp.asarray(
        build_histogram_np(b, np.abs(terms), num_bin)))).astype(np.float64)
    assert np.array_equal(got[2], want[2]) and np.array_equal(got[2], ref[2])
    assert np.all(np.abs(got - want) <= 1e-6 * room + 1e-30)
    assert np.all(np.abs(got - ref) <= 2e-6 * room + 1e-30)


@pytest.mark.parametrize("g,num_bin", [(5, 255), (16, 64), (3, 130)])
def test_pool_round_trip_is_the_identity(rng, g, num_bin):
    """Write two children into the pool, read them back, subtract: every
    cell of every plane comes back bit for bit, the pad bins included (the
    pool's row IS the (3, G, Bp) histogram: nothing between the two is
    reshaped, rounded or cut), and no other leaf's row is touched."""
    leaves, bp = 6, hist_bins(num_bin)
    parent = jnp.asarray(rng.randn(3, g, bp).astype(np.float32))
    small = jnp.asarray(rng.randn(3, g, bp).astype(np.float32))

    @jax.jit
    def trip(pool, leaf, new_leaf, left_smaller):
        par = pool_read(pool, leaf)
        large = par - small
        pool = pool_write(pool, leaf, jnp.where(left_smaller, small, large))
        pool = pool_write(pool, new_leaf,
                          jnp.where(left_smaller, large, small))
        return pool, pool_read(pool, leaf), pool_read(pool, new_leaf)

    pool0 = pool_write(jnp.full((leaves, 3, g, bp), 7.0, jnp.float32),
                       jnp.int32(2), parent)
    pool, left, right = trip(pool0, jnp.int32(2), jnp.int32(4),
                             jnp.bool_(True))
    large = np.asarray(parent) - np.asarray(small)
    assert np.asarray(left).tobytes() == np.asarray(small).tobytes()
    assert np.asarray(right).tobytes() == large.tobytes()
    # parent - (parent - small) is the small child again to rounding only,
    # but what was written is what is read, bit for bit
    assert np.asarray(pool)[4].tobytes() == large.tobytes()
    assert np.asarray(pool)[2].tobytes() == np.asarray(small).tobytes()
    others = np.delete(np.asarray(pool), [2, 4], axis=0)
    assert np.all(others == 7.0)
    # the other orientation lands the children the other way round
    pool, left, right = trip(pool0, jnp.int32(2), jnp.int32(4),
                             jnp.bool_(False))
    assert np.asarray(left).tobytes() == large.tobytes()
    assert np.asarray(right).tobytes() == np.asarray(small).tobytes()
