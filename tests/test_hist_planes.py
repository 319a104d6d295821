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
                                     maps, bm, binned.bundle_view())
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


# ------------------------------------------- the bundled table's view (PR 38)

def _layout(groups):
    """A ``BinnedDataset`` that holds nothing but a bundle layout:
    ``groups`` lists every device column's members as (feature, num_bins,
    default_bin), laid out as ``dataset._make_groups`` does (slot 0 of a
    shared column is the shared zero, a member owns the next num_bins - 1)."""
    from types import SimpleNamespace
    from lightgbm_tpu.dataset import BinnedDataset, FeatureGroupInfo
    ds = BinnedDataset()
    feats = sorted(m for g in groups for m in g)
    assert [f for f, _, _ in feats] == list(range(len(feats)))
    ds.bin_mappers = [SimpleNamespace(num_bins=nb, default_bin=d)
                      for _, nb, d in feats]
    ds.feature_to_group = np.zeros(len(feats), np.int32)
    ds.feature_group_offset = np.zeros(len(feats), np.int32)
    for gid, members in enumerate(groups):
        offs, off = [], 1 if len(members) > 1 else 0
        for f, nb, _ in members:
            offs.append(off)
            ds.feature_to_group[f], ds.feature_group_offset[f] = gid, off
            off += nb - 1
        ds.groups.append(FeatureGroupInfo(
            [f for f, _, _ in members], offs,
            off if len(members) > 1 else members[0][1]))
    return ds


def _view_layouts(name):
    rng = np.random.RandomState(7)
    if name == "expo_like":
        # expo.train's table: 698 one-hot columns in eight bundles and two
        # numeric columns alone, 240 and 200 bins
        groups, f = [], 0
        for k in (12, 31, 7, 22, 255, 58, 255, 58):
            groups.append([(f + i, 2, 0) for i in range(k)])
            f += k
        return groups + [[(f, 240, 0)], [(f + 1, 200, 0)]]
    if name.startswith("default_"):
        # numerical members of 3-60 bins, the default bin first, in the
        # middle or last
        where = name[len("default_"):]
        groups, f = [], 0
        for widths in ((3, 60, 17, 5), (33, 8, 4, 21, 12), (60, 60), (9, 3)):
            g = []
            for nb in widths:
                g.append((f, nb, {"first": 0, "middle": nb // 2,
                                  "last": nb - 1}[where]))
                f += 1
            groups.append(g)
        return groups
    if name == "alone_beside_bundles":
        # more features alone than dataset.VIEW_ALONE_SLICES, between the
        # bundles' members: the row selection with an index a feature
        groups, f = [], 0
        for k in range(6):
            groups.append([(f, int(rng.randint(2, 9)), 0),
                           (f + 2, int(rng.randint(2, 9)), 1)])
            groups.append([(f + 1, int(rng.randint(20, 64)), 0)])
            groups.append([(f + 3, int(rng.randint(2, 64)), 0)])
            f += 4
        return groups
    if name == "few_alone":
        return [[(0, 4, 1), (3, 7, 0)], [(1, 31, 0)], [(2, 9, 3), (4, 2, 0)],
                [(5, 12, 0)]]
    if name == "out_of_order":
        # a bundle's members follow neither feature-index order nor each
        # other
        return [[(5, 7, 2), (0, 3, 0), (3, 12, 11)], [(4, 2, 0), (1, 9, 4)],
                [(2, 30, 0)], [(7, 5, 0), (6, 5, 4)]]
    if name == "over_limit":
        # 400 members, one of 61 bins among them: a matrix of 160 slots x
        # 400 x 61 passes dataset.VIEW_SEL_MAX_BYTES and the table keeps
        # the gather
        groups, f = [], 0
        for g in range(4):
            wide = [(f, 61, 0)] if g == 0 else []
            rest = [(f + len(wide) + i, 2, 0) for i in range(100 - len(wide))]
            groups.append(wide + rest)
            f += 100
        return groups
    raise AssertionError(name)


def _gather_view(hg, total_sum, maps, num_bin_hist):
    """The view as PR 28 wrote it and every commit to PR 37 ran it: one
    (g, h, count) triple gathered an index of ``proj``, F x B of them. The
    plain reference the program's view is held to, bit for bit."""
    num_feat, num_bin = maps["proj"].shape
    bp = hg.shape[-1]
    proj = maps["proj"] // num_bin_hist * bp + maps["proj"] % num_bin_hist
    flat = jnp.moveaxis(hg, 0, -1).reshape(-1, 3)
    fh = jnp.take(flat, proj.reshape(-1), axis=0).T \
        .reshape(3, num_feat, num_bin)
    fh = fh * maps["valid"][None]
    rest = total_sum[:, None] - jnp.sum(fh, axis=2)
    dpos_oh = (jnp.arange(num_bin, dtype=jnp.int32)[None, :]
               == maps["dpos"][:, None])
    put = dpos_oh & maps["has_rest"][:, None]
    return jnp.where(put[None], rest[:, :, None], fh)


VIEW_LAYOUTS = {"expo_like": "runs", "default_first": "runs",
                "default_middle": "runs", "default_last": "runs",
                "alone_beside_bundles": "runs", "few_alone": "runs",
                "out_of_order": "runs", "over_limit": "gather"}


@pytest.mark.parametrize("mode", ["alone", "jit", "vmap_one_node",
                                  "vmap_two_nodes_global_totals",
                                  "vmap_two_nodes_local_totals"])
@pytest.mark.parametrize("layout", sorted(VIEW_LAYOUTS))
def test_bundle_view_is_the_gather_bit_for_bit(layout, mode, monkeypatch):
    """``bundle_feature_view`` in the form ``bundle_view()`` names for the
    layout against the (feature, bin) gather it replaced, ``np.array_equal``
    on the f32 planes: alone, under ``jit`` and under ``vmap`` as
    ``feat_views`` calls it (one node at the root, a pair of children; the
    totals the nodes' own, or, as the voting learner's, other ones)."""
    from lightgbm_tpu import dataset as D
    ds = _layout(_view_layouts(layout))
    view = ds.bundle_view()
    assert view.form == VIEW_LAYOUTS[layout]
    assert view.alone + view.bundled == ds.num_features
    maps = {k: jnp.asarray(v) for k, v in ds.bundle_maps().items()}
    assert ("sel" in maps, "proj" in maps) == (view.form == "runs",
                                               view.form == "gather")
    monkeypatch.setattr(D, "VIEW_SEL_MAX_BYTES", 0)
    assert ds.bundle_view().form == "gather"
    ref_maps = {k: jnp.asarray(v) for k, v in ds.bundle_maps().items()}
    monkeypatch.undo()

    g, bm = ds.num_groups, int(ds.group_num_bins().max())
    nodes = 1 if mode in ("alone", "jit", "vmap_one_node") else 2
    put = np.asarray(maps["put"])
    one_slot = np.asarray(maps["nbm1"]) == 1

    def new(h, t):
        return bundle_feature_view(h, t, maps, bm, view)

    def old(h, t):
        return _gather_view(h, t, ref_maps, bm)

    rng = np.random.RandomState(3)
    for cells in ("every_f32", "exact_sums"):
        hg = np.zeros((nodes, 3, g, hist_bins(bm)), np.float32)
        for gid, grp in enumerate(ds.groups):
            # the pad lanes past a column's bins stay zero, as the kernels'
            shape = (nodes, 3, grp.num_bins)
            if cells == "every_f32":
                # all 24 bits of the mantissa, twenty binades, both signs:
                # what the product's three bfloat16 terms must carry whole
                v = (rng.randint(1 << 23, 1 << 24, shape) * 2.0
                     ** rng.randint(-33, -13, shape)) * rng.choice((-1, 1),
                                                                   shape)
            else:
                # multiples of 1 / 64 under 1024: up to 64 of them sum
                # exactly in whatever order a backend's reduce takes them
                v = rng.randint(-(1 << 16), 1 << 16, shape) / 64.0
            hg[:, :, gid, :grp.num_bins] = v
        if mode.endswith("local_totals"):
            totals = rng.randint(-(1 << 16), 1 << 16, (nodes, 3)) / 64.0
        else:
            totals = hg[:, :, 0, :].sum(axis=-1)
        hg, totals = jnp.asarray(hg), jnp.asarray(totals, jnp.float32)
        if mode == "alone":
            got, want = new(hg[0], totals[0]), old(hg[0], totals[0])
        elif mode == "jit":
            got, want = jax.jit(new)(hg[0], totals[0]), old(hg[0], totals[0])
        else:
            got = jax.jit(jax.vmap(new))(hg, totals)
            want = jnp.stack([old(h, t) for h, t in zip(hg, totals)])
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype == np.float32
        assert got.shape == want.shape == (
            (3, ds.num_features, view.num_bin) if got.ndim == 3
            else (nodes, 3, ds.num_features, view.num_bin))
        # a default bin over SEVERAL own slots is a float32 sum whose order
        # is the backend's (XLA:CPU's fused reduce follows LLVM's
        # vectorisation, and two jits of one function differ): held where
        # no order can round, and every other cell, each a copy, always
        if cells == "every_f32":
            ordered = put & ~one_slot[:, None]
            got, want = (np.where(ordered, 0, x) for x in (got, want))
        assert np.array_equal(got, want), cells
        # and every own slot really arrived (a view of zeros equals nothing)
        assert np.count_nonzero(got) > ds.num_features
