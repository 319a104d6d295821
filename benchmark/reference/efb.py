"""Plain reference for exclusive feature bundling (EFB) over a sparse table:
numpy + scipy only, imports nothing from the program.

It takes the program's GROUP MAP as data of the same standing as the bin
bounds ``binary_root`` takes: for each used feature its source column, its
bin upper bounds, its default bin (the bin of the value 0), the device column
(group) it lives in and its bin offset there. Which features share a column
is the data layer's decision (greedy, on a sample of the rows); what the
learner, the kernels and the predictor then have to do with it is fixed, and
that is what this file computes in its own way:

(a) ``bundle``: CSC columns + group map -> the bundled uint8 matrix and the
    rows in which two sub-features of one bundle are both set (conflicts);
(b) ``feature_histograms``: bundled histogram -> per-feature histograms, the
    shared default bin recovered as total - own slots;
(c) ``root_split``: the first split of a binary log-loss job over every used
    feature in O(nnz), from the raw CSC columns;
(d) ``walk_raw`` / ``walk_bundled``: a finished tree walked over the raw CSC
    columns, or over a bundled matrix, without a dense rows x columns table.

The coding of a bundle (LightGBM ``include/LightGBM/feature_group.h``): bin 0
of the column means "every sub-feature at its default bin"; sub-feature j owns
the slots ``[offset_j, offset_j + num_bins_j - 1)``, its non-default bins in
order with the default one taken out. A single-feature column holds the
feature's bins as they are.

Departures from the reference implementation, each on purpose:
- ``FindGroups`` (``src/io/dataset.cpp``) allows ``total_sample_cnt / 10000``
  conflicting sample rows in a bundle (it removed ``max_conflict_rate`` in
  v3 and fixed the budget); the program allows ``max_conflict_rate`` x sample
  rows and the configuration sets it to 0: no conflict on the sample. Both
  count on the sample only, so the full table may hold conflicts; here they
  are counted on every row.
- On a conflicting row LightGBM's dense bin keeps whichever sub-feature was
  pushed last (the higher column index within the row). The program keeps the
  sub-feature placed LATER in the bundle; slots grow with placement, so that
  is the LARGEST bundle bin of the row, which is how it is computed here
  (``np.maximum``), in no particular write order.
- ``FixHistogram`` (``include/LightGBM/dataset.h``) recovers the MOST FREQUENT
  bin of a sub-feature; bundling candidates here are sparse (the value 0 in at
  least 80 % of the sample), so that bin is the default bin, and the default
  bin is what the program's map names.
- Only numerical features without missing values are bundled or walked here
  (the one-hot tables this stands for have neither categories nor NaN); other
  maps are refused.
"""
from collections import namedtuple
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8

GroupMap = namedtuple("GroupMap", "column bounds default_bin num_bins group offset "
                                  "multi group_bins")


def group_map_of(binned):
    """The program's group map as plain arrays. ``binned`` is the constructed
    dataset, read by attribute (``bin_mappers``, ``used_feature_indices``,
    ``feature_to_group``, ``feature_group_offset``, ``groups``)."""
    mappers = binned.bin_mappers
    for m in mappers:
        if m.bin_type != 0 or m.missing_type != 0:
            raise ValueError("reference handles numerical features without "
                             "missing-value bins only")
    group = np.asarray(binned.feature_to_group, np.int64)
    sizes = np.bincount(group, minlength=len(binned.groups))
    return GroupMap(
        column=np.asarray(binned.used_feature_indices, np.int64),
        bounds=[np.asarray(m.upper_bounds, np.float64) for m in mappers],
        default_bin=np.array([m.default_bin for m in mappers], np.int64),
        num_bins=np.array([m.num_bins for m in mappers], np.int64),
        group=group,
        offset=np.asarray(binned.feature_group_offset, np.int64),
        multi=sizes[group] > 1,
        group_bins=np.array([g.num_bins for g in binned.groups], np.int64))


_CSC = {}


def csc_of(X):
    """``X`` by columns, rows sorted within a column; the last one is kept,
    since several checks of one run walk the same table."""
    if _CSC.get("id") != id(X):
        Xc = X.tocsc()
        Xc.sort_indices()
        _CSC.clear()
        _CSC.update(id=id(X), X=X, csc=Xc)
    return _CSC["csc"]


def bundle_of(X, gm):
    """``bundle`` of ``csc_of(X)``, kept beside it for the same reason."""
    Xc = csc_of(X)
    if "bundle" not in _CSC:
        _CSC["bundle"] = bundle(Xc, gm)
    return _CSC["bundle"]


def _column(Xc, col):
    a, b = Xc.indptr[col], Xc.indptr[col + 1]
    return Xc.indices[a:b], Xc.data[a:b]


def _bins(gm, j, values):
    """Bin of each value of feature j: the first upper bound at or above it."""
    b = np.searchsorted(gm.bounds[j], np.asarray(values, np.float64), side="left")
    return np.minimum(b, gm.num_bins[j] - 1)


def _zero_bin(gm, j):
    """Bin of the value 0: what a row that stores nothing in the column holds."""
    return _bins(gm, j, np.zeros(1))[0]


def _slot(gm, j, bins):
    """Bundle bin of a NON-default bin of sub-feature j."""
    return gm.offset[j] + bins - (bins > gm.default_bin[j])


def bundle(Xc, gm):
    """-> (bundled uint8 matrix (rows, groups), sorted row numbers of the
    conflict rows). A conflict row is a row in which two or more sub-features
    of one bundle are away from their default bins; it keeps the largest of
    their bundle bins. Stored zeros are binned like absent ones."""
    n = Xc.shape[0]
    if gm.group_bins.max() > 256:
        raise ValueError("a bundle wider than 256 bins does not fit uint8")
    out = np.zeros((n, len(gm.group_bins)), np.uint8)
    conflicts = []
    for g in range(len(gm.group_bins)):
        members = np.flatnonzero(gm.group == g)
        if len(members) == 1:
            j = members[0]
            rows, vals = _column(Xc, gm.column[j])
            out[:, g] = _zero_bin(gm, j)
            out[rows, g] = _bins(gm, j, vals)
            continue
        rows_all, slots_all = [], []
        for j in members:
            rows, vals = _column(Xc, gm.column[j])
            b = _bins(gm, j, vals)
            away = b != gm.default_bin[j]
            rows_all.append(rows[away])
            slots_all.append(_slot(gm, j, b[away]))
        rows_all, slots_all = np.concatenate(rows_all), np.concatenate(slots_all)
        held = np.bincount(rows_all, minlength=n)   # sub-features set, per row
        out[rows_all, g] = slots_all          # any order: right wherever held == 1
        clash = held[rows_all] > 1
        if clash.any():
            top = np.zeros(n, np.int64)
            np.maximum.at(top, rows_all[clash], slots_all[clash])
            rows_c = np.flatnonzero(held > 1)
            out[rows_c, g] = top[rows_c]
            conflicts.append(rows_c)
    rows_c = np.unique(np.concatenate(conflicts)) if conflicts else np.zeros(0, np.int64)
    return out, rows_c


def feature_histograms(hist, total, gm):
    """Bundled histogram ``hist`` (groups, bins, channels) -> per-feature
    histograms (features, max feature bins, channels). A sub-feature's own
    slots are copied; its default bin is ``total`` (channels,) less their sum
    (FixHistogram). A single-feature column is copied as it is."""
    hist = np.asarray(hist, np.float64)
    out = np.zeros((len(gm.column), int(gm.num_bins.max()), hist.shape[2]))
    for j in range(len(gm.column)):
        nb, g, d = gm.num_bins[j], gm.group[j], gm.default_bin[j]
        if not gm.multi[j]:
            out[j, :nb] = hist[g, :nb]
            continue
        own = np.array([b for b in range(nb) if b != d], np.int64)
        out[j, own] = hist[g, _slot(gm, j, own)]
        out[j, d] = np.asarray(total, np.float64) - out[j, own].sum(axis=0)
    return out


def raw_feature_histograms(Xc, gm, channels):
    """Per-feature histograms taken straight from the raw columns: ``channels``
    is (rows, C) float64; a feature's zero bin gets what its stored entries
    leave. -> (features, max feature bins, C)."""
    channels = np.asarray(channels, np.float64)
    total = channels.sum(axis=0)
    out = np.zeros((len(gm.column), int(gm.num_bins.max()), channels.shape[1]))
    for j in range(len(gm.column)):
        rows, vals = _column(Xc, gm.column[j])
        b = _bins(gm, j, vals)
        for c in range(channels.shape[1]):
            out[j, :gm.num_bins[j], c] = np.bincount(
                b, weights=channels[rows, c], minlength=gm.num_bins[j])
        zero = _zero_bin(gm, j)
        out[j, zero] += total - out[j].sum(axis=0)
    return out


def root_split(Xc, label, gm, min_data_in_leaf=20, min_sum_hessian=1e-3):
    """-> (feature, bin, gain): the best ``bin <= t`` split of all rows of a
    binary log-loss job at its initial score, over the RAW columns (so a
    conflict row counts under both of its sub-features, which the bundled
    matrix cannot; the two agree where there is no conflict). O(nnz): a
    column's zero bin gets the rows it does not store."""
    n = len(label)
    y = np.asarray(label, np.float64)
    Y = float(y.sum())
    p = Y / n                                      # boost_from_average
    hess = p * (1.0 - p)                           # the same for every row
    G, H = p * n - Y, hess * n

    def best_of(j):
        rows, vals = _column(Xc, gm.column[j])
        nb = int(gm.num_bins[j])
        b = _bins(gm, j, vals)
        cnt = np.bincount(b, minlength=nb).astype(np.float64)
        ysum = np.bincount(b, weights=y[rows], minlength=nb)
        zero = _zero_bin(gm, j)
        cnt[zero] += n - len(rows)
        ysum[zero] += Y - ysum.sum()
        g = p * cnt - ysum                         # sum of (p - y)
        cl, gl = np.cumsum(cnt)[:-1], np.cumsum(g)[:-1]
        hl = cl * hess
        cr, gr, hr = n - cl, G - gl, H - hl
        ok = ((cl >= min_data_in_leaf) & (cr >= min_data_in_leaf)
              & (hl >= min_sum_hessian) & (hr >= min_sum_hessian))
        if not ok.any():
            return -np.inf, -j, 0
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = np.where(ok, gl * gl / hl + gr * gr / hr - G * G / H, -np.inf)
        t = int(np.argmax(gain))
        return float(gain[t]), -j, t

    with ThreadPoolExecutor(THREADS) as pool:
        gain, neg_j, t = max(pool.map(best_of, range(len(gm.column))))
    return -neg_j, t, gain


def _walk(tree, n, split):
    """Leaf of each of ``n`` rows. ``split(s, rows)`` parts the (sorted) rows
    that reached split ``s`` into those that go left and those that go right;
    a child is split after its parent (the model text numbers internal nodes
    in the order they were made)."""
    leaf = np.zeros(n, np.int64)
    if tree["num_leaves"] <= 1:
        return leaf
    at = {0: np.arange(n, dtype=np.int32 if n < 2 ** 31 else np.int64)}
    for s in range(tree["num_leaves"] - 1):
        for child, part in zip((tree["left_child"][s], tree["right_child"][s]),
                               split(s, at.pop(s))):
            if child >= 0:
                at[int(child)] = part
            else:
                leaf[part] = ~child
    return leaf


def walk_raw(tree, Xc):
    """Leaf of each row over the raw CSC columns: ``x <= threshold`` goes
    left, in float64, an absent entry being 0. ``split_feature`` numbers the
    source columns, as the model text does. A sparse column costs what it
    stores (or the node, if that is smaller) plus one copy of the node: the
    rows that leave the zero side are found by searching the shorter of the
    two sorted row lists in the longer."""
    n = Xc.shape[0]

    def split(s, rows):
        nz_rows, vals = _column(Xc, int(tree["split_feature"][s]))
        thr = np.float64(tree["threshold"][s])
        if len(nz_rows) == n:                      # a full column
            left = vals[rows] <= thr
            return rows[left], rows[~left]
        zero_left = bool(0.0 <= thr)
        if len(nz_rows) <= len(rows):
            pos = np.searchsorted(rows, nz_rows)
            ok = pos < len(rows)
            ok[ok] = rows[pos[ok]] == nz_rows[ok]
            at, v = pos[ok], vals[ok]
        else:
            pos = np.searchsorted(nz_rows, rows)
            ok = pos < len(nz_rows)
            ok[ok] = nz_rows[pos[ok]] == rows[ok]
            at, v = np.flatnonzero(ok), vals[pos[ok]]
        away = at[(v <= thr) != zero_left]         # stored, and off the zero side
        moved, stay = rows[away], np.delete(rows, away)
        return (stay, moved) if zero_left else (moved, stay)
    return _walk(tree, n, split)


def walk_bundled(tree, bundled, gm):
    """Leaf of each row over a bundled matrix: a row's bundle bin is turned
    back into the split feature's bin (its default bin unless the row holds
    one of the feature's own slots) and compared with the bin of the
    threshold, through a table over the 256 bundle bins made for each split.
    This is the table the program trained on, conflicts and all."""
    feature_of_column = {int(c): j for j, c in enumerate(gm.column)}
    columns = {}
    slots = np.arange(256, dtype=np.int64)

    def split(s, rows):
        j = feature_of_column[int(tree["split_feature"][s])]
        t = int(np.searchsorted(gm.bounds[j][:-1], float(tree["threshold"][s]), side="left"))
        g = int(gm.group[j])
        if g not in columns:
            columns[g] = np.ascontiguousarray(bundled[:, g])
        bins = slots
        if gm.multi[j]:
            rank = slots - gm.offset[j]
            own = (rank >= 0) & (rank < gm.num_bins[j] - 1)
            bins = np.where(own, rank + (rank >= gm.default_bin[j]), gm.default_bin[j])
        left = (bins <= t)[columns[g][rows]]
        return rows[left], rows[~left]
    return _walk(tree, bundled.shape[0], split)


def leaf_counts(leaf, tree):
    return np.bincount(leaf, minlength=tree["num_leaves"])


def raw_score(header, trees, Xc):
    """Sum of leaf values in float64 over the raw columns."""
    out = np.full(Xc.shape[0], float(header.get("init_score", 0.0)))
    for t in trees:
        out += t["leaf_value"][walk_raw(t, Xc)]
    return out
