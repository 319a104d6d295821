"""CPU tests of what the ``expo.train`` cell added to the harness: the one-hot
CSR generator, the plain EFB reference, the five checks. By hand, like the
others:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest
import scipy.sparse as sp

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

from datagen import onehot_csr  # noqa: E402
from reference import binary_root, efb, model_text  # noqa: E402

CFG = json.load(open(os.path.join(BENCH, "configs", "expo-binary-255.json")))


def _check(kind):
    spec = importlib.util.spec_from_file_location(
        "bench_checks_" + kind, os.path.join(BENCH, "checks", kind + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _make(rows, seed):
    return onehot_csr.make(dict(CFG["shape"], rows=rows), CFG["datagen"]["args"], seed)


def test_table_is_the_seeds_and_its_shape_is_the_configurations():
    a, b, c = (_make(30000, s) for s in (1, 1, 3000000019))
    X = a["X"]
    assert sp.issparse(X) and X.format == "csr" and X.dtype == np.float32
    assert X.shape == (30000, CFG["shape"]["source_features"]) == (30000, 700)
    assert X.has_sorted_indices and np.all(np.diff(X.indptr) == CFG["shape"]["nonzeros_per_row"])
    assert np.all(np.diff(X.indices.reshape(-1, 8), axis=1) > 0) and np.all(X.data > 0)
    assert (X != b["X"]).nnz == 0 and np.array_equal(a["label"], b["label"])
    assert (X != c["X"]).nnz > 0 and a["group"] is None
    # another seed is another sample of the same problem: other rows, other
    # noise, other labels (as linear_score.py), the same levels and weights
    assert not np.array_equal(X.indices, c["X"].indices)
    assert not np.array_equal(a["label"], c["label"])
    va, vc = X.data.reshape(-1, 8), c["X"].data.reshape(-1, 8)
    assert np.all(va[:, :6] == 1.0) and np.all(vc[:, :6] == 1.0)
    for j in (6, 7):
        assert np.array_equal(np.unique(va[:, j]), np.unique(vc[:, j]))
    assert len(np.unique(va[:, 6])) <= 240 and len(np.unique(va[:, 7])) <= 200
    assert abs(a["label"].mean() - 0.2) < 0.01 and set(np.unique(a["label"])) == {0.0, 1.0}
    # one stored 1 in each of the six one-hot blocks, and both numeric columns
    starts, first_numeric, width = onehot_csr.layout(CFG["datagen"]["args"])
    assert (list(starts), first_numeric, width) == ([0, 12, 43, 50, 72, 385], 698, 700)
    cols = X.indices.reshape(-1, 8)
    for j, (lo, hi) in enumerate(zip(starts, list(starts[1:]) + [698])):
        assert np.all((cols[:, j] >= lo) & (cols[:, j] < hi))
    assert np.all(cols[:, 6:] == [698, 699])
    # the frequencies are the configuration's: hottest airport 6.3 %, hottest
    # carrier 17.5 %, every one-hot column zero in over 80 % of the rows
    share = np.bincount(X.indices, minlength=700)[:698] / 30000.0
    assert share.max() < 0.2 and abs(share[72] - 0.063) < 0.01 and abs(share[50] - 0.175) < 0.015
    # the problem is the configuration's too, not the seed's
    assert np.array_equal(onehot_csr.score_weights(CFG["datagen"]["args"]),
                          onehot_csr.score_weights(dict(CFG["datagen"]["args"])))


def test_a_categorys_weight_follows_its_frequency_to_weights_power():
    """Power 1 (the configuration's): a draw times the category's frequency
    over its column's hottest, so the uniform calendar columns and every
    hottest category keep their draws and the rarest airport keeps 1.8 %."""
    args = CFG["datagen"]["args"]
    assert args["weights_power"] == 1.0
    flat = onehot_csr.score_weights(dict(args, weights_power=0.0))
    w = onehot_csr.score_weights(args)
    starts, first_numeric, _ = onehot_csr.layout(args)
    assert w.dtype == np.float32 and w.shape == (first_numeric,) == (698,)
    assert np.array_equal(w[:50], flat[:50])                          # month, day, weekday
    assert np.array_equal(w[starts[3:]], flat[starts[3:]])            # hottest of each
    for start, c in zip(starts[3:], args["columns"][3:]):
        share = np.arange(1, c["categories"] + 1) ** -c["zipf"]       # frequency / hottest
        np.testing.assert_allclose(w[start:start + len(share)],
                                   flat[start:start + len(share)] * share, rtol=1e-6)
    assert abs(w[384] / flat[384] - 313 ** -0.7) < 1e-6 and 313 ** -0.7 < 0.018


def _map(column, bounds, default_bin, group, offset, group_bins):
    num_bins = np.array([len(b) for b in bounds])
    group = np.asarray(group)
    return efb.GroupMap(column=np.asarray(column), bounds=[np.asarray(b, float) for b in bounds],
                        default_bin=np.asarray(default_bin), num_bins=num_bins, group=group,
                        offset=np.asarray(offset),
                        multi=np.bincount(group)[group] > 1, group_bins=np.asarray(group_bins))


def test_bundle_rule_worked_by_hand():
    # columns 0, 1 (one-hot) and 3 (three bins, default 0) share device column
    # 0 with slots 1 | 2 | 3, 4; column 2 (numeric, three bins) is alone
    inf = np.inf
    gm = _map(column=[0, 1, 2, 3], bounds=[[0.5, inf], [0.5, inf], [1.0, 2.0, inf], [0.5, 1.5, inf]],
              default_bin=[0, 0, 0, 0], group=[0, 0, 1, 0], offset=[1, 2, 0, 3], group_bins=[5, 3])
    X = sp.csr_matrix(np.array([[1, 0, 0.5, 0],      # slot 1
                                [0, 1, 1.5, 0],      # slot 2
                                [0, 0, 2.5, 1],      # column 3 bin 1 -> slot 3
                                [0, 0, 0.0, 2],      # column 3 bin 2 -> slot 4
                                [1, 1, 0.0, 0],      # conflict: keeps the later (slot 2)
                                [1, 0, 0.0, 2],      # conflict: keeps slot 4
                                [0, 0, 0.0, 0]], np.float32))
    bundled, conflicts = efb.bundle(efb.csc_of(X), gm)
    assert bundled.dtype == np.uint8
    assert bundled.tolist() == [[1, 0], [2, 1], [3, 2], [4, 0], [2, 0], [4, 0], [0, 0]]
    assert conflicts.tolist() == [4, 5]
    # FixHistogram: own slots copied, default bin = total - own slots
    ghc = np.stack([np.arange(7.0), np.ones(7), np.ones(7)], axis=1)
    hist = np.zeros((2, 5, 3))
    for g in range(2):
        for ch in range(3):
            hist[g, :, ch] = np.bincount(bundled[:, g], weights=ghc[:, ch], minlength=5)
    fh = efb.feature_histograms(hist, ghc.sum(axis=0), gm)
    assert fh[0, :2, 2].tolist() == [6, 1]           # row 0 only: rows 4, 5 lost column 0
    assert fh[1, :2, 2].tolist() == [5, 2] and fh[3, :3, 2].tolist() == [4, 1, 2]
    assert fh[2, :3, 2].tolist() == [5, 1, 1]
    assert fh[3, :3, 0].tolist() == [0 + 1 + 4 + 6, 2, 3 + 5]
    # the raw columns count a conflict row under BOTH of its sub-features
    raw = efb.raw_feature_histograms(efb.csc_of(X), gm, ghc)
    assert raw[0, :2, 2].tolist() == [4, 3] and raw[1, :2, 2].tolist() == [5, 2]


@pytest.fixture(scope="module")
def job():
    """A small bundled job, trained for real on the CPU."""
    import lightgbm_tpu as lgb
    data = _make(40000, 7)
    params = dict(CFG["params"], num_leaves=15)
    ds = lgb.Dataset(data["X"], label=data["label"], params=params)
    binned = ds.construct()
    bst = lgb.train(dict(params), ds, num_boost_round=3)
    header, trees = model_text.parse(bst.model_to_string())
    return {"params": params, "rows": 40000, "X": data["X"], "label": data["label"],
            "group": None, "booster": bst, "binned": binned, "header": header, "trees": trees}


def test_reference_agrees_with_the_dense_references_on_a_trained_model(job):
    gm = efb.group_map_of(job["binned"])
    Xc = efb.csc_of(job["X"])
    dense = np.ascontiguousarray(job["X"].toarray(), dtype=np.float32)
    bundled, conflicts = efb.bundle(Xc, gm)
    assert len(conflicts) == 0 and np.array_equal(bundled, job["binned"].binned)
    for t in job["trees"]:
        leaf = model_text.route(t, dense)
        assert np.array_equal(efb.walk_raw(t, Xc), leaf)
        assert np.array_equal(efb.walk_bundled(t, bundled, gm), leaf)
    assert np.array_equal(efb.raw_score(job["header"], job["trees"], Xc),
                          model_text.raw_score(job["header"], job["trees"], dense))
    # the O(nnz) root split is the dense reference's over the used columns
    bounds = [np.asarray(m.upper_bounds, np.float64) for m in job["binned"].bin_mappers]
    used = dense[:, gm.column]
    f, b, gain = binary_root.root_split(used, job["label"], bounds, 0, 100)
    assert efb.root_split(Xc, job["label"], gm, 0, 100) == pytest.approx((f, b, gain), rel=1e-9)


def test_checks_pass_on_a_sound_job_and_fail_on_a_tampered_one(job):
    census = {"kind": "bundle_census", "columns": job["binned"].binned.shape[1],
              "used_features": 700, "column_bins": 256, "feature_bins": 240}
    sound = [(census, "bundle_census"),
             ({"share_max": 1e-4}, "efb_conflicts"),
             ({"trees": ["first", "last"]}, "routed_counts_bundled"),
             ({"rows": 4096, "tol": 1e-5, "edge_rows_max": 64}, "predict_sparse"),
             ({"gain_rtol": 1e-4}, "root_split_binary_sparse")]
    for args, kind in sound:
        ok, detail = _check(kind).run(args, job)
        assert ok, (kind, detail)
    # another column count than the configuration's
    ok, detail = _check("bundle_census").run(dict(census, columns=census["columns"] + 1), job)
    assert not ok and "configured" in detail

    def tampered(**changes):
        fake = types.SimpleNamespace(**{k: getattr(job["binned"], k) for k in (
            "binned", "bin_mappers", "used_feature_indices", "feature_to_group",
            "feature_group_offset", "groups", "efb_conflict_rows")})
        for k, v in changes.items():
            setattr(fake, k, v)
        return dict(job, binned=fake)
    # a program that counts another number of conflict rows than the reference
    ok, detail = _check("efb_conflicts").run({"share_max": 1e-4}, tampered(efb_conflict_rows=3))
    assert not ok and "program 3" in detail
    # a program from before the count existed is judged on the reference's alone
    old = tampered()
    del old["binned"].efb_conflict_rows
    ok, detail = _check("efb_conflicts").run({"share_max": 1e-4}, old)
    assert ok and "program None" in detail
    # one byte of the bundled matrix binned another way
    other = job["binned"].binned.copy()
    other[17, 0] ^= 1
    ok, detail = _check("efb_conflicts").run({"share_max": 1e-4}, tampered(binned=other))
    assert not ok and "False" in detail
    # a leaf count that no walk gives
    trees = [dict(t) for t in job["trees"]]
    trees[-1]["leaf_count"] = trees[-1]["leaf_count"].copy()
    trees[-1]["leaf_count"][:2] += [1, -1]
    ok, detail = _check("routed_counts_bundled").run({"trees": ["first", "last"]},
                                                     dict(job, trees=trees))
    assert not ok and "bundled matrix 1" in detail


def test_conflict_share_over_the_ceiling_fails_the_check():
    import lightgbm_tpu as lgb
    data = _make(20000, 5)
    X = data["X"].tolil(copy=True)
    for r in range(0, 20000, 100):                   # 200 rows: 1e-2 of the table
        own = [c for c in X.rows[r] if 72 <= c < 385][0]
        X[r, 72 + (own - 72 + 1) % 20] = 1.0
    X = sp.csr_matrix(X, dtype=np.float32)
    X.sort_indices()
    # bundles chosen on 2,000 sampled rows; the planted rows that fall among
    # them make their pairs of columns conflict there, the others do not
    ds = lgb.Dataset(X, label=data["label"],
                     params=dict(CFG["params"], bin_construct_sample_cnt=2000))
    binned = ds.construct()
    c = {"binned": binned, "X": X, "rows": 20000}
    ok, detail = _check("efb_conflicts").run({"share_max": 1e-4}, c)
    assert binned.efb_conflict_rows > 2 and not ok and "== reference's: True" in detail
    assert "reference %d, program %d" % (binned.efb_conflict_rows, binned.efb_conflict_rows) in detail
    ok, _ = _check("efb_conflicts").run({"share_max": 1.0}, c)
    assert ok


def test_expo_rehearsal_walks_every_phase_on_the_cpu_and_exits_4():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "expo.train",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse",
         '{"rows": 120000, "params": {"num_leaves": 7}}'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 4, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    line = json.loads(last[len("REHEARSAL "):])
    assert line["failed"] == 0, p.stdout[-3000:]
    checks = [json.loads(l[6:]) for l in p.stdout.splitlines() if l.startswith("CHECK ")]
    assert [c["check"] for c in checks] == [c["kind"] for c in CFG["checks"]]
    # the AUC floor is the real size's (255 leaves): 7 leaves stay under it
    assert all(c["ok"] for c in checks if c["check"] != "quality_floor"), p.stdout[-3000:]
