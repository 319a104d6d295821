"""Resident permuted training state: parity with the planes/rows paths.

tpu_resident_state keeps the bin planes ONCE in original row order and
partitions only the slim route/ridx/g/h/c payload; segment histograms
gather the resident planes through the permuted row-index plane. The
contract is BIT-IDENTICAL trees to tpu_work_layout=planes (same chunking,
same f32 accumulation order, same compaction dest arithmetic). These tests
pin that contract on the CPU backend, validate the fused Pallas partition
on the slim payload under the pallas interpreter, and cover the config
gates.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.histogram import (
    hist16_segment_planes, hist16_segment_resident)

CH = 256
G = P.guard_rows(CH)


def _mk(rng, n, f=6, num_bin=32):
    bins = rng.randint(0, num_bin, (n, f)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[:, 2] = 1.0
    return jnp.asarray(bins), jnp.asarray(ghc)


def _pack_pair(bins, ghc, num_bin, guard=G, part_kernel="xla"):
    """(resident work + planes, planes work) packed from the same rows."""
    n, f = bins.shape
    npad = P.planes_npad(n, guard, part_kernel)
    res = P.resident_bin_planes(bins, guard, npad)
    _, w_rs = P.work_spec(f, False, part_kernel, CH, CH, layout="resident")
    _, w_pl = P.work_spec(f, False, part_kernel, CH, CH, layout="planes")
    work_r = jnp.zeros((2, w_rs, npad), jnp.uint8)
    work_r, root_r = P.pack_resident_fold_root(
        work_r, bins, ghc, guard, num_bins=num_bin, exact=True, chunk=CH)
    work_p = P.pack_planes_write(jnp.zeros((2, w_pl, npad), jnp.uint8),
                                 bins, ghc, guard, chunk=CH)
    root_p = hist16_segment_planes(work_p, jnp.int32(0), jnp.int32(guard),
                                   jnp.int32(n), num_bins=num_bin,
                                   num_feat=f, chunk=CH)
    return res, work_r, root_r, work_p, root_p, npad


def test_pack_resident_fold_root_matches_planes(rng):
    """Same root histogram bits as the planes path's (its segment
    histogram over the packed root segment), ridx planes encoding
    absolute positions, and the g/h/c byte planes equal to the planes
    pack's payload planes."""
    n, f, num_bin = 1000, 6, 32
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    res, work_r, root_r, work_p, root_p, npad = _pack_pair(bins, ghc, num_bin)
    assert np.array_equal(np.asarray(root_r).view(np.uint8),
                          np.asarray(root_p).view(np.uint8))
    s = slice(G, G + n)
    ridx = np.asarray(P._decode_ridx(work_r[0, P.RST_ROUTE:P.RST_GH_OFF, s],
                                     npad))
    assert np.array_equal(ridx, np.arange(G, G + n))
    assert np.array_equal(np.asarray(work_r)[0, P.RST_GH_OFF:P.RST_WIDTH, s],
                          np.asarray(work_p)[0, f:f + P.GH_BYTES, s])
    # resident planes carry the transposed bins at the guard offset
    assert np.array_equal(np.asarray(res)[:, G:G + n], np.asarray(bins).T)


def test_hist16_segment_resident_bit_identical(rng):
    n, f, num_bin = 900, 5, 32
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    res, work_r, _, work_p, _, _ = _pack_pair(bins, ghc, num_bin)
    hr = np.asarray(hist16_segment_resident(
        work_r, res, jnp.int32(0), jnp.int32(G + 57), jnp.int32(700),
        num_bins=num_bin, num_feat=f, chunk=CH))
    hp = np.asarray(hist16_segment_planes(
        work_p, jnp.int32(0), jnp.int32(G + 57), jnp.int32(700),
        num_bins=num_bin, num_feat=f, chunk=CH))
    assert np.array_equal(hr.view(np.uint8), hp.view(np.uint8))


def test_write_route_plane_gathers_split_feature(rng):
    n, f, num_bin = 777, 6, 32
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    res, work_r, _, _, _, _ = _pack_pair(bins, ghc, num_bin)
    wk = P.write_route_plane(work_r, res, jnp.int32(0), jnp.int32(G),
                             jnp.int32(n), jnp.int32(4), ch=CH)
    assert np.array_equal(np.asarray(wk)[0, 0, G:G + n],
                          np.asarray(bins)[:, 4])
    # planes 1.. and the sibling plane are untouched
    assert np.array_equal(np.asarray(wk)[0, 1:], np.asarray(work_r)[0, 1:])
    assert np.array_equal(np.asarray(wk)[1], np.asarray(work_r)[1])


@pytest.mark.parametrize("start,cnt", [(0, 1000), (137, 700), (513, 100)])
def test_partition_resident_matches_planes(rng, start, cnt):
    """The slim partition (route pre-pass + planes partition on plane 0)
    applies the SAME permutation as the planes partition on the full
    payload: gathering the bins through the moved ridx plane reproduces the
    moved bin planes, and the moved g/h/c planes match bit-for-bit."""
    n, f, num_bin = 1000, 6, 32
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    res, work_r, _, work_p, _, npad = _pack_pair(bins, ghc, num_bin)
    table = jnp.asarray(rng.rand(num_bin) < 0.45)
    feat = jnp.int32(3)
    a = (jnp.int32(0), jnp.int32(G + start), jnp.int32(cnt))
    wk = P.write_route_plane(work_r, res, *a, feat, ch=CH)
    out_r, lt_r = P.partition_segment_planes(wk, *a, jnp.int32(0), table,
                                             ch=CH)
    out_p, lt_p = P.partition_segment_planes(work_p, *a, feat, table, ch=CH)
    assert int(lt_r) == int(lt_p)
    s = slice(G + start, G + start + cnt)
    ridx = np.asarray(P._decode_ridx(out_r[1, P.RST_ROUTE:P.RST_GH_OFF, s],
                                     npad))
    got_bins = np.asarray(bins)[ridx - G].T
    assert np.array_equal(got_bins, np.asarray(out_p)[1, :f, s])
    assert np.array_equal(np.asarray(out_r)[1, P.RST_GH_OFF:P.RST_WIDTH, s],
                          np.asarray(out_p)[1, f:f + P.GH_BYTES, s])


@pytest.mark.parametrize("start,cnt,ch", [(137, 700, 256), (0, 1500, 256),
                                          (333, 1400, 512)])
def test_resident_fused_kernel_interpret(rng, start, cnt, ch, monkeypatch):
    """The fused Pallas partition streaming the slim resident payload, run
    under the pallas interpreter, must match the XLA resident path: left
    child bit-exact in order, right child the same row set, neighbors
    outside the segment untouched (same contract as the planes kernel)."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    n, f, num_bin = 1500, 20, 32
    guard = ch + 2 * P.PLANE_ALIGN
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    npad = P.planes_npad(n, guard, "pallas")
    res = P.resident_bin_planes(bins, guard, npad)
    _, w_rs = P.work_spec(f, False, "pallas", ch, ch, layout="resident")
    assert w_rs % 32 == 0
    work = jnp.zeros((2, w_rs, npad), jnp.uint8)
    work, _ = P.pack_resident_fold_root(
        work, bins, ghc, guard, num_bins=num_bin, exact=True, chunk=ch)
    sib = rng.randint(0, 256, (w_rs, npad)).astype(np.uint8)  # junk dst
    work = work.at[1].set(jnp.asarray(sib))
    table = jnp.asarray(rng.rand(num_bin) < 0.45)
    a = (jnp.int32(0), jnp.int32(guard + start), jnp.int32(cnt))
    wk = P.write_route_plane(work, res, *a, jnp.int32(7), ch=ch)
    out_x, lt_x = P.partition_segment_planes(wk, *a, jnp.int32(0), table,
                                             ch=ch)
    out_p, lt_p = P.partition_segment_planes_fused(wk, *a, jnp.int32(0),
                                                   table, ch=ch)
    out_x, out_p = np.asarray(out_x), np.asarray(out_p)
    lt = int(lt_p)
    assert lt == int(lt_x)
    s0, s1 = guard + start, guard + start + cnt
    assert np.array_equal(out_p[1, :, s0:s0 + lt], out_x[1, :, s0:s0 + lt])
    assert sorted(map(bytes, out_p[1, :, s0 + lt:s1].T)) == \
        sorted(map(bytes, out_x[1, :, s0 + lt:s1].T))
    assert np.array_equal(out_p[1, :, :s0], sib[:, :s0])
    assert np.array_equal(out_p[1, :, s1:], sib[:, s1:])


def _train_tree(layout, resident, n, f, leaves, seed=0):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": leaves, "max_bin": 31,
        "tree_builder": "partition", "tpu_part_chunk": CH,
        "tpu_hist_chunk": CH, "min_data_in_leaf": 2, "verbosity": -1,
        "tpu_work_layout": layout,
        "tpu_resident_state": "on" if resident else "off"})
    ds = construct_dataset(X, cfg, label=y)
    lrn = SerialTreeLearner(cfg, ds)
    want = "resident" if resident else layout
    assert lrn.build_kwargs()["work_layout"] == want
    ghc = jnp.stack([jnp.asarray(g), jnp.asarray(h),
                     jnp.ones(n, jnp.float32)], axis=1)
    return jax.device_get(
        lrn.train(ghc, jnp.ones(ds.num_features, bool),
                  jax.random.PRNGKey(0)))


_FIELDS = ("split_leaf", "feature", "bin", "kind", "default_left", "gain",
           "left_sum", "right_sum", "go_left", "leaf_value", "leaf_sum",
           "row_leaf")


# F=28 / F=137 cross leaves=255 / leaves=2; N deliberately NOT a multiple
# of the 256-row chunks
@pytest.mark.parametrize("n,f,leaves", [(2999, 28, 255), (1237, 137, 2),
                                        (1237, 28, 2), (1501, 137, 255)])
def test_tree_parity_resident_vs_planes(n, f, leaves):
    a = _train_tree("planes", False, n, f, leaves)
    b = _train_tree("planes", True, n, f, leaves)
    assert int(a.num_splits) == int(b.num_splits)
    for fld in _FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, fld)), np.asarray(getattr(b, fld)),
            err_msg=fld)


@pytest.mark.parametrize("n,f,leaves", [(2999, 28, 255), (1237, 28, 2)])
def test_tree_parity_resident_vs_rows(n, f, leaves):
    a = _train_tree("rows", False, n, f, leaves)
    b = _train_tree("planes", True, n, f, leaves)
    assert int(a.num_splits) == int(b.num_splits)
    for fld in _FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, fld)), np.asarray(getattr(b, fld)),
            err_msg=fld)


def test_resident_carried_work_buf_parity(rng):
    """A resident work buffer carried from a previous tree (fused-block
    contract) must grow the same tree as a fresh zero buffer, with the
    resident planes hoisted once outside the build like fused.py does."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    n, f = 1201, 6
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 8, "max_bin": 31,
        "tree_builder": "partition", "tpu_part_chunk": CH,
        "tpu_hist_chunk": CH, "min_data_in_leaf": 5, "verbosity": -1,
        "tpu_work_layout": "planes", "tpu_resident_state": "on"})
    ds = construct_dataset(X, cfg, label=y)
    lrn = SerialTreeLearner(cfg, ds)
    rspec = lrn.resident_spec()
    assert rspec is not None
    bins_res = ds.device_resident_planes(*rspec)

    def mk_ghc():
        return jnp.stack(
            [jnp.asarray(rng.randn(n).astype(np.float32)),
             jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1),
             jnp.ones(n, jnp.float32)], axis=1)

    build = lrn.make_build_fn()
    key = jax.random.PRNGKey(0)
    used = jnp.zeros((ds.num_features,), bool)
    fmask = jnp.ones(ds.num_features, bool)
    ghc1, ghc2 = mk_ghc(), mk_ghc()
    _, carried = build(lrn.bins, ghc1, lrn.meta, fmask, key, used,
                       return_work=True, bins_res=bins_res)
    log_a = build(lrn.bins, ghc2, lrn.meta, fmask, key, used,
                  bins_res=bins_res)
    log_b, _ = build(lrn.bins, ghc2, lrn.meta, fmask, key, used,
                     work_buf=carried, return_work=True, bins_res=bins_res)
    # and the in-graph derivation (bins_res=None) matches the hoisted copy
    log_c = build(lrn.bins, ghc2, lrn.meta, fmask, key, used)
    for fld in ("num_splits", "feature", "bin", "gain", "leaf_value",
                "row_leaf"):
        np.testing.assert_array_equal(
            np.asarray(getattr(log_a, fld)), np.asarray(getattr(log_b, fld)),
            err_msg=fld)
        np.testing.assert_array_equal(
            np.asarray(getattr(log_a, fld)), np.asarray(getattr(log_c, fld)),
            err_msg=fld)


def test_config_rejects_bad_resident_state():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError, match="tpu_resident_state"):
        Config.from_params({"tpu_resident_state": "maybe"})


def _mini_ds(rng, params):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset

    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    base = {"objective": "binary", "num_leaves": 4, "max_bin": 15,
            "tree_builder": "partition", "verbosity": -1,
            "min_data_in_leaf": 2}
    base.update(params)
    cfg = Config.from_params(base)
    return cfg, construct_dataset(X, cfg, label=y)


def test_resident_on_rejects_rows_layout(rng):
    from lightgbm_tpu.learner import SerialTreeLearner
    from lightgbm_tpu.utils.log import LightGBMError

    cfg, ds = _mini_ds(rng, {"tpu_resident_state": "on",
                             "tpu_work_layout": "rows"})
    with pytest.raises(LightGBMError, match="planes work layout"):
        SerialTreeLearner(cfg, ds)


def test_resident_on_rejects_int8(rng):
    from lightgbm_tpu.learner import SerialTreeLearner
    from lightgbm_tpu.utils.log import LightGBMError

    cfg, ds = _mini_ds(rng, {"tpu_resident_state": "on",
                             "use_quantized_grad": True})
    with pytest.raises(LightGBMError, match="int8"):
        SerialTreeLearner(cfg, ds)


def test_resident_auto_stays_planes(rng, monkeypatch):
    """auto never turns resident on — not on a CPU mesh and, since the
    first v5e timing (PERF.md, PR 21: the gathered histogram is 1.8-3.6x
    slower per iteration than plain planes), not on a TPU either; ``on``
    still forces it."""
    from lightgbm_tpu import runtime
    from lightgbm_tpu.learner import SerialTreeLearner

    cfg, ds = _mini_ds(rng, {"tpu_resident_state": "auto",
                             "tpu_work_layout": "planes"})
    kw = SerialTreeLearner(cfg, ds).build_kwargs()
    assert kw["work_layout"] == "planes"
    with monkeypatch.context() as m:
        m.setattr(runtime, "on_tpu", lambda: True)
        cfg, ds = _mini_ds(rng, {})          # every knob auto, as on a TPU
        kw = SerialTreeLearner(cfg, ds).build_kwargs()
        assert (kw["work_layout"], kw["part_kernel"]) == ("planes", "pallas")
    cfg, ds = _mini_ds(rng, {"tpu_resident_state": "on",
                             "tpu_work_layout": "planes"})
    lrn = SerialTreeLearner(cfg, ds)
    assert lrn.build_kwargs()["work_layout"] == "resident"
    # forcing resident with the pallas hist kernel falls back to the XLA
    # gather (no resident gather path in the kernel)
    cfg, ds = _mini_ds(rng, {"tpu_resident_state": "on",
                             "tpu_work_layout": "planes",
                             "tpu_partition_kernel": "pallas",
                             "tpu_hist_kernel": "pallas",
                             "tpu_part_chunk": 256, "tpu_hist_chunk": 256})
    kw = SerialTreeLearner(cfg, ds).build_kwargs()
    assert kw["work_layout"] == "resident"
    assert kw["hist_kernel"] == "xla"


def test_device_resident_planes_version_token(rng):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset

    X = rng.randn(64, 3)
    cfg = Config.from_params({"max_bin": 15, "verbosity": -1,
                              "min_data_in_leaf": 1, "min_data_in_bin": 1})
    ds = construct_dataset(X, cfg, label=(X[:, 0] > 0).astype(np.float64))
    cached = ds.device_resident_planes(256, 576)
    assert ds.device_resident_planes(256, 576) is cached   # cache hit
    other = ds.device_resident_planes(128, 576)            # new geometry
    assert other is not cached
    assert cached.shape == (3, 576) and cached.dtype == jnp.uint8
    old = int(ds.binned[0, 0])
    ds.binned[0, 0] = old ^ 1                 # in-place host write
    ds.bump_version()
    fresh = ds.device_resident_planes(128, 576)
    assert fresh is not other                 # token invalidated the entry
    assert int(np.asarray(fresh)[0, 128]) == old ^ 1


def test_traffic_spec_resident_halves_partition_bytes(rng):
    """Acceptance: the resident partition moves >= 2x less data per split
    than the planes path at the HIGGS shape (F=28)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    X = rng.randn(400, 28)
    y = (X[:, 0] > 0).astype(np.float64)

    def spec(rs):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 4, "max_bin": 15,
            "tree_builder": "partition", "verbosity": -1,
            "min_data_in_leaf": 2, "tpu_work_layout": "planes",
            "tpu_resident_state": rs})
        ds = construct_dataset(X, cfg, label=y)
        return SerialTreeLearner(cfg, ds).traffic_spec()

    planes, res = spec("off"), spec("on")
    assert planes["work_layout"] == "planes"
    assert res["work_layout"] == "resident"
    assert planes["partition_bytes_per_row"] >= \
        2 * res["partition_bytes_per_row"]


def test_bench_phases_traffic_merge():
    """The optional traffic dict merges into the breakdown without touching
    the wall-accounting fields (accounted_pct stays a pure self-check)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import _phases

    grown = {"fused/block_fn": 0.5, "fused/dispatch": 0.3,
             "fused/logs_transfer": 0.15, "fused/host_trees": 0.05}

    base = _phases(grown, 1.0)
    traffic = {"work_layout": "resident", "partition_bytes_per_row": 40,
               "hist_bytes_per_row": 23}
    got = _phases(grown, 1.0, traffic)
    assert got["accounted_pct"] == base["accounted_pct"]
    assert got["other"] == base["other"]
    assert got["work_layout"] == "resident"
    assert got["partition_bytes_per_row_split"] == 40
    assert got["hist_gather_bytes_per_row"] == 23
    assert _phases(grown, 1.0, None) == base
