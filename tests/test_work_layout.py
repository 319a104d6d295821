"""Transposed (W, N) work-plane layout: parity with the row-major path.

The planes layout (ops/partition.py pack_planes, tpu_work_layout=planes)
must grow BIT-IDENTICAL trees to the rows layout: identical chunk
boundaries, identical compaction dest arithmetic (stable row order) and
identical f32 accumulation order in the histogram einsums. These tests pin
that contract on the CPU backend, and validate the fused planes Pallas
kernel under the pallas interpreter (the kernel reads dst-plane state
through the aliased output ref, which makes interpret runs byte-faithful
to device runs).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.histogram import (
    _hist16_chunk, _hist16_chunk_planes, hist16_segment,
    hist16_segment_planes, hist_pallas_segment)

CH = 256
G = P.guard_rows(CH)


def _mk(rng, n, f=6, num_bin=32, guard=G):
    npad = n + 2 * guard
    bins = np.zeros((npad, f), np.uint8)
    bins[guard:guard + n] = rng.randint(0, num_bin, (n, f))
    ghc = np.zeros((npad, 3), np.float32)
    ghc[guard:guard + n] = rng.randn(n, 3)
    ghc[guard:guard + n, 2] = 1.0
    return bins, ghc


def _pair(bins, ghc):
    """(rows work pair, planes work pair) from the same padded source."""
    w_r = np.asarray(P.pack_rows(jnp.asarray(bins), jnp.asarray(ghc)))
    w_p = np.asarray(P.pack_planes(jnp.asarray(bins), jnp.asarray(ghc)))
    work_r = jnp.stack([jnp.asarray(w_r), jnp.zeros_like(jnp.asarray(w_r))])
    work_p = jnp.stack([jnp.asarray(w_p), jnp.zeros_like(jnp.asarray(w_p))])
    return w_r, work_r, work_p


def test_pack_planes_is_transposed_pack_rows(rng):
    bins, ghc = _mk(rng, 777)
    w_r = np.asarray(P.pack_rows(jnp.asarray(bins), jnp.asarray(ghc)))
    w_p = np.asarray(P.pack_planes(jnp.asarray(bins), jnp.asarray(ghc)))
    assert np.array_equal(w_p, w_r.T)
    cg_r = np.asarray(P.unpack_ghc(jnp.asarray(w_r[G:G + 256]), 6))
    cg_p = np.asarray(P.unpack_ghc_planes(jnp.asarray(w_p[:, G:G + 256]), 6))
    assert np.array_equal(cg_p, cg_r.T)


@pytest.mark.parametrize("n,start,cnt", [(1000, 0, 1000), (1000, 137, 700),
                                         (300, 10, 100), (700, 100, 550)])
def test_partition_segment_planes_matches_rows(rng, n, start, cnt):
    num_bin = 32
    bins, ghc = _mk(rng, n, num_bin=num_bin)
    _, work_r, work_p = _pair(bins, ghc)
    table = rng.rand(num_bin) < 0.45
    args = (jnp.int32(0), jnp.int32(G + start), jnp.int32(cnt), jnp.int32(3),
            jnp.asarray(table))
    out_r, lt_r = P.partition_segment(work_r, *args, ch=CH)
    out_p, lt_p = P.partition_segment_planes(work_p, *args, ch=CH)
    assert int(lt_r) == int(lt_p)
    # the planes compaction uses the same dest arithmetic transposed:
    # the whole destination plane is the rows result bit-for-bit
    assert np.array_equal(np.asarray(out_p)[1], np.asarray(out_r)[1].T)


@pytest.mark.parametrize("num_bin,exact,lo_w", [(32, True, 4), (32, True, 8),
                                                (256, True, 8),
                                                (17, False, 4)])
def test_hist_chunk_planes_bit_identical(rng, num_bin, exact, lo_w):
    bins, ghc = _mk(rng, 600, num_bin=num_bin)
    cb = jnp.asarray(bins[G:G + CH])
    cg = jnp.asarray(ghc[G:G + CH])
    hr = np.asarray(_hist16_chunk(cb, cg, num_bin, exact, lo_w))
    hp = np.asarray(_hist16_chunk_planes(cb.T, cg.T, num_bin, exact, lo_w))
    assert np.array_equal(hr.view(np.uint8), hp.view(np.uint8))


def test_hist16_segment_planes_bit_identical(rng):
    n, f, num_bin = 900, 5, 32
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    _, work_r, work_p = _pair(bins, ghc)
    hr = np.asarray(hist16_segment(
        work_r, jnp.int32(0), jnp.int32(G + 57), jnp.int32(700),
        num_bins=num_bin, num_feat=f, chunk=CH))
    hp = np.asarray(hist16_segment_planes(
        work_p, jnp.int32(0), jnp.int32(G + 57), jnp.int32(700),
        num_bins=num_bin, num_feat=f, chunk=CH))
    assert np.array_equal(hr.view(np.uint8), hp.view(np.uint8))


def test_pack_planes_write_root_matches_rows(rng):
    """The planes pack's root histogram (the write-only pack, then the
    planes segment histogram over the packed root segment) must be
    bit-identical to the rows path's root pass (same chunking and
    accumulation order), and the packed planes the transposed packed rows."""
    n, f, num_bin = 1000, 6, 32
    guard, width = P.work_spec(f, False, "xla", CH, CH, layout="planes")
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin, guard=guard)
    npad = P.planes_npad(n, guard, "xla")
    work = P.pack_planes_write(
        jnp.zeros((2, width, npad), jnp.uint8),
        jnp.asarray(bins[guard:guard + n]),
        jnp.asarray(ghc[guard:guard + n]), guard, chunk=P.planes_pack_chunk(
            width))
    root = hist16_segment_planes(work, jnp.int32(0), jnp.int32(guard),
                                 jnp.int32(n), num_bins=num_bin, num_feat=f,
                                 chunk=CH)
    w_r = np.asarray(P.pack_rows(jnp.asarray(bins), jnp.asarray(ghc)))
    work_r = jnp.stack([jnp.asarray(w_r), jnp.zeros_like(jnp.asarray(w_r))])
    ref = np.asarray(hist16_segment(
        work_r, jnp.int32(0), jnp.int32(guard), jnp.int32(n),
        num_bins=num_bin, num_feat=f, chunk=CH))
    assert np.array_equal(np.asarray(root).view(np.uint8),
                          ref.view(np.uint8))
    got = np.asarray(work)[0, :w_r.shape[1], :w_r.shape[0]]
    assert np.array_equal(got, w_r.T)


def _sub_block_runs(go, head, sb):
    """(p_l % sb, nl) per compaction sub-block, as the kernel walks them:
    sub-blocks tile the lanes from the segment's 128-aligned base, and the
    left cursor starts at ``head`` (the segment's offset from that base)."""
    flags = np.concatenate([np.zeros(head, bool), go])
    runs, p_l = [], head
    for i in range(0, len(flags), sb):
        nl = int(flags[i:i + sb].sum())
        runs.append((p_l % sb, nl))
        p_l += nl
    return runs


# (start, cnt, ch, f, left share, sb, what the case must cross)
_PLANES_KERNEL_CASES = [
    (137, 700, 256, 20, 0.45, None, None),
    (0, 1500, 256, 20, 0.45, None, None),
    (513, 100, 256, 20, 0.45, None, None),
    (333, 1400, 512, 20, 0.45, None, None),
    # a left run that wraps a half boundary (p_l % SB + nl > SB), per SB
    (137, 700, 256, 20, 0.6, 256, "wrap"),
    (137, 700, 256, 20, 0.6, 128, "wrap"),
    (333, 1400, 512, 20, 0.45, 128, "wrap"),
    # every row right / every row left: nl == 0 and nl == SB sub-blocks
    (137, 900, 256, 20, 0.0, 256, "nl0"),
    (137, 900, 256, 20, 0.0, 128, "nl0"),
    (50, 1100, 256, 20, 1.0, 256, "nlsb"),
    (50, 1100, 256, 20, 1.0, 128, "nlsb"),
    # a segment shorter than one sub-block, inside one and across two
    (513, 37, 256, 20, 0.45, 128, "short"),
    (600, 37, 256, 20, 0.45, 256, "short"),
    (250, 90, 256, 20, 0.5, 128, "short"),
    # W = 160 planes (F = 137 + 12, padded to whole u8 sublane tiles)
    (137, 700, 256, 137, 0.45, None, None),
    (333, 1400, 512, 137, 0.55, 128, "wrap"),
    (333, 1400, 512, 137, 0.55, 256, "wrap"),
]


@pytest.mark.parametrize("start,cnt,ch,f,share,sb,crosses",
                         _PLANES_KERNEL_CASES)
def test_planes_pallas_kernel_interpret(rng, start, cnt, ch, f, share, sb,
                                        crosses, monkeypatch):
    """The fused planes kernel, run under the pallas interpreter, must place
    rows exactly where the kernel's contract says — left child in row order
    ascending from ``start``, right child in REVERSED row order below
    ``start + cnt`` — whatever the sub-block size, so that the work buffer
    (and with it the histograms' summation order and the model) is
    byte-identical across sub-block sizes; neighbors outside the segment and
    the source plane untouched. Against the XLA planes path: same left
    child, same right row set (its right child is chunk-reversed)."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    n, num_bin, feat = 1500, 32, 3
    guard = ch + 2 * P.PLANE_ALIGN
    npad = ((n + 2 * guard + 127) // 128) * 128
    bins = np.zeros((npad, f), np.uint8)
    bins[guard:guard + n] = rng.randint(0, num_bin, (n, f))
    ghc = np.zeros((npad, 3), np.float32)
    ghc[guard:guard + n] = rng.randn(n, 3)
    ghc[guard:guard + n, 2] = 1.0
    w0 = np.asarray(P.pack_planes(jnp.asarray(bins), jnp.asarray(ghc)))
    width = P.work_spec(f, False, "pallas", ch, ch, layout="planes")[1]
    w0 = np.pad(w0, ((0, width - w0.shape[0]), (0, 0)))
    assert width == {20: 32, 137: 160}[f]
    sib = rng.randint(0, 256, w0.shape).astype(np.uint8)  # junk dst plane
    work = jnp.stack([jnp.asarray(w0), jnp.asarray(sib)])
    table = rng.rand(num_bin) < share
    args = (jnp.int32(0), jnp.int32(guard + start), jnp.int32(cnt),
            jnp.int32(feat), jnp.asarray(table))
    kw = {} if sb is None else {"sb": sb}
    out_x, lt_x = P.partition_segment_planes(work, *args, ch=ch)
    out_p, lt_p = P.partition_segment_planes_fused(work, *args, ch=ch, **kw)
    out_x, out_p = np.asarray(out_x), np.asarray(out_p)
    lt = int(lt_p)
    assert lt == int(lt_x)
    s0, s1 = guard + start, guard + start + cnt

    seg = w0[:, s0:s1]
    go = table[seg[feat]]
    assert lt == int(go.sum())
    if crosses is not None:
        runs = _sub_block_runs(go, s0 % P.PLANE_ALIGN, sb)
        assert {
            "wrap": any(a + nl > sb for a, nl in runs),
            "nl0": all(nl == 0 for _, nl in runs),
            "nlsb": any(nl == sb for _, nl in runs),
            "short": cnt < sb and len(runs) <= 2,
        }[crosses], runs
    want = sib.copy()
    want[:, s0:s0 + lt] = seg[:, go]
    want[:, s0 + lt:s1] = seg[:, ~go][:, ::-1]
    assert np.array_equal(out_p[1], want)
    assert np.array_equal(out_p[0], w0)

    assert np.array_equal(out_p[1, :, s0:s0 + lt], out_x[1, :, s0:s0 + lt])
    assert sorted(map(bytes, out_p[1, :, s0 + lt:s1].T)) == \
        sorted(map(bytes, out_x[1, :, s0 + lt:s1].T))


def _train_tree(layout, n, f, leaves, seed=0, part_chunk=CH, hist_chunk=CH):
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
        "tree_builder": "partition", "tpu_part_chunk": part_chunk,
        "tpu_hist_chunk": hist_chunk, "min_data_in_leaf": 2,
        "verbosity": -1, "tpu_work_layout": layout})
    ds = construct_dataset(X, cfg, label=y)
    lrn = SerialTreeLearner(cfg, ds)
    assert lrn.build_kwargs()["work_layout"] == layout
    ghc = jnp.stack([jnp.asarray(g), jnp.asarray(h),
                     jnp.ones(n, jnp.float32)], axis=1)
    return jax.device_get(
        lrn.train(ghc, jnp.ones(ds.num_features, bool),
                  jax.random.PRNGKey(0)))


# F=28 / F=137 cross leaves=255 / leaves=2; N deliberately NOT a multiple
# of the 256-row chunks
@pytest.mark.parametrize("n,f,leaves", [(2999, 28, 255), (1237, 137, 2),
                                        (1237, 28, 2), (1501, 137, 255)])
def test_tree_parity_layouts(n, f, leaves):
    a = _train_tree("rows", n, f, leaves)
    b = _train_tree("planes", n, f, leaves)
    assert int(a.num_splits) == int(b.num_splits)
    for fld in ("split_leaf", "feature", "bin", "kind", "default_left",
                "gain", "left_sum", "right_sum", "go_left", "leaf_value",
                "leaf_sum", "row_leaf"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, fld)), np.asarray(getattr(b, fld)),
            err_msg=fld)


def test_planes_carried_work_buf_parity(rng):
    """A planes buffer carried from a PREVIOUS tree (the fused-block
    contract) must grow the same tree as a fresh zero buffer: the pack
    rewrites every consumed lane, so last tree's leftovers are never read."""
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
        "tpu_work_layout": "planes"})
    ds = construct_dataset(X, cfg, label=y)
    lrn = SerialTreeLearner(cfg, ds)

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
                       return_work=True)
    log_a = build(lrn.bins, ghc2, lrn.meta, fmask, key, used)
    log_b, _ = build(lrn.bins, ghc2, lrn.meta, fmask, key, used,
                     work_buf=carried, return_work=True)
    for fld in ("num_splits", "feature", "bin", "gain", "leaf_value",
                "row_leaf"):
        np.testing.assert_array_equal(
            np.asarray(getattr(log_a, fld)), np.asarray(getattr(log_b, fld)),
            err_msg=fld)


def test_hist_pallas_chunk_not_32_raises():
    work = jnp.zeros((2, 256, 128), jnp.uint8)
    with pytest.raises(ValueError, match="multiple of 32"):
        hist_pallas_segment(work, jnp.int32(0), jnp.int32(0), jnp.int32(64),
                            num_bins=32, num_feat=6, chunk=100)


def test_learner_gate_hist_chunk_32(rng):
    """The learner gate refuses a misaligned tpu_hist_chunk with the pallas
    histogram kernel instead of silently corrupting histograms."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner
    from lightgbm_tpu.utils.log import LightGBMError

    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 4, "max_bin": 15,
        "tree_builder": "partition", "verbosity": -1,
        "tpu_partition_kernel": "pallas", "tpu_hist_kernel": "pallas",
        "tpu_hist_chunk": 100, "tpu_part_chunk": 256})
    ds = construct_dataset(X, cfg, label=y)
    with pytest.raises(LightGBMError, match="multiple of 32"):
        SerialTreeLearner(cfg, ds).build_kwargs()


def test_config_rejects_bad_layout():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError, match="tpu_work_layout"):
        Config.from_params({"tpu_work_layout": "diagonal"})


def test_device_cache_version_token(rng):
    """In-place host mutation + bump_version() must refresh the cached
    device copies (identity alone cannot see in-place writes)."""
    from lightgbm_tpu.dataset import Metadata

    meta = Metadata(8)
    meta.label = np.arange(8, dtype=np.float32)
    cached = meta.device_label()
    assert meta.device_label() is cached      # identity-keyed cache hit
    meta.label[0] = 99.0          # in-place: identity key unchanged
    meta.bump_version()
    fresh = meta.device_label()
    assert fresh is not cached                # token invalidated the entry
    assert float(np.asarray(fresh)[0]) == 99.0


def test_device_bins_version_token(rng):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset

    X = rng.randn(64, 3)
    cfg = Config.from_params({"max_bin": 15, "verbosity": -1,
                              "min_data_in_leaf": 1, "min_data_in_bin": 1})
    ds = construct_dataset(X, cfg, label=(X[:, 0] > 0).astype(np.float64))
    cached = ds.device_bins()
    assert ds.device_bins() is cached         # identity-keyed cache hit
    old = int(ds.binned[0, 0])
    ds.binned[0, 0] = old ^ 1                 # in-place host write
    ds.bump_version()
    fresh = ds.device_bins()
    assert fresh is not cached                # token invalidated the entry
    assert int(np.asarray(fresh)[0, 0]) == old ^ 1


def test_bench_breakdown_accounting():
    """bench.py's phase attribution must account for >= 95% of a fused
    train's wall (the PERF.md tables rely on this attribution)."""
    import sys
    import time
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import _phase_mark, _phases
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(3)
    n = 3000
    X = rng.randn(n, 8)
    y = (X @ rng.randn(8) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 31,
              "verbosity": -1, "tpu_iter_block": 5}
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    lgb.train(dict(params), ds, num_boost_round=5)   # warmup/compile
    mark = _phase_mark()
    t0 = time.time()
    lgb.train(dict(params), ds, num_boost_round=10)
    wall = time.time() - t0
    ph = _phases(mark.grown(), wall)
    assert ph["accounted_pct"] >= 95.0, ph
