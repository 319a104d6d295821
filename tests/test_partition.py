"""Partitioned learner building blocks: ops/partition.py + hist16_segment.

Mirrors the reference's implicit DataPartition contract (reference:
src/treelearner/data_partition.hpp Split): after a split, the parent's rows
are exactly the union of the two children's contiguous segments, left rows
in stable order.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.partition import (
    DEFAULT_CH, guard_rows, pack_rows, partition_segment, unpack_ghc)
from lightgbm_tpu.ops.histogram import hist16_segment, hist_fb3

CH = 256  # small chunk so multi-chunk paths are exercised at test sizes
G = guard_rows(CH)


def _mk(rng, n, f=6, num_bin=32):
    npad = n + 2 * G
    bins = np.zeros((npad, f), np.uint8)
    bins[G:G + n] = rng.randint(0, num_bin, (n, f))
    ghc = np.zeros((npad, 3), np.float32)
    ghc[G:G + n] = rng.randn(n, 3)
    ghc[G:G + n, 2] = 1.0
    work0 = np.asarray(pack_rows(jnp.asarray(bins), jnp.asarray(ghc)))
    work = jnp.stack([jnp.asarray(work0), jnp.zeros_like(jnp.asarray(work0))])
    return bins, ghc, work0, work


@pytest.mark.parametrize("n,start,cnt", [(1000, 0, 1000), (1000, 137, 700),
                                         (300, 10, 100), (700, 100, 550)])
def test_partition_segment(rng, n, start, cnt):
    num_bin = 32
    bins, ghc, work0, work = _mk(rng, n, num_bin=num_bin)
    table = rng.rand(num_bin) < 0.45
    feat = 3
    out, lt = partition_segment(work, jnp.int32(0), jnp.int32(G + start),
                                jnp.int32(cnt), jnp.int32(feat),
                                jnp.asarray(table), ch=CH)
    out, lt = np.asarray(out), int(lt)
    seg = work0[G + start:G + start + cnt]
    go = table[seg[:, feat]]
    assert lt == int(go.sum())
    got = out[1, G + start:G + start + cnt]          # children land in plane 1
    # left child: stable order; right child: same rows, any order
    assert np.array_equal(got[:lt], seg[go])
    assert sorted(map(bytes, got[lt:])) == sorted(map(bytes, seg[~go]))
    # everything outside the segment in the target plane is untouched (zeros)
    assert not np.any(out[1, :G + start - CH])


def test_partition_preserves_channels(rng):
    n = 500
    bins, ghc, work0, work = _mk(rng, n)
    table = rng.rand(32) < 0.5
    out, lt = partition_segment(work, jnp.int32(0), jnp.int32(G),
                                jnp.int32(n), jnp.int32(0),
                                jnp.asarray(table), ch=CH)
    got = np.asarray(unpack_ghc(jnp.asarray(np.asarray(out)[1, G:G + n]), 6))
    seg_g = ghc[G:G + n]
    go = table[bins[G:G + n, 0]]
    exp = np.concatenate([seg_g[go], seg_g[~go]])
    # rows are bit-exact through the compaction matmul (byte payloads)
    assert np.array_equal(np.sort(got, axis=0), np.sort(exp, axis=0))
    assert np.allclose(got[:lt], seg_g[go])


@pytest.mark.parametrize("num_bin,exact", [(32, True), (256, True), (17, False)])
def test_hist16_segment(rng, num_bin, exact):
    n, f = 900, 5
    bins, ghc, work0, work = _mk(rng, n, f=f, num_bin=num_bin)
    start, cnt = 57, 700
    out = np.asarray(hist_fb3(hist16_segment(
        work, jnp.int32(0), jnp.int32(G + start), jnp.int32(cnt),
        num_bins=num_bin, num_feat=f, exact=exact, chunk=CH), num_bin))
    seg_b = bins[G + start:G + start + cnt]
    seg_g = ghc[G + start:G + start + cnt]
    ref = np.zeros((f, num_bin, 3), np.float64)
    for ff in range(f):
        for ch in range(3):
            ref[ff, :, ch] = np.bincount(seg_b[:, ff],
                                         weights=seg_g[:, ch].astype(np.float64),
                                         minlength=num_bin)
    tol = 1e-4 if exact else 2e-2
    scale = max(1.0, np.abs(ref).max())
    assert np.abs(out - ref).max() / scale < tol


def test_builders_agree_first_tree(rng):
    """Dense (O(N) masked) and partitioned builders grow the same tree."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    n, f = 1200, 6
    X = rng.randn(n, f)
    y = (X @ rng.randn(f) > 0).astype(np.float64)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    logs = {}
    for builder in ("dense", "partition"):
        cfg = Config.from_params({
            "objective": "binary", "num_leaves": 8, "max_bin": 31,
            "tree_builder": builder, "tpu_part_chunk": CH,
            "tpu_hist_chunk": CH, "min_data_in_leaf": 5, "verbosity": -1})
        ds = construct_dataset(X, cfg, label=y)
        lrn = SerialTreeLearner(cfg, ds)
        ghc = jnp.stack([jnp.asarray(g), jnp.asarray(h),
                         jnp.ones(n, jnp.float32)], axis=1)
        log = lrn.train(ghc, jnp.ones(ds.num_features, bool),
                        jax.random.PRNGKey(0))
        logs[builder] = jax.device_get(log)
    a, b = logs["dense"], logs["partition"]
    assert a.num_splits == b.num_splits
    np.testing.assert_array_equal(a.split_leaf, b.split_leaf)
    np.testing.assert_array_equal(a.feature, b.feature)
    np.testing.assert_array_equal(a.bin, b.bin)
    np.testing.assert_array_equal(a.row_leaf, b.row_leaf)
    np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=2e-3,
                               atol=1e-5)


def test_zero_as_missing_predict_parity(rng):
    """Training-time routing and all prediction paths must agree on
    zero_as_missing models (reference: tree.h NumericalDecision
    MissingType::Zero -> default direction for zeros)."""
    import lightgbm_tpu as lgb

    n, f = 1500, 3
    X = rng.randn(n, f)
    X[rng.rand(n) < 0.4, 0] = 0.0
    y = ((X[:, 0] != 0) * 1.0 + X[:, 1] > 0.5).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "zero_as_missing": True, "min_data_in_leaf": 5}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)
    train_raw = np.asarray(bst.inner.train_score.score)
    pred_raw = bst.predict(X, raw_score=True)
    np.testing.assert_allclose(train_raw, pred_raw, atol=1e-4)
    # text round-trip keeps routing identical
    bst2 = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(bst2.predict(X, raw_score=True), pred_raw,
                               atol=1e-4)
