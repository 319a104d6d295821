"""GOSS row compaction (tpu_goss_compact): parity with the dense-mask oracle.

After `make_sampler` zeroes out-of-bag gradients, the compact path
(ISSUE 17) sorts the in-bag survivors to the front of the row set
(ops/partition.py compact_rows_by_inbag) and rebuilds the tree over a
STATIC ceil((top_rate+other_rate)*N)-row slice — same shapes every
iteration, zero recompiles — while the dense-mask path is retained
verbatim as the bit-parity oracle. The contract is byte-identical
model_to_string() output: the compact branch feeds the dense row sums
to the root (f32 row-reduction grouping is the one compaction-visible
reassociation) and routes leaf assignment over the FULL bin matrix, so
leaf counts and values match the oracle exactly.

Also pins satellite 1: the GOSS threshold in fused.make_sampler moved
from a full jnp.sort to jax.lax.top_k — bit-compatible by test.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import obs  # noqa: E402
from lightgbm_tpu.ops import partition as P  # noqa: E402

# lr=0.5 keeps the 1/lr GOSS warmup at 2 rounds, so rounds 2+ exercise
# the compacted branch of the in-graph cond
BASE = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "boosting": "goss", "top_rate": 0.3, "other_rate": 0.2,
        "learning_rate": 0.5, "tpu_iter_block": 2}


# --------------------------------------------------------------- op level

def test_topk_threshold_matches_sort(rng):
    """Satellite 1 pin: lax.top_k's k-th value is bit-identical to the
    full-sort threshold make_sampler used before, ties included."""
    for n, k in ((700, 210), (1024, 1), (333, 333), (64, 17)):
        s = jnp.asarray(rng.randn(n).astype(np.float32))
        s = jnp.where(jnp.asarray(rng.rand(n) < 0.3), s[0], s)  # duplicates
        thr_topk = jax.lax.top_k(s, k)[0][k - 1]
        thr_sort = jnp.sort(s)[n - k]
        assert thr_topk.dtype == thr_sort.dtype
        assert np.asarray(thr_topk).tobytes() == np.asarray(thr_sort).tobytes()


def test_goss_compact_rows_margin():
    """The static slice must cover top_k + binomial(rest, p) draws with
    slack, never exceed n, and stay well under n at production rates."""
    for n in (1000, 10_500_000):
        m = P.goss_compact_rows(n, 0.2, 0.1)
        assert int(n * 0.3) < m <= n
    assert P.goss_compact_rows(10_500_000, 0.2, 0.1) < 0.35 * 10_500_000
    assert P.goss_compact_rows(100, 0.9, 0.5) == 100       # clamps at n
    # slack covers 4 sigma of the binomial other_rate draw
    n, top, other = 50_000, 0.2, 0.1
    m = P.goss_compact_rows(n, top, other)
    top_k = int(n * top)
    rest = n - top_k
    p = other / (1 - top)
    assert m >= top_k + rest * p + 4 * np.sqrt(rest * p * (1 - p))


def test_compact_rows_by_inbag_stable_order(rng):
    """In-bag rows move to the front in their original relative order
    (bucket-stable integer argsort), and the in-bag count rides along."""
    n, f, m = 500, 6, 320
    bins = jnp.asarray(rng.randint(0, 32, (n, f)).astype(np.uint8))
    ghc = rng.randn(n, 3).astype(np.float32)
    mask = rng.rand(n) < 0.5
    ghc[:, 2] = mask
    ghc = jnp.asarray(ghc)
    bc, gc, c_in = P.compact_rows_by_inbag(bins, ghc, m)
    assert bc.shape == (m, f) and gc.shape == (m, 3)
    assert int(c_in) == int(mask.sum())
    idx = np.nonzero(mask)[0]
    np.testing.assert_array_equal(np.asarray(bc)[:len(idx)],
                                  np.asarray(bins)[idx])
    np.testing.assert_array_equal(np.asarray(gc)[:len(idx)],
                                  np.asarray(ghc)[idx])
    # tail is the out-of-bag filler, also in stable order
    out_idx = np.nonzero(~mask)[0][:m - len(idx)]
    np.testing.assert_array_equal(np.asarray(bc)[len(idx):],
                                  np.asarray(bins)[out_idx])


# ----------------------------------------------------- full-train parity

def _model(params, X, y, rounds=6, **dskw):
    ds = lgb.Dataset(X, label=y, params=dict(params), **dskw)
    bst = lgb.train(dict(params), ds, num_boost_round=rounds)
    return bst.model_to_string()


def _ab_models(extra, X, y, rounds=6, **dskw):
    on = dict(BASE, tpu_goss_compact="on", **extra)
    off = dict(BASE, tpu_goss_compact="off", **extra)
    return (_model(on, X, y, rounds, **dskw),
            _model(off, X, y, rounds, **dskw))


def test_train_parity_binary(rng):
    n = 700
    X = rng.randn(n, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    on, off = _ab_models({}, X, y)
    assert on == off


@pytest.mark.slow
def test_train_parity_multiclass(rng):
    n = 700
    X = rng.randn(n, 6)
    y = (np.abs(X[:, 0]) + X[:, 1] > 0.5).astype(np.float64) \
        + (X[:, 2] > 0.3)
    on, off = _ab_models({"objective": "multiclass", "num_class": 3}, X, y,
                         rounds=4)
    assert on == off


@pytest.mark.slow
def test_train_parity_nan_missing(rng):
    n = 700
    X = rng.randn(n, 6)
    X[rng.rand(n, 6) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.2 * rng.randn(n) > 0).astype(np.float64)
    on, off = _ab_models({"use_missing": True}, X, y)
    assert on == off


@pytest.mark.slow
def test_train_parity_categorical(rng):
    n = 700
    X = rng.randn(n, 5)
    X[:, 0] = rng.randint(0, 12, n)
    y = ((X[:, 0] % 3 == 0) ^ (X[:, 1] > 0)).astype(np.float64)
    on, off = _ab_models({"min_data_per_group": 5}, X, y,
                         categorical_feature=[0])
    assert on == off


@pytest.mark.slow
def test_train_parity_planes_pallas_partition(rng, monkeypatch):
    """Compaction composes with the planes layout and the Pallas partition
    (the path auto takes on a TPU): the compacted recursion grows the
    dense-mask build's trees, byte for byte."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    n = 700
    X = rng.randn(n, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    extra = {"tpu_work_layout": "planes", "tpu_partition_kernel": "pallas",
             "tpu_part_chunk": 256, "tpu_hist_chunk": 256, "max_bin": 31}
    on, off = _ab_models(extra, X, y, rounds=4)
    assert on == off


# --------------------------------------------------- telemetry + retrace

def test_second_identical_train_compiles_nothing(rng):
    """test_retrace.py discipline: the in-graph sort/slice/cond keeps one
    static shape across iterations — a second identical train recompiles
    nothing."""
    n = 530                      # shape distinct from other test modules
    X = rng.randn(n, 9)
    y = (X @ rng.randn(9) > 0).astype(np.float64)
    params = dict(BASE, tpu_goss_compact="on")
    ds = lgb.Dataset(X, label=y, params=dict(params))
    lgb.train(dict(params), ds, num_boost_round=4)   # warm every cache
    obs.telemetry.reset()
    bst = lgb.train(dict(params), ds, num_boost_round=4)
    jc = bst.telemetry()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc


def test_traffic_spec_effective_rows(rng):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float64)

    def spec(gc):
        cfg = Config.from_params(dict(BASE, num_leaves=4, max_bin=15,
                                      tpu_goss_compact=gc))
        ds = construct_dataset(X, cfg, label=y)
        lrn = SerialTreeLearner(cfg, ds)
        return lrn.build_kwargs(), lrn.traffic_spec()

    kw, tr = spec("on")
    m = P.goss_compact_rows(300, 0.3, 0.2)
    assert kw["goss_compact_rows"] == m
    assert tr["goss_compact"] == "on"
    assert tr["effective_rows"] == m
    # work buffers shrink to the compact row count
    lrn_spec = None
    kw_off, tr_off = spec("off")
    assert kw_off["goss_compact_rows"] == 0
    assert tr_off["goss_compact"] == "off"
    assert tr_off["effective_rows"] == 300


# ------------------------------------------------------------ knob gates

def test_config_rejects_bad_goss_compact():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError, match="tpu_goss_compact"):
        Config.from_params({"tpu_goss_compact": "maybe"})


def test_auto_resolves_off_with_record(rng):
    """auto stays off until scripts/goss_bisect.py validates a win on real
    hardware; the honest reason names the bisect script on GOSS configs
    and the structural miss elsewhere."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float64)

    def resolve(params):
        cfg = Config.from_params(params)
        ds = construct_dataset(X, cfg, label=y)
        obs.telemetry.reset()
        kw = SerialTreeLearner(cfg, ds).build_kwargs()
        recs = obs.telemetry.snapshot()["records"]["auto_resolution"]
        mine = [r for r in recs if r["knob"] == "tpu_goss_compact"]
        assert len(mine) == 1
        assert kw["goss_compact_rows"] == 0
        return mine[0]

    rec = resolve(dict(BASE, num_leaves=4, max_bin=15))
    assert rec["value"] == "off"
    assert "goss_bisect" in rec["reason"]
    rec = resolve({"objective": "binary", "num_leaves": 4, "max_bin": 15,
                   "verbosity": -1})
    assert rec["value"] == "off"
    assert "no GOSS sampling" in rec["reason"]


def test_ineligible_on_downgrades_to_off(rng):
    """Forcing on where the structure can't support it warns and keeps the
    dense-mask path instead of failing the train."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.learner import SerialTreeLearner

    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    # no GOSS sampling: nothing to compact
    cfg = Config.from_params({"objective": "binary", "num_leaves": 4,
                              "max_bin": 15, "verbosity": -1,
                              "tpu_goss_compact": "on"})
    ds = construct_dataset(X, cfg, label=y)
    assert SerialTreeLearner(cfg, ds).build_kwargs()["goss_compact_rows"] == 0
    # int8 quantized gradients: stochastic-rounding draws are row-position
    # seeded, so moving rows changes the dither stream
    cfg = Config.from_params(dict(BASE, num_leaves=4, max_bin=15,
                                  tpu_goss_compact="on",
                                  use_quantized_grad=True))
    ds = construct_dataset(X, cfg, label=y)
    assert SerialTreeLearner(cfg, ds).build_kwargs()["goss_compact_rows"] == 0
