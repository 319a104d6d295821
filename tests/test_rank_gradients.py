"""``LambdarankNDCG.get_gradients`` against a plain per-query double loop.

The reference below is written from ``rank_objective.hpp``
``GetGradientsForOneQuery`` as THIS repo defines the objective (pairs of the
document at rank i < truncation_level with every document at a rank j > i
whose gain differs; |delta NDCG| = |discount_i - discount_j| x |gain_i -
gain_j| / max DCG of the whole query; ``lambdarank_norm`` scales a query by
log2(1 + sum |lambda|) / sum |lambda|), in float64, and imports nothing from
``objective.py``. The program computes the same pairs in float32 in SLOT
order: ranks by counting, no sort (buckets up to ``_COUNT_MAX_P``), or
between two sorts (longer buckets); both are held by the same reference.

Tolerance. A document's gradient is a sum of up to P (a top-K document) or
K (any other) pair terms. The program's terms carry a few f32 roundings
each (the exponential, five products) and are added in another order than
the reference's, so the error is a few 1e-7 of the sum of |terms|, which
the largest |gradient| of the query bounds from below within a small
factor. The discounts are the reference's own rounded to f32 on BOTH sides
of a pair: the j side reads the float64-made table by a one-hot select on
the rank (``_table_at_rank``: exact, held below), not by arithmetic: the
chip's ``1 / log2(rank + 2)`` is 601 ulp off that table (PERF.md section 6,
PR 31) where the CPU's is 2. So the tolerance follows from the order of the
f32 sums alone. Held: 2e-5 of the query's largest |value| + 1e-4 of the
value (seen on the CPU: 4e-7 of the largest).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import objective as O
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.obs import telemetry

SIGMOID = 1.0           # Config's default, asserted in _objective


def reference(score, label, sizes, trunc, norm, weight=None, sigmoid=SIGMOID):
    """float64 (N,) grad, hess: one query at a time, one pair at a time."""
    score = np.asarray(score, np.float64)
    gain_of = 2.0 ** np.asarray(label, np.float64) - 1.0
    grad = np.zeros(len(score))
    hess = np.zeros(len(score))
    start = 0
    for n in sizes:
        s, g = score[start:start + n], gain_of[start:start + n]
        order = np.argsort(-s, kind="stable")       # ties: the earlier row
        discount = 1.0 / np.log2(np.arange(n) + 2.0)
        max_dcg = float(np.sum(np.sort(g)[::-1] * discount))
        inv_max_dcg = 1.0 / max_dcg if max_dcg > 0 else 0.0
        lam = np.zeros(n)
        hes = np.zeros(n)
        for i in range(min(trunc, n)):
            a = order[i]
            for j in range(i + 1, n):
                b = order[j]
                if g[a] == g[b]:
                    continue
                hi, lo = (a, b) if g[a] > g[b] else (b, a)
                delta_ndcg = abs(discount[i] - discount[j]) \
                    * abs(g[a] - g[b]) * inv_max_dcg
                p = 1.0 / (1.0 + np.exp(sigmoid * (s[hi] - s[lo])))
                pl = -sigmoid * p * delta_ndcg
                ph = sigmoid * sigmoid * p * (1.0 - p) * delta_ndcg
                lam[hi] += pl
                lam[lo] -= pl
                hes[hi] += ph
                hes[lo] += ph
        if norm:
            total = np.abs(lam).sum()
            if total > 0:
                lam *= np.log2(1.0 + total) / total
                hes *= np.log2(1.0 + total) / total
        grad[start:start + n] = lam
        hess[start:start + n] = hes
        start += n
    hess = np.maximum(hess, 1e-20)
    if weight is not None:
        grad, hess = grad * weight, hess * weight
    return grad, hess


def _objective(sizes, label, weight=None, **params):
    cfg = Config.from_params(dict(objective="lambdarank", verbosity=-1,
                                  **params))
    assert cfg.sigmoid == SIGMOID
    ob = O.create_objective(cfg)
    ob.init(Metadata(len(label), label=label, weight=weight,
                     group=np.asarray(sizes)))
    return ob


def _data(sizes, seed, decimals=None, labels=5):
    rng = np.random.default_rng(seed)
    n = int(np.sum(sizes))
    label = rng.integers(0, labels, n).astype(np.float32)
    score = rng.normal(size=n).astype(np.float32)
    if decimals is not None:
        score = np.round(score, decimals)
    return score, label


def one_label(sizes, seed):
    score, label = _data(sizes, seed)
    label[sizes[0]:sizes[0] + sizes[1]] = 2.0      # the second query
    return score, label


# name -> (sizes, data(sizes, seed), params, row weights?, _COUNT_MAX_P)
CASES = {
    "tied_scores": ([1, 7, 20, 33, 130], lambda z, s: _data(z, s, 1), {}, False, None),
    "all_scores_equal": ([9, 40], lambda z, s: _data(z, s, -2), {}, False, None),
    "one_document_queries": ([1, 1, 5, 1], _data, {}, False, None),
    "query_of_one_label": ([12, 30, 8], one_label, {}, False, None),
    "truncation_below_length": ([4, 17, 60, 100], _data,
                                {"lambdarank_truncation_level": 5}, False, None),
    "truncation_above_length": ([4, 17, 60, 100], _data,
                                {"lambdarank_truncation_level": 1000}, False, None),
    "norm_off": ([3, 25, 70], lambda z, s: _data(z, s, 1),
                 {"lambdarank_norm": False}, False, None),
    "row_weights": ([6, 31, 90], _data, {}, True, None),
    "three_rungs": ([5, 8, 41, 48, 130, 160, 2, 150], _data, {}, False, None),
    # buckets longer than 16 sort, the two shorter count: both paths in one
    # program, one reference
    "sorted_and_counted": ([5, 8, 16, 17, 48, 130], lambda z, s: _data(z, s, 1),
                           {}, False, 16),
    "sorted_truncated_weighted": ([3, 40, 130], _data,
                                  {"lambdarank_truncation_level": 7}, True, 0),
    # a bucket really past the crossing, beyond the ladder's last rung
    "past_the_crossing": ([O._COUNT_MAX_P + 5, 3], lambda z, s: _data(z, s, 2),
                          {}, False, None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_gradients_equal_reference(name, monkeypatch):
    sizes, data, params, weighted, count_max = CASES[name]
    if count_max is not None:
        monkeypatch.setattr(O, "_COUNT_MAX_P", count_max)
    seed = sorted(CASES).index(name)
    score, label = data(sizes, seed)
    weight = np.random.default_rng(99).uniform(0.5, 2.0, len(score)) \
        .astype(np.float32) if weighted else None
    ob = _objective(sizes, label, weight, **params)
    counted = [p_b <= O._COUNT_MAX_P for _, p_b, _ in ob.bucket_shapes]
    if name.startswith("sorted") or name == "past_the_crossing":
        assert not all(counted), ob.bucket_shapes
    else:
        assert all(counted), ob.bucket_shapes
    grad, hess = jax.jit(ob.get_gradients)(jnp.asarray(score))
    trunc = int(params.get("lambdarank_truncation_level", 30))
    ref_g, ref_h = reference(score, label, sizes, trunc,
                             params.get("lambdarank_norm", True), weight)
    assert np.abs(ref_g).max() > 0
    start = 0
    for n in sizes:
        q = slice(start, start + n)
        for got, ref in ((np.asarray(grad)[q], ref_g[q]),
                         (np.asarray(hess)[q], ref_h[q])):
            np.testing.assert_allclose(
                got, ref, rtol=1e-4, atol=2e-5 * np.abs(ref).max(),
                err_msg="%s, query at row %d" % (name, start))
        start += n


def test_table_at_rank_is_the_table():
    # the j-side discount: bit for bit the table the i side and the sorted
    # path read, at every rank, whatever slot holds it
    table = (1.0 / np.log2(np.arange(300) + 2.0)).astype(np.float32)
    rank = np.stack([np.random.default_rng(q).permutation(300)
                     for q in range(4)]).astype(np.int32)
    got = np.asarray(O._table_at_rank(jnp.asarray(table), jnp.asarray(rank)))
    np.testing.assert_array_equal(got, table[rank])


def test_rank_by_counting_is_the_stable_argsort():
    rng = np.random.default_rng(3)
    s = np.round(rng.normal(size=(7, 48)), 1).astype(np.float32)
    s[:, 40:] = -np.inf                 # invalid slots, behind the valid ones
    s[2, :] = 0.5                       # a query of one score
    s[3, 5] = -0.0
    s[3, 6] = 0.0
    want = np.argsort(np.argsort(-s, axis=1, kind="stable"), axis=1,
                      kind="stable")
    np.testing.assert_array_equal(np.asarray(O._rank_by_counting(
        jnp.asarray(s))), want)


def _ops(text, op):
    return text.count('"stablehlo.%s"(' % op) + text.count(" stablehlo.%s " % op)


def test_counted_program_has_no_sort_and_two_movers():
    sizes = [3, 8, 20, 48, 130, 100, 7]
    score, label = _data(sizes, 0)
    ob = _objective(sizes, label)
    lowered = jax.jit(ob.get_gradients).lower(jnp.asarray(score))
    text = lowered.as_text()
    assert _ops(text, "sort") == 0
    # one gather a bucket on the way in, one on the way out, none for the
    # discount
    assert _ops(text, "gather") == len(ob.bucket_shapes) + 1
    assert _ops(text, "scatter") == 0
    named = lowered.as_text(debug_info=True)
    for phase in ("rank_gather", "rank_sort", "rank_pairs", "rank_scatter"):
        assert "lgbtpu/" + phase in named


def test_sorted_program_still_sorts(monkeypatch):
    monkeypatch.setattr(O, "_COUNT_MAX_P", 0)
    sizes = [3, 8, 20]
    score, label = _data(sizes, 0)
    ob = _objective(sizes, label)
    text = jax.jit(ob.get_gradients).lower(jnp.asarray(score)).as_text()
    assert _ops(text, "sort") == 2 * len(ob.bucket_shapes)


def test_slot_of_row_inverts_the_buckets():
    sizes = [1, 8, 9, 130, 48, 5, 1, 300]
    score, label = _data(sizes, 1)
    ob = _objective(sizes, label)
    n = len(score)
    row_of_slot = np.concatenate(
        [np.asarray(a["safe_idx"]).reshape(-1) for a in ob.bucket_arrays])
    valid = np.concatenate(
        [np.asarray(a["valid"]).reshape(-1) for a in ob.bucket_arrays])
    slot = np.asarray(ob.slot_of_row)
    assert slot.shape == (n,) and slot.dtype == np.int32
    assert len(np.unique(slot)) == n                    # a slot a row
    assert valid[slot].all()
    np.testing.assert_array_equal(row_of_slot[slot], np.arange(n))
    assert valid.sum() == n                             # every valid slot used


@pytest.mark.parametrize("count_max, share", [(None, 1.0), (16, None), (0, 0.0)])
def test_counted_share_gauge(count_max, share, monkeypatch):
    if count_max is not None:
        monkeypatch.setattr(O, "_COUNT_MAX_P", count_max)
    sizes = [5, 8, 16, 17, 48]
    _, label = _data(sizes, 0)
    ob = _objective(sizes, label)
    gauges = telemetry.snapshot()["gauges"]
    slots = sum(q * p for q, p, _ in ob.bucket_shapes)
    if share is None:
        share = sum(q * p for q, p, _ in ob.bucket_shapes if p <= 16) / slots
        assert 0 < share < 1
    assert gauges["rank/slots_counted_share"] == pytest.approx(share)
    assert gauges["rank/slots"] == slots
    assert gauges["rank/buckets"] == len(ob.bucket_shapes)
