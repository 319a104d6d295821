"""Histogram kernels vs naive reference (SURVEY.md §4: 'add real unit tests
for kernels (histogram vs naive reference)'): the dense XLA histogram, and
the plane-major Pallas segment kernel (what ``auto`` takes on a TPU) under
the pallas interpreter."""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import partition as P
from lightgbm_tpu.ops.histogram import (
    build_histogram_jit, build_histogram_np, hist16_segment_planes, hist_bins,
    hist_fb3, hist_pallas_segment_planes)

CH = 256


def _mk(rng, n, f=6, num_bin=32):
    bins = rng.randint(0, num_bin, (n, f)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    ghc[:, 2] = 1.0
    return jnp.asarray(bins), jnp.asarray(ghc)


def test_histogram_matches_naive(rng):
    n, f, b = 5000, 7, 32
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    dev = np.asarray(build_histogram_jit(jnp.asarray(bins), jnp.asarray(ghc), b))
    ref = build_histogram_np(bins, ghc, b)
    np.testing.assert_allclose(dev, ref, rtol=1e-4, atol=1e-3)


def test_histogram_chunked_equals_single(rng):
    n, f, b = 3000, 4, 16
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    a = np.asarray(build_histogram_jit(jnp.asarray(bins), jnp.asarray(ghc), b, 512))
    c = np.asarray(build_histogram_jit(jnp.asarray(bins), jnp.asarray(ghc), b, 4096))
    np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-4)


def test_histogram_masked_rows_zero_out(rng):
    n, f, b = 1000, 3, 8
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    ghc = rng.randn(n, 3).astype(np.float32)
    mask = (rng.rand(n) < 0.5).astype(np.float32)
    dev = np.asarray(build_histogram_jit(
        jnp.asarray(bins), jnp.asarray(ghc * mask[:, None]), b))
    ref = build_histogram_np(bins[mask > 0], ghc[mask > 0], b)
    np.testing.assert_allclose(dev, ref, rtol=1e-4, atol=1e-3)


def _hilo(x):
    """The value the histograms really sum: x as its bf16 (hi, lo) pair."""
    bf = lambda v: np.asarray(jnp.asarray(v, jnp.float32)
                              .astype(jnp.bfloat16).astype(jnp.float32))
    hi = bf(x)
    return hi.astype(np.float64) + bf(x - hi)


# (n, F, num_bins, start, cnt, chunk, lo_w); chunk / lo_w 0 = what the code
# derives from F and the bins (planes_kernel_params)
_KERNEL_CASES = [
    # the three cells' (F, W) = (28, 64), (137, 160), (10, 32) at 256 bins,
    # derived lo_w / g / chunk: F a multiple of g, F % g == 1, F % 8 == 2
    (1500, 28, 256, 0, 1500, 0, 0),
    (1200, 137, 256, 57, 1000, 0, 0),
    (1500, 10, 256, 513, 100, 0, 0),
    # a start that is not 128-aligned; cnt under one chunk; cnt over a
    # ragged last chunk (head + cnt = 3 chunks and a bit)
    (1500, 28, 16, 57, 700, 256, 0),
    (1500, 28, 16, 513, 100, 256, 0),
    (1500, 28, 256, 130, 800, 256, 0),
    (1500, 10, 256, 1, 1499, 128, 0),
    # the other factorisation (g = 2) and a narrower bundle's bins
    (1200, 137, 256, 57, 1000, 256, 4),
    (1500, 10, 59, 57, 1000, 512, 4),
    (1500, 10, 59, 300, 1100, 256, 0),
    (1500, 28, 256, 7, 1400, 512, 16),
    # the whole packed root segment, as the learner builds the root: the
    # reference below is then the XLA paths' root histogram
    (1500, 28, 255, 0, 1500, 256, 0),   # narrow: higgs.train's W = 64
    (1300, 10, 256, 0, 1300, 256, 0),   # bundled: expo.train's 10 columns
    (900, 300, 31, 0, 900, 256, 0),     # wide: W = 320 planes
]


@pytest.mark.parametrize("n,f,num_bin,start,cnt,chunk,lo_w", _KERNEL_CASES)
def test_hist_pallas_planes_kernel_interpret(rng, n, f, num_bin, start, cnt,
                                             chunk, lo_w, monkeypatch):
    """The plane-major Pallas histogram kernel under the interpreter (f32
    operands) against the float64 oracle over the SAME terms (each gradient
    as its bf16 hi + lo pair, which is what every histogram path sums).
    Counts exactly; sums within 1e-6 of the cell's sum of |terms|: the
    products are exact and only the ORDER of at most ``cnt`` f32 additions
    differs from the oracle's (the MXU's grouping, the chunk grid), each
    rounding by at most 2^-24 of a partial sum no larger than that sum of
    |terms|; far below it in practice, and 1e-6 is still 8x under what a
    dropped lo half (2^-17) or a lost row would show."""
    from lightgbm_tpu.ops.histogram import (build_histogram_np,
                                            planes_kernel_params)
    monkeypatch.setattr(P, "_INTERPRET", True)
    derived = planes_kernel_params(f, num_bin, lo_w, chunk)
    guard = derived[3] + 2 * P.PLANE_ALIGN
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    npad = P.planes_npad(n, guard, "pallas")
    _, w_pl = P.work_spec(f, False, "pallas", CH, CH, layout="planes")
    work = P.pack_planes_write(jnp.zeros((2, w_pl, npad), jnp.uint8),
                               bins, ghc, guard, chunk=CH)
    a = (jnp.int32(0), jnp.int32(guard + start), jnp.int32(cnt))
    got, work_out = hist_pallas_segment_planes(
        work, *a, num_bins=num_bin, num_feat=f, chunk=chunk, lo_w=lo_w)
    assert got.shape == (3, f, hist_bins(num_bin))
    assert not np.asarray(got)[..., num_bin:].any()
    got = np.asarray(hist_fb3(got, num_bin))
    seg = slice(start, start + cnt)
    terms = np.asarray(ghc)[seg].astype(np.float64)
    terms[:, :2] = _hilo(np.asarray(ghc)[seg, :2])
    b = np.asarray(bins)[seg]
    want = build_histogram_np(b, terms, num_bin).astype(np.float64)
    room = build_histogram_np(b, np.abs(terms), num_bin).astype(np.float64)
    assert np.array_equal(got[..., 2], want[..., 2])
    assert np.all(np.abs(got - want) <= 1e-6 * room + 1e-30)
    # and the XLA loop reads the same histogram to the same tolerance
    ref = np.asarray(hist_fb3(hist16_segment_planes(
        work, *a, num_bins=num_bin, num_feat=f, chunk=CH), num_bin))
    assert np.array_equal(got[..., 2], ref[..., 2])
    assert np.all(np.abs(got - ref) <= 2e-6 * room + 1e-30)
    assert np.array_equal(np.asarray(work_out), np.asarray(work))


def test_hist_pallas_planes_kernel_interpret_bf16_channels(rng, monkeypatch):
    """``exact=False`` (tpu_hist_precision=bf16): three channel rows (g, h,
    count, each rounded to bf16) a lo digit instead of five; the oracle
    sums the rounded terms."""
    from lightgbm_tpu.ops.histogram import build_histogram_np
    monkeypatch.setattr(P, "_INTERPRET", True)
    n, f, num_bin, start, cnt = 1500, 10, 256, 57, 1200
    guard = 256 + 2 * P.PLANE_ALIGN
    bins, ghc = _mk(rng, n, f=f, num_bin=num_bin)
    npad = P.planes_npad(n, guard, "pallas")
    _, w_pl = P.work_spec(f, False, "pallas", CH, CH, layout="planes")
    work = P.pack_planes_write(jnp.zeros((2, w_pl, npad), jnp.uint8),
                               bins, ghc, guard, chunk=CH)
    a = (jnp.int32(0), jnp.int32(guard + start), jnp.int32(cnt))
    got, _ = hist_pallas_segment_planes(
        work, *a, num_bins=num_bin, num_feat=f, chunk=256, exact=False)
    got = np.asarray(hist_fb3(got, num_bin))
    seg = slice(start, start + cnt)
    terms = np.asarray(jnp.asarray(ghc)[seg].astype(jnp.bfloat16)
                       .astype(jnp.float32)).astype(np.float64)
    b = np.asarray(bins)[seg]
    want = build_histogram_np(b, terms, num_bin).astype(np.float64)
    room = build_histogram_np(b, np.abs(terms), num_bin).astype(np.float64)
    assert np.array_equal(got[..., 2], want[..., 2])
    assert np.all(np.abs(got - want) <= 1e-6 * room + 1e-30)


def test_hist_pallas_planes_raises_on_bad_shapes():
    a = (jnp.int32(0), jnp.int32(0), jnp.int32(64))
    work = jnp.zeros((2, 40, 1280), jnp.uint8)     # 40 planes: not 32-mult
    with pytest.raises(ValueError, match="32-sublane"):
        hist_pallas_segment_planes(work, *a, num_bins=16, num_feat=6,
                                   chunk=256)
    work = jnp.zeros((2, 64, 1280), jnp.uint8)
    with pytest.raises(ValueError, match="multiple of 128"):
        hist_pallas_segment_planes(work, *a, num_bins=16, num_feat=6,
                                   chunk=100)
    # lo_w 2 at 256 bins: a hi range of 128 leaves one feature a pass and
    # 2 lo rows, not whole sublane tiles
    with pytest.raises(ValueError, match="does not tile"):
        hist_pallas_segment_planes(work, *a, num_bins=256, num_feat=6,
                                   chunk=256, lo_w=2)


@pytest.mark.parametrize("chunk", [128, 256, 512, 2048])
def test_write_pack_leaves_the_packed_planes(rng, chunk):
    """The pack writes plane 0 of the work buffer with the transposed
    planes of (bins, ghc), byte for byte at every lane of the root segment,
    whatever its chunk (a ragged last pass, one pass longer than the
    segment), and zeros below them; nothing outside the segment or in
    plane 1."""
    n, f = 1100, 20
    bins, ghc = _mk(rng, n, f=f, num_bin=64)
    guard, w_pl = P.work_spec(f, False, "pallas", CH, CH, layout="planes")
    work = jnp.zeros((2, w_pl, P.planes_npad(n, guard, "pallas")), jnp.uint8)
    written = np.asarray(P.pack_planes_write(work, bins, ghc, guard,
                                             chunk=chunk))
    rows = f + P.GH_BYTES
    assert np.array_equal(written[0, :rows, guard:guard + n],
                          np.asarray(P.pack_planes(bins, ghc)))
    assert not written[0, rows:].any()
    assert not written[0, :, :guard].any()
    assert not written[0, :, guard + n:].any()
    assert not written[1].any()
