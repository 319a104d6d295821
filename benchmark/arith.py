"""The benchmark's own arithmetic: time per iteration, job start, bytes a kernel
needs, peaks. Pure functions of numbers, tested in benchmark/tests."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def window_ms_per_iter(returns, first, last):
    """The end-to-end number: all the time between returns ``first`` and
    ``last`` over all the iterations dispatched between them. ``returns`` is
    a list of (host seconds, iterations dispatched by then); the loop is
    closed and a block behind at every return alike, so what was dispatched
    between two returns is what the device did between them."""
    return (returns[last][0] - returns[first][0]) * 1e3 \
        / (returns[last][1] - returns[first][1])


def block_periods_ms(returns, first, last):
    """ms per iteration of each block between returns ``first`` and ``last``:
    for people (READINGS), to see drift or a stall inside the window."""
    return [window_ms_per_iter(returns, i, i + 1) for i in range(first, last)]


def closing_return(returns, first, seconds):
    """Index of the first return at or after ``seconds`` past return
    ``first``; None while the window is still open."""
    for i in range(first + 1, len(returns)):
        if returns[i][0] - returns[first][0] >= seconds:
            return i
    return None


def job_start_s(enter_s, open_s, iters_done_at_open, ms_per_iter, compile_s):
    """Seconds a user waits before boosting starts: entry of the train call
    to the return that opens the window, less the boosting already done by
    then at this run's own pace, less XLA's compile-or-load seconds."""
    return (open_s - enter_s) - iters_done_at_open * ms_per_iter / 1e3 - compile_s


def partition_bytes(parent_rows, features, bin_bytes=1):
    """Bytes a row partition needs for one tree: every row of each split's
    parent is read once and written once, with what travels with a row: its
    bins in every feature, its gradient and hessian (float32) and its row
    index (int32)."""
    return 2 * int(sum(parent_rows)) * (features * bin_bytes + 12)


def histogram_bytes(left_rows, right_rows, features, bins=256, bin_bytes=1):
    """Bytes the per-split histograms need for one tree: the rows of the
    SMALLER child are read once (bins of every feature, gradient, hessian);
    the larger child comes by subtraction. Each split writes two histograms
    of (features x bins) x (grad, hess, count) float32."""
    rows = sum(min(int(l), int(r)) for l, r in zip(left_rows, right_rows))
    return rows * (features * bin_bytes + 8) + len(left_rows) * 2 * features * bins * 12


def peaks(device_kind):
    """The published peaks of a device; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError("device %r is not in benchmark/peaks.json" % device_kind)
    return table[device_kind]


def roofline_pct(bytes_needed, seconds, hbm_bytes_per_s):
    """Share of the HBM roofline: least time the chip could take over the
    time the kernel took. These kernels do a few integer/float adds per byte,
    so bandwidth bounds them, not FLOP/s."""
    return 100.0 * (bytes_needed / hbm_bytes_per_s) / seconds
