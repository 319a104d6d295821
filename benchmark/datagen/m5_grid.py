"""The M5 Forecasting - Accuracy training grid of one or more Walmart stores,
with the feature grid of the public per-store LightGBM notebooks ("M5 -
Simple FE", "M5 - Three shades of Dark: Darker magic"), synthetic at the
source's shape. Dense float32 ``(N, 73)``; the label is the day's unit sales.

A row is one (series, day): a series is one item in one store, and it enters
the grid at its release, the first day of its first priced week (the
notebooks' ``release`` rule). Series are ordered item by item, the stores
of one item next to each other, and each series' days in order; ``shape``
``rows`` takes that many rows of the grid from its start, so a smaller
``rows`` is a prefix of the same grid (a rehearsal's table), but for the
encodings, which are taken over the rows there are.

What is a constant of the configuration's file (``args``), drawn from
``constants_seed`` and never from ``--seed``: each item's department,
category, sales level, intermittency, price path and promotions; the
stores' levels and the release days; the weekly and yearly profiles; the
calendar, its events and SNAP days. ``--seed`` draws the sales noise
alone: the zero-inflation and the negative binomial of every (series, day).
Everything the features derive from sales (lags, rolls, encodings) follows
that noise; everything static (rows, columns, categories, where the NaNs
are) does not.

The columns, in order (``COLUMNS``):

- codes (``categorical_feature``): ``item_id``, ``dept_id``, ``cat_id``,
  ``store_id``, ``event_name_1``, ``event_type_1``, ``event_name_2``,
  ``event_type_2``; an event column is NaN on a day without one, as pandas
  categories reach LightGBM;
- ``release``: the series' release week;
- prices: ``sell_price``, ``price_max``, ``price_min``, ``price_std``,
  ``price_mean``, ``price_norm``, ``price_nunique``, ``item_nunique``,
  ``price_momentum`` (over the week before; NaN in the first),
  ``price_momentum_m``, ``price_momentum_y`` (over the series' mean price in
  that calendar month / year);
- ``snap_CA``, ``snap_TX``, ``snap_WI``;
- calendar: ``tm_d``, ``tm_w``, ``tm_m``, ``tm_y``, ``tm_wm``, ``tm_dw``,
  ``tm_w_end``;
- encodings over the grid's rows: ``enc_cat_id_mean``/``_std``,
  ``enc_dept_id_mean``/``_std``, ``enc_item_id_mean``/``_std``;
- ``sales_lag_28`` ... ``sales_lag_42``;
- ``rolling_mean_W`` and ``rolling_std_W`` of the sales 28 days back, W in
  7, 14, 30, 60, 180;
- ``rolling_mean_tmp_S_W``: the mean of the W days ending S days back, S in
  1, 7, 14 and W in 7, 14, 30, 60.

A lag or a roll whose days reach before the series' release is NaN (pandas
``shift`` and ``rolling`` with their default ``min_periods`` over the grid's
rows of the series); a standard deviation is pandas' (ddof 1).
"""
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8
SERIES_CHUNK = 64
FIRST_DAY = np.datetime64("2011-01-29")     # d_1, a Saturday: wm_yr_wk starts it
LAGS = tuple(range(28, 43))
ROLL_WINDOWS = (7, 14, 30, 60, 180)
TMP_SHIFTS = (1, 7, 14)
TMP_WINDOWS = (7, 14, 30, 60)
CODES = ("item_id", "dept_id", "cat_id", "store_id", "event_name_1",
         "event_type_1", "event_name_2", "event_type_2")
PRICES = ("sell_price", "price_max", "price_min", "price_std", "price_mean",
          "price_norm", "price_nunique", "item_nunique", "price_momentum",
          "price_momentum_m", "price_momentum_y")
CALENDAR = ("tm_d", "tm_w", "tm_m", "tm_y", "tm_wm", "tm_dw", "tm_w_end")
ENCODED = ("cat_id", "dept_id", "item_id")
COLUMNS = (CODES + ("release",) + PRICES + ("snap_CA", "snap_TX", "snap_WI")
           + CALENDAR
           + tuple("enc_%s_%s" % (c, s) for c in ENCODED for s in ("mean", "std"))
           + tuple("sales_lag_%d" % k for k in LAGS)
           + tuple("rolling_%s_%d" % (s, w) for w in ROLL_WINDOWS for s in ("mean", "std"))
           + tuple("rolling_mean_tmp_%d_%d" % (s, w) for s in TMP_SHIFTS for w in TMP_WINDOWS))
CATEGORICAL = tuple(range(len(CODES)))


def calendar(days, args):
    """Per day: the calendar columns, the SNAP flags and the event codes
    (NaN where there is none), as float64 arrays of ``days``."""
    date = FIRST_DAY + np.arange(days)
    y = date.astype("datetime64[Y]")
    m = date.astype("datetime64[M]")
    dom = (date - m).astype(np.int64) + 1
    month = (m - y).astype(np.int64) + 1
    year = y.astype(np.int64) + 1970
    dow = (date.astype(np.int64) + 3) % 7                  # 1970-01-01 was a Thursday; Monday 0
    doy = (date - y).astype(np.int64)
    # ISO week of the year: the week of the Thursday of this date's week
    thu = date - dow + 3
    iso_week = (thu - thu.astype("datetime64[Y]")).astype(np.int64) // 7 + 1
    out = {"tm_d": dom, "tm_w": iso_week, "tm_m": month, "tm_y": year - year[0],
           "tm_wm": -(-dom // 7), "tm_dw": dow, "tm_w_end": (dow >= 5).astype(np.int64),
           "doy": doy, "month": month, "year": year}
    for state, snap_days in args["snap_days"].items():
        out["snap_" + state] = np.isin(dom, snap_days).astype(np.int64)
    names = [np.full(days, np.nan), np.full(days, np.nan)]
    types = [np.full(days, np.nan), np.full(days, np.nan)]
    for slot, key in ((0, "events"), (1, "events_2")):
        for code, ev in enumerate(args[key]):
            on = (month == ev["month"]) & (dom == ev["day"])
            if "years" in ev:
                on &= np.isin(year, ev["years"])
            names[slot][on] = code
            types[slot][on] = ev["type"]
    out.update(event_name_1=names[0], event_type_1=types[0],
               event_name_2=names[1], event_type_2=types[1])
    return out


def series_constants(args, days):
    """The file's constants for every (item, store) series, drawn from
    ``constants_seed``: item codes, departments, levels, release days, and
    weekly price paths (series x weeks, float64, cents rounded)."""
    def rng(stream):         # one stream a quantity: tuning one moves no other
        return np.random.default_rng([int(args["constants_seed"]), stream])
    depts = args["departments"]
    dept_of_item = np.repeat(np.arange(len(depts)), [d["items"] for d in depts])
    items = len(dept_of_item)
    cat_of_dept = np.array([d["category"] for d in depts])
    stores = args["stores"]
    S = items * len(stores)
    item = np.repeat(np.arange(items), len(stores))
    store = np.tile(np.arange(len(stores)), items)
    dept = dept_of_item[item]
    weeks = -(-days // 7)
    # an item's level and intermittency, the same in every store up to the
    # store's own level
    lvl = np.exp(rng(1).normal(args["level_log_mean"], args["level_log_sd"], items))
    zero = rng(2).beta(*args["intermittency_beta"], items)
    level = lvl[item] * np.array([s["level"] for s in stores])[store]
    # release: a share of the items are on sale from the first week, the rest
    # from a week drawn uniformly over the span; a store may release later
    r = rng(3)
    first_week = np.where(r.random(items) < args["released_at_start"], 0,
                          r.integers(0, weeks, items))
    lag = r.integers(0, args["store_release_lag_weeks"] + 1, S)
    release_week = np.minimum(first_week[item] + np.where(first_week[item] > 0, lag, 0),
                              weeks - 1)
    release_day = np.minimum(release_week * 7, days - 1)
    # prices: a dept's base, an item's level, a few permanent changes and
    # weeks of promotion; the same path in every store of the item
    base = np.array([d["price"] for d in depts])[dept_of_item] \
        * np.exp(rng(4).normal(0.0, args["price_log_sd"], items))
    r = rng(5)
    steps = r.random((items, weeks)) < args["price_change_per_week"]
    change = np.where(steps, np.exp(r.normal(0.0, args["price_change_log_sd"],
                                             (items, weeks))), 1.0)
    path = base[:, None] * np.cumprod(change, axis=1)
    promo = rng(6).random((items, weeks)) < args["promo_per_week"]
    path = np.where(promo, path * (1.0 - args["promo_discount"]), path)
    price = np.round(np.maximum(path, 0.1) * 100.0) / 100.0
    weekly = np.array(args["weekly_profile"])[cat_of_dept]          # dept -> 7 day factors
    return dict(items=items, S=S, item=item, store=store, dept=dept,
                cat=cat_of_dept[dept], level=level, zero=zero[item],
                release_week=release_week, release_day=release_day,
                price=price[item], base_price=base[item], weekly=weekly,
                elasticity=np.array([d["elasticity"] for d in depts])[dept],
                weeks=weeks)


def sales(sc, cal, args, seed, days):
    """(S, days) float64 unit sales, zero-inflated negative binomial around
    the series' mean; NaN before the series' release. ``--seed`` draws
    here and nowhere else."""
    t = np.arange(days)
    year_wave = 1.0 + args["yearly_amplitude"] * np.cos(2 * np.pi * (cal["doy"] - 350) / 365.25)
    trend = np.exp(args["trend_per_year"] * t / 365.25)
    snap = 1.0 + np.array(args["snap_lift"])[sc["cat"]][:, None] * cal["snap_CA"][None, :]
    event = np.where(np.isnan(cal["event_name_1"]), 1.0, args["event_lift"])
    closed = (cal["tm_m"] == 12) & (cal["tm_d"] == 25)
    week = t // 7
    rel_price = sc["price"][:, week] / sc["base_price"][:, None]
    mu = (sc["level"][:, None] / (1.0 - sc["zero"][:, None])
          * sc["weekly"][sc["dept"]][:, cal["tm_dw"]]
          * (year_wave * trend * event)[None, :] * snap
          * rel_price ** (-sc["elasticity"][:, None]))
    r = float(args["nb_shape"])
    y = np.empty_like(mu)
    for at in range(0, len(mu), SERIES_CHUNK):
        # a stream a chunk of series: a prefix of the grid draws what the
        # whole grid draws for those series
        rng = np.random.default_rng([int(seed) % (1 << 63), 41, at // SERIES_CHUNK])
        part = slice(at, at + SERIES_CHUNK)
        yp = rng.poisson(rng.gamma(r, mu[part] / r)).astype(np.float64)
        yp[rng.random(yp.shape) < sc["zero"][part, None]] = 0.0
        y[part] = yp
    y[:, closed] = 0.0
    y[t[None, :] < sc["release_day"][:, None]] = np.nan
    return y


def _cumsums(y):
    """Running sums of the sales and of their squares along the days, one
    leading 0 a series: float64 (S, days + 1)."""
    S, days = y.shape
    z = np.nan_to_num(y)
    c1 = np.zeros((S, days + 1))
    np.cumsum(z, axis=1, out=c1[:, 1:])
    c2 = np.zeros((S, days + 1))
    np.cumsum(z * z, axis=1, out=c2[:, 1:])
    return c1.reshape(-1), c2.reshape(-1)


def _group_stats(key, values):
    """Mean and pandas std (ddof 1) of ``values`` by integer ``key``."""
    n = np.bincount(key).astype(np.float64)
    s1 = np.bincount(key, weights=values)
    s2 = np.bincount(key, weights=values * values)
    mean = s1 / np.maximum(n, 1)
    var = (s2 - n * mean * mean) / np.maximum(n - 1, 1)
    return mean, np.sqrt(np.maximum(var, 0.0))


def _nanstd1(p):
    """pandas' std (ddof 1) of each row's values that are not NaN; NaN
    for a row of one value."""
    n = np.sum(~np.isnan(p), axis=1)
    mean = np.nansum(p, axis=1) / np.maximum(n, 1)
    ss = np.nansum((p - mean[:, None]) ** 2, axis=1)
    return np.where(n > 1, np.sqrt(ss / np.maximum(n - 1, 1)), np.nan)


def price_columns(sc, cal, days):
    """The notebooks' price statistics, by (series, week) -> (S, weeks)."""
    price = sc["price"]
    S, weeks = price.shape
    live = np.arange(weeks)[None, :] >= sc["release_week"][:, None]
    p = np.where(live, price, np.nan)
    out = {"price_max": np.nanmax(p, axis=1)[:, None] + 0 * p,
           "price_min": np.nanmin(p, axis=1)[:, None] + 0 * p,
           "price_std": _nanstd1(p)[:, None] + 0 * p,
           "price_mean": np.nanmean(p, axis=1)[:, None] + 0 * p}
    out["price_norm"] = p / out["price_max"]
    cents = np.round(np.nan_to_num(p) * 100).astype(np.int64)
    srt = np.sort(np.where(live, cents, -1), axis=1)
    distinct = (srt[:, 1:] != srt[:, :-1]) & (srt[:, 1:] >= 0)
    out["price_nunique"] = (distinct.sum(axis=1) + (srt[:, 0] >= 0))[:, None] + 0 * p
    # items of the store that ever sold at this price
    key = sc["store"][:, None] * (1 << 40) + cents
    pairs = np.unique(np.stack([key[live], np.broadcast_to(sc["item"][:, None], live.shape)[live]]),
                      axis=1)
    keys, per_key = np.unique(pairs[0], return_counts=True)
    at = np.searchsorted(keys, key)
    out["item_nunique"] = np.where(live, per_key[np.minimum(at, len(keys) - 1)], np.nan)
    prev = np.concatenate([np.full((S, 1), np.nan), p[:, :-1]], axis=1)
    out["price_momentum"] = p / prev
    wday = np.minimum(np.arange(weeks) * 7, days - 1)
    for name, by in (("price_momentum_m", cal["month"][wday]), ("price_momentum_y", cal["year"][wday])):
        mean = np.full_like(p, np.nan)
        for g in np.unique(by):
            cols = by == g
            with warnings.catch_warnings():     # a series not yet released in g
                warnings.simplefilter("ignore", RuntimeWarning)
                mean[:, cols] = np.nanmean(p[:, cols], axis=1, keepdims=True)
        out[name] = p / mean
    out["sell_price"] = p
    return out


def make(shape, args, seed):
    days = int(shape["days"])
    rows = int(shape["rows"])
    cal = calendar(days, args)
    sc = series_constants(args, days)
    active = days - sc["release_day"]
    ends = np.cumsum(active)
    if rows > ends[-1]:
        raise ValueError("the grid holds %d rows, %d asked" % (ends[-1], rows))
    # over every series of the grid: item_nunique counts the store's items
    prices = price_columns(sc, cal, days)
    # the series the prefix reaches, in whole chunks of the sales' streams
    S = int(np.searchsorted(ends, rows) + 1)
    S = min(-(-S // SERIES_CHUNK) * SERIES_CHUNK, sc["S"])
    for k in ("item", "store", "dept", "cat", "level", "zero", "release_week",
              "release_day", "price", "base_price", "elasticity"):
        sc[k] = sc[k][:S]
    y = sales(sc, cal, args, seed, days)
    live = ~np.isnan(y)
    # the grid's rows: series by series, days in order, the first ``rows``
    take = np.flatnonzero(live.reshape(-1))[:rows]
    sid, day = take // days, take % days
    X = np.empty((rows, len(COLUMNS)), np.float32)
    col = {c: j for j, c in enumerate(COLUMNS)}

    def put(name, values):
        X[:, col[name]] = values

    label = y.reshape(-1)[take].astype(np.float32)
    week = day // 7
    jobs = [lambda: put("item_id", sc["item"][sid]), lambda: put("dept_id", sc["dept"][sid]),
            lambda: put("cat_id", sc["cat"][sid]), lambda: put("store_id", sc["store"][sid]),
            lambda: put("release", sc["release_week"][sid])]
    for c in ("event_name_1", "event_type_1", "event_name_2", "event_type_2",
              "snap_CA", "snap_TX", "snap_WI") + CALENDAR:
        jobs.append(lambda c=c: put(c, cal[c][day]))
    for c in PRICES:
        jobs.append(lambda c=c: put(c, prices[c][sid, week]))
    yv = label.astype(np.float64)
    for c, key in (("cat_id", sc["cat"][sid]), ("dept_id", sc["dept"][sid]),
                   ("item_id", sc["item"][sid])):
        def enc(c=c, key=key):
            mean, std = _group_stats(key, yv)
            put("enc_%s_mean" % c, mean[key])
            put("enc_%s_std" % c, std[key])
        jobs.append(enc)
    flat = y.reshape(-1)
    for k in LAGS:
        def lag(k=k):
            back = take - k
            ok = day >= k
            put("sales_lag_%d" % k, np.where(ok, flat[np.where(ok, back, 0)], np.nan))
        jobs.append(lag)
    c1, c2 = _cumsums(y)
    base = sid * (days + 1)
    release = sc["release_day"][sid]
    windows = [(w, 28, "rolling_%s_" + str(w)) for w in ROLL_WINDOWS] + \
        [(w, s, "rolling_mean_tmp_%d_%d" % (s, w)) for s in TMP_SHIFTS for w in TMP_WINDOWS]
    for w, shift, name in windows:
        def roll(w=w, shift=shift, name=name):
            # the w days ending ``shift`` days back, all on or after the release
            end = day - shift + 1
            ok = end - w >= release
            hi = base + np.where(ok, end, 0)
            lo = base + np.where(ok, end - w, 0)
            mean = np.where(ok, (c1[hi] - c1[lo]) / w, np.nan)
            if "tmp" in name:
                put(name, mean)
                return
            put(name % "mean", mean)
            var = (c2[hi] - c2[lo] - w * mean * mean) / (w - 1)
            put(name % "std", np.sqrt(np.maximum(var, 0.0)))
        jobs.append(roll)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(j) for j in jobs]:
            f.result()
    return {"X": X, "label": label, "group": None}
