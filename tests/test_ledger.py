"""Run ledger (ISSUE 10): round-trip, regression gate, knob preresolution.

Pins the self-calibration contract from the ROADMAP: one JSONL entry per
train run carrying machine identity + dataset shape + config fingerprint
+ every resolved auto knob, and a second train with an identical
(machine, shape, config) key pre-resolves all ``tpu_*`` auto knobs from
the ledger — ZERO new auto_resolution records — while producing the
bit-identical model. Plus the gate/compare/CLI surfaces behind
``scripts/check.sh --ledger``.
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import obs, obs_ledger  # noqa: E402
from lightgbm_tpu.config import Config  # noqa: E402

PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "tpu_iter_block": 5}


# NOT test_retrace.py's (600, 8): these suites share the cross-Booster
# block cache, and retrace's "first train" must stay genuinely cold
def _data(n=620, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.1 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _params(path, **over):
    p = dict(PARAMS, obs_ledger=True, obs_ledger_path=str(path))
    p.update(over)
    return p


# ------------------------------------------------------------------ round trip

def test_entry_round_trip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    cfg = Config.from_params(_params(path))
    obs.telemetry.reset()
    entry = obs_ledger.record_run(cfg, "train", 600, 8, extra={"x": 1})
    assert entry is not None
    read = list(obs_ledger.read_entries(path))
    assert len(read) == 1
    e = read[0]
    assert e["kind"] == "train"
    assert e["dataset"] == {"rows": 600, "features": 8}
    assert e["config_fp"] == obs_ledger.config_fingerprint(cfg)
    assert e["extra"] == {"x": 1}
    assert "device_cost" in e and "machine" in e
    # appends accumulate; corrupt lines are skipped, not fatal
    with open(path, "a") as f:
        f.write("{truncated garbage\n")
    obs_ledger.append(path, entry)
    assert len(list(obs_ledger.read_entries(path))) == 2


def test_fingerprint_ignores_volatile_fields(tmp_path):
    base = _params(str(tmp_path / "l.jsonl"))
    a = Config.from_params(base)
    b = Config.from_params(dict(base, verbosity=2,
                                output_model="elsewhere.txt",
                                obs_ledger_path="other.jsonl"))
    c = Config.from_params(dict(base, num_leaves=31))
    assert obs_ledger.config_fingerprint(a) == \
        obs_ledger.config_fingerprint(b)
    assert obs_ledger.config_fingerprint(a) != \
        obs_ledger.config_fingerprint(c)


# ------------------------------------------------------------- preresolution

def test_second_train_preresolves_all_tpu_auto_knobs(tmp_path):
    """The acceptance pin: run 1 records every resolved tpu_* auto knob;
    run 2 (same machine, shape, config) applies them from the ledger —
    zero NEW auto_resolution records — and trains the identical model."""
    path = str(tmp_path / "ledger.jsonl")
    X, y = _data()
    p = _params(path)

    obs.telemetry.reset()
    ds1 = lgb.Dataset(X, label=y)
    b1 = lgb.train(dict(p), ds1, num_boost_round=5)
    first = {r["knob"]: r["value"]
             for r in obs.telemetry.records("auto_resolution")}
    assert first, "first run resolved no auto knobs"
    assert all(k.startswith("tpu_") for k in first)
    entries = list(obs_ledger.read_entries(path))
    assert len(entries) == 1
    assert entries[0]["resolved_knobs"] == first

    obs.telemetry.reset()
    ds2 = lgb.Dataset(X, label=y)
    b2 = lgb.train(dict(p), ds2, num_boost_round=5)
    assert obs.telemetry.records("auto_resolution") == [], \
        "second identical train re-resolved auto knobs"
    pre = {r["knob"]: r["value"]
           for r in obs.telemetry.records("ledger_preresolution")}
    assert pre == first
    assert obs.telemetry.counter("ledger/preresolved_knobs") >= len(first)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X))
    # run 2's own entry still carries the full knob set forward
    entries = list(obs_ledger.read_entries(path))
    assert entries[-1]["resolved_knobs"] == first


def test_goss_knob_preresolves(tmp_path):
    """ISSUE 17 pin: on a GOSS config tpu_goss_compact resolves through
    the bisect-gated path (not the structural no-GOSS branch) and
    preresolves from the ledger on run 2 — zero NEW auto_resolution
    records."""
    path = str(tmp_path / "ledger.jsonl")
    X, y = _data(n=640, f=11, seed=4)   # keep the shared block cache cold
    p = _params(path, boosting="goss", top_rate=0.3, other_rate=0.2)

    obs.telemetry.reset()
    lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=5)
    first = {r["knob"]: r for r in obs.telemetry.records("auto_resolution")}
    assert first["tpu_goss_compact"]["value"] == "off"
    assert "goss_bisect" in first["tpu_goss_compact"]["reason"]

    obs.telemetry.reset()
    lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=5)
    assert obs.telemetry.records("auto_resolution") == [], \
        "second identical GOSS train re-resolved auto knobs"
    pre = {r["knob"]: r["value"]
           for r in obs.telemetry.records("ledger_preresolution")}
    assert pre == {k: r["value"] for k, r in first.items()}
    assert "tpu_goss_compact" in pre


@pytest.mark.slow  # two fresh-resolution trainings; the preresolve hit
# path itself stays tier-1 (test_second_train_preresolves_all_tpu_auto_knobs)
def test_preresolve_ignores_mismatched_key(tmp_path):
    """Different shape or different config fingerprint: no preresolution,
    knobs resolve fresh."""
    path = str(tmp_path / "ledger.jsonl")
    X, y = _data()
    p = _params(path)
    obs.telemetry.reset()
    lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=5)

    # different dataset shape
    X2, y2 = _data(n=700, f=9, seed=1)
    obs.telemetry.reset()
    lgb.train(dict(p), lgb.Dataset(X2, label=y2), num_boost_round=5)
    assert obs.telemetry.records("auto_resolution"), \
        "shape mismatch must resolve fresh"
    assert obs.telemetry.records("ledger_preresolution") == []

    # different (non-volatile) config
    obs.telemetry.reset()
    lgb.train(dict(_params(path, num_leaves=31)), lgb.Dataset(X, label=y),
              num_boost_round=5)
    assert obs.telemetry.records("auto_resolution")


def test_preresolve_sanitizes_corrupt_values(tmp_path):
    """A tampered ledger (invalid kernel name, negative chunk) must not
    reach the learner: bad values fall back to fresh auto resolution."""
    path = str(tmp_path / "ledger.jsonl")
    X, y = _data()
    p = _params(path)
    lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=5)
    entries = list(obs_ledger.read_entries(path))
    bad = dict(entries[0])
    bad["resolved_knobs"] = {"tpu_partition_kernel": "evil",
                             "tpu_part_chunk": -5,
                             "tpu_hist_chunk": "4096"}
    obs_ledger.append(path, bad)
    obs.telemetry.reset()
    bst = lgb.train(dict(p), lgb.Dataset(X, label=y), num_boost_round=5)
    assert bst.inner.iter_ == 5
    assert obs.telemetry.records("ledger_preresolution") == []
    assert obs.telemetry.records("auto_resolution")


def test_off_mode_writes_nothing_and_costs_nothing(tmp_path):
    """obs_ledger=False (default): no file, no ledger counters, and —
    via the compile-budget harness — zero compiles on a warm second
    train (the ledger path must add no device work either way)."""
    path = str(tmp_path / "never.jsonl")
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    lgb.train(dict(PARAMS), ds, num_boost_round=5)     # warm every cache
    obs.telemetry.reset()
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=5)
    assert not os.path.exists(path)
    assert obs.telemetry.counter("ledger/entries_written") == 0
    jc = bst.telemetry()["jit_compiles"]
    assert jc["total"] == 0, jc
    assert jc["backend_compiles"] == 0, jc


# -------------------------------------------------------------------- gating

def _entry(cfg, rows, features, train_s, kind="bench"):
    e = obs_ledger.build_entry(cfg, kind, rows, features,
                               extra={"train_s": train_s})
    return e


def test_gate_passes_then_fails_on_regression(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    cfg = Config.from_params(_params(path))
    # 0 entries: pass (fresh machine must not fail CI)
    ok, msg = obs_ledger.gate(path, cfg, 600, 8, "extra.train_s", 0.25)
    assert ok and "nothing to compare" in msg
    obs_ledger.append(path, _entry(cfg, 600, 8, 10.0))
    ok, _ = obs_ledger.gate(path, cfg, 600, 8, "extra.train_s", 0.25)
    assert ok  # 1 entry: still pass
    obs_ledger.append(path, _entry(cfg, 600, 8, 11.0))
    ok, msg = obs_ledger.gate(path, cfg, 600, 8, "extra.train_s", 0.25)
    assert ok, msg  # +10% within 25% tolerance
    obs_ledger.append(path, _entry(cfg, 600, 8, 20.0))
    ok, msg = obs_ledger.gate(path, cfg, 600, 8, "extra.train_s", 0.25)
    assert not ok, msg  # 11 -> 20 is +82%: fail
    # entries under a different key never enter the comparison
    other = Config.from_params(_params(path, num_leaves=31))
    obs_ledger.append(path, _entry(other, 600, 8, 1.0))
    ok, msg = obs_ledger.gate(path, cfg, 600, 8, "extra.train_s", 0.25)
    assert not ok, "foreign-key entry leaked into the gate"


def test_metric_value_dotted_paths():
    e = {"extra": {"train_s": 2.5},
         "telemetry": {"timers": {"fused/device_wait": 1.25}}}
    assert obs_ledger.metric_value(e, "extra.train_s") == 2.5
    assert obs_ledger.metric_value(
        e, "telemetry.timers.fused/device_wait") == 1.25
    assert obs_ledger.metric_value(e, "extra.missing") is None


# ----------------------------------------------------------------------- CLI

def test_cli_list_show_gate(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import ledger as ledger_cli
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "ledger.jsonl")
    cfg = Config.from_params(_params(path))
    obs_ledger.append(path, _entry(cfg, 600, 8, 5.0))
    assert ledger_cli.main(["list", "--path", path]) == 0
    assert ledger_cli.main(["show", "--path", path]) == 0
    # the CLI gate uses its own fixed CI key; foreign entries -> pass
    assert ledger_cli.main(["gate", "--path", path]) == 0


@pytest.mark.slow  # subprocess gate (check.sh --ledger pair), per the marker's charter
def test_cli_train_then_gate(tmp_path):
    """The check.sh --ledger pair end-to-end: train appends a gated
    entry, gate compares (first run: pass on no prior)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import ledger as ledger_cli
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "ledger.jsonl")
    rc = ledger_cli.main(["train", "--path", path,
                          "--rows", "400", "--features", "6"])
    assert rc == 0
    kinds = [e["kind"] for e in obs_ledger.read_entries(path)]
    assert "bench" in kinds      # the gated entry
    assert ledger_cli.main(["gate", "--path", path,
                            "--rows", "400", "--features", "6"]) == 0
    # second run: two bench entries, gate now actually compares
    assert ledger_cli.main(["train", "--path", path,
                            "--rows", "400", "--features", "6"]) == 0
    assert ledger_cli.main(["gate", "--path", path, "--rows", "400",
                            "--features", "6",
                            "--tolerance", "1000"]) == 0
