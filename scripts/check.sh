#!/bin/sh
# Pre-commit gate, layered by cost:
#
#   check.sh            lint (full repo) + lint tests + the fast
#                       serve/online/obs/chip-path tier-1 subset (a few
#                       min CPU; the chip-path parity trains run under
#                       the pallas interpreter)
#   check.sh --fast     lint only files changed vs git + lint tests
#
# Every mode (including --fast) fails on baseline drift: lint.py exits
# nonzero on net-new findings AND on stale lint_baseline.json entries
# (a frozen finding whose source line no longer exists — the baseline
# must shrink monotonically; run scripts/lint.py --update-baseline).
#   check.sh --fleet    lint + lint tests + the fleet/online/serve fast
#                       subset (durability/fairness/rollback plus the
#                       failover/compaction/transport hardening tests,
#                       the fleet-observatory status/trace tests and
#                       the region control-plane suite: remote write
#                       surface, multi-endpoint failover, ingest
#                       forwarding, snapshot bootstrap)
#   check.sh --aot      lint + lint tests + the sandbox AOT pre-flight
#                       (tests/test_aot_tpu.py, slow: the real TPU
#                       compiler over what auto resolves to on a v5e
#                       and over every selectable kernel; no chip
#                       needed, one libtpu process at a time). Run it
#                       after touching ops/, learner.py or fused.py.
#   check.sh --slo      everything above, plus the closed-loop serving
#                       SLO bench gated against SLO_BASELINE.json
#   check.sh --ledger   everything above, plus the run-ledger regression
#                       gate: train the fixed CI workload (appends one
#                       ledger entry) and fail on >25% train-wall
#                       regression vs the previous matching entry
set -e
cd "$(dirname "$0")/.."

LINT_ARGS=""
RUN_SUBSET=1
RUN_FLEET=0
RUN_AOT=0
RUN_SLO=0
RUN_LEDGER=0
case "$1" in
    --fast)   LINT_ARGS="--changed"; RUN_SUBSET=0 ;;
    --fleet)  RUN_SUBSET=0; RUN_FLEET=1 ;;
    --aot)    RUN_SUBSET=0; RUN_AOT=1 ;;
    --slo)    RUN_SLO=1 ;;
    --ledger) RUN_LEDGER=1 ;;
esac

echo "== graftlint =="
python scripts/lint.py $LINT_ARGS

echo "== lint tests =="
JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py -q -m 'not slow'

if [ "$RUN_SUBSET" = 1 ]; then
    echo "== serve/online/obs/linear/chip-path/goss fast tests =="
    JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow' \
        tests/test_serve.py tests/test_online.py \
        tests/test_obs.py tests/test_trace.py \
        tests/test_linear_device.py tests/test_chip_path.py \
        tests/test_goss_compact.py
fi

if [ "$RUN_FLEET" = 1 ]; then
    echo "== fleet/online/serve fast tests =="
    JAX_PLATFORMS=cpu python -m pytest -q -m 'not slow' \
        tests/test_fleet.py tests/test_failover.py \
        tests/test_fleet_obs.py tests/test_control.py \
        tests/test_online.py tests/test_serve.py
fi

if [ "$RUN_AOT" = 1 ]; then
    echo "== AOT pre-flight: compile for v5e without a chip =="
    JAX_PLATFORMS=cpu python -m pytest -q -rxXs tests/test_aot_tpu.py
fi

if [ "$RUN_SLO" = 1 ]; then
    echo "== serving SLO bench (vs SLO_BASELINE.json) =="
    JAX_PLATFORMS=cpu python scripts/slo_bench.py --quick \
        --against SLO_BASELINE.json
fi

if [ "$RUN_LEDGER" = 1 ]; then
    echo "== run-ledger regression gate (scripts/ledger.py) =="
    LEDGER_PATH="${LEDGER_PATH:-lgbtpu_ledger.jsonl}"
    JAX_PLATFORMS=cpu python scripts/ledger.py train --path "$LEDGER_PATH"
    JAX_PLATFORMS=cpu python scripts/ledger.py gate --path "$LEDGER_PATH" \
        --metric extra.train_s --tolerance "${LEDGER_TOLERANCE:-0.25}"
fi
