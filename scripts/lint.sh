#!/usr/bin/env bash
# One-shot gigalint entry point for pre-commit / CI.
#
#   bash scripts/lint.sh            # lint the tree, exit nonzero on findings
#   bash scripts/lint.sh --json     # ONE machine-readable verdict line
#                                   # (other extra args pass through)
#
# Scans gigapath_tpu/ + scripts/ + tests/ — the same scope
# tests/test_gigalint.py enforces on every tier-1 run — honoring the
# GIGALINT_WAIVERS file at the repo root. Also runs a battery of
# selftests, each of which must land on its expected exit code:
#   - obs       (scripts/obs_report.py --selftest): RunLog -> watchdog ->
#               spans -> forced stall -> anomaly engine -> flight dump ->
#               rendered report incl. the per-rank merge and the
#               locktrace-fed "== locks ==" section;
#   - ledger_diff / perf_history: the perf regression + trend verdicts
#               must flip on injected regressions;
#   - GL008/GL012/GL013/GL014/GL015/GL016/GL017: each seeded gigalint
#               fixture must fire (rc=1; 0 or 2 mean the rule went blind
#               or crashed) — negative controls are covered by
#               tests/test_gigalint.py;
#   - GL018     (gigarace): the seeded lock-order-cycle + self-deadlock
#               fixture must fire;
#   - GL019     (gigarace): the seeded guarded-field-race fixture must
#               fire (reads/writes of a lock-guarded attribute outside
#               the lock);
#   - GL020     (gigarace): the seeded signal-path fixture must fire
#               (blocking acquire / print reachable from a signal
#               handler instead of the *_from_signal try-acquire
#               surface);
#   - GL021     (gigarace): the seeded blocking-under-lock fixture must
#               fire (join/wait/sleep while holding a lock);
#   - GL022     the seeded untraced-dist-span fixture must fire
#               (span() in dist/ library code without trace=ctx never
#               reaches the fleet's merged timeline);
#   - GL023     the seeded running-moments fixture must fire (by-hand
#               Welford triple in library code instead of the obs
#               accumulators).
#
# Default mode fails fast on the first broken selftest. --json mode runs
# EVERYTHING, then emits a single {"metric": "lint", ..., "decision":
# {...}} line (scripts/lint_json.py) whose decision.ok folds lint
# cleanliness and every selftest together; exit mirrors decision.ok.
set -euo pipefail
cd "$(dirname "$0")/.."

JSON=0
PASS_ARGS=()
for a in "$@"; do
    if [ "$a" = "--json" ]; then
        JSON=1
    else
        PASS_ARGS+=("$a")
    fi
done

SELFTEST_ARGS=()
run_selftest() {  # <name> <expected-rc> <cmd...>
    local name="$1" expect="$2" rc
    shift 2
    set +e
    "$@" 1>&2
    rc=$?
    set -e
    if [ "$rc" -eq "$expect" ]; then
        SELFTEST_ARGS+=(--selftest "$name=pass")
        echo "lint.sh selftest $name OK" 1>&2
    else
        SELFTEST_ARGS+=(--selftest "$name=fail")
        echo "lint.sh selftest $name FAILED: expected rc=$expect, got rc=$rc" 1>&2
        if [ "$JSON" -eq 0 ]; then
            exit 1
        fi
    fi
}

run_selftest obs 0 python scripts/obs_report.py --selftest
run_selftest ledger_diff 0 python scripts/ledger_diff.py --selftest
run_selftest perf_history 0 python scripts/perf_history.py --selftest

# Seeded-fixture selftests: rc=1 (findings) is the ONLY pass — 0 means
# the rule went blind, 2 means it crashed.
run_selftest GL008 1 python -m tools.gigalint --no-waivers --select GL008 \
    tools/gigalint/selftest/fixture/models/timing.py
run_selftest GL012 1 python -m tools.gigalint --no-waivers --select GL012 \
    tools/gigalint/selftest/fixture/models/latency.py
run_selftest GL013 1 python -m tools.gigalint --no-waivers --select GL013 \
    tools/gigalint/selftest/fixture/models/channels.py
run_selftest GL014 1 python -m tools.gigalint --no-waivers --select GL014 \
    tools/gigalint/selftest/fixture/ops/streaming_prefill.py
run_selftest GL015 1 python -m tools.gigalint --no-waivers --select GL015 \
    tools/gigalint/selftest/fixture/models/sockets.py
run_selftest GL016 1 python -m tools.gigalint --no-waivers --select GL016 \
    tools/gigalint/selftest/fixture/models/lowprec.py
run_selftest GL017 1 python -m tools.gigalint --no-waivers --select GL017 \
    tools/gigalint/selftest/fixture/models/dispatch.py
run_selftest GL022 1 python -m tools.gigalint --no-waivers --select GL022 \
    tools/gigalint/selftest/fixture/dist/worker.py
run_selftest GL023 1 python -m tools.gigalint --no-waivers --select GL023 \
    tools/gigalint/selftest/fixture/models/moments.py

# gigarace (lock-discipline) seeded fixtures — same rc=1 contract
run_selftest GL018 1 python -m tools.gigalint --no-waivers --select GL018 \
    tools/gigarace/selftest/fixture/deadlock.py
run_selftest GL019 1 python -m tools.gigalint --no-waivers --select GL019 \
    tools/gigarace/selftest/fixture/races.py
run_selftest GL020 1 python -m tools.gigalint --no-waivers --select GL020 \
    tools/gigarace/selftest/fixture/sigpath.py
run_selftest GL021 1 python -m tools.gigalint --no-waivers --select GL021 \
    tools/gigarace/selftest/fixture/joinwait.py

if [ "$JSON" -eq 1 ]; then
    LINT_OUT="$(mktemp)"
    trap 'rm -f "$LINT_OUT"' EXIT
    set +e
    python -m tools.gigalint --json --strict-waivers \
        gigapath_tpu scripts tests \
        ${PASS_ARGS[@]+"${PASS_ARGS[@]}"} > "$LINT_OUT"
    set -e
    exec python scripts/lint_json.py "${SELFTEST_ARGS[@]}" < "$LINT_OUT"
fi

exec python -m tools.gigalint --strict-waivers gigapath_tpu scripts tests \
    ${PASS_ARGS[@]+"${PASS_ARGS[@]}"}
