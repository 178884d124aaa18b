#!/usr/bin/env bash
# One-command regeneration of a LOCAL golden ledger
# (tests/goldens/LEDGER_flagship.json; ignored by git, none is committed).
#
#   bash scripts/refresh_ledger.sh            # regenerate; REFUSES on metric regressions
#   bash scripts/refresh_ledger.sh --force    # overwrite anyway (say why in the commit)
#   bash scripts/refresh_ledger.sh --check    # diff only, write nothing (CI)
#
# Runs on CPU deliberately — the ledger is the perf signal that works
# without a chip (ISSUE 4). scripts/refresh_ledger.py pins the same
# JAX_PLATFORMS/XLA_FLAGS the test suite uses, so the golden and the
# ledger tests/test_ledger.py builds are byte-comparable.
set -euo pipefail
cd "$(dirname "$0")/.."
exec python scripts/refresh_ledger.py "$@"
