#!/usr/bin/env bash
# Local CI gate: tier-1 tests + the serving example + a fuzz sweep.
#
#   scripts/check.sh                 # full suite + example + fuzz smoke
#   scripts/check.sh --fast          # tier-1 tests only (quick pre-push loop)
#   scripts/check.sh -k serve        # pass pytest args through
#   JUNIT_XML=out.xml scripts/check.sh   # also write pytest junit XML (CI)
#
# Serving correctness (bit-exact streams, zero drops, traces that pass
# trace_tool --check) is asserted by the tier-1 tests; serving speed is
# measured on the chip by bench/run.py, never here.
#
# Runs every stage even if an earlier one fails, prints per-stage wall-clock
# timing, then exits nonzero if any stage did.
set -uo pipefail
cd "$(dirname "$0")/.."

status=0
FAST=0
PYTEST_ARGS=()
for arg in "$@"; do
    case "$arg" in
        --fast) FAST=1 ;;
        *) PYTEST_ARGS+=("$arg") ;;
    esac
done

# stage NAME CMD...: run CMD, report [stage] NAME: OK|FAILED (12.3s)
stage() {
    local name="$1"; shift
    local t0 t1
    t0=$(date +%s.%N)
    if "$@"; then
        local rc=0
    else
        local rc=1
        status=1
    fi
    t1=$(date +%s.%N)
    local dt
    dt=$(awk -v a="$t0" -v b="$t1" 'BEGIN { printf "%.1f", b - a }')
    if [ "$rc" -eq 0 ]; then
        echo "[stage] $name: OK (${dt}s)"
    else
        echo "[stage] $name: FAILED (${dt}s)"
    fi
    echo
}

run_pytest() {
    local junit=()
    if [ -n "${JUNIT_XML:-}" ]; then
        junit=(--junitxml "$JUNIT_XML")
    fi
    python -m pytest -q ${junit[@]+"${junit[@]}"} \
        ${PYTEST_ARGS[@]+"${PYTEST_ARGS[@]}"}
}

run_example() { python examples/serve_with_faults.py > /dev/null; }

echo "== tier-1: python -m pytest -q ${PYTEST_ARGS[*]:-} =="
stage "tier-1 tests" run_pytest

if [ "$FAST" -eq 1 ]; then
    echo "(--fast: skipping the example + fuzz smoke)"
    exit $status
fi

echo "== serve smoke: examples/serve_with_faults.py =="
stage "serve smoke" run_example

# every smoke writes its file artifacts (traces, WALs, fuzz state) under
# this gitignored directory — CI uploads it wholesale, the repo root stays
# clean
ARTIFACTS="${REPRO_ARTIFACTS:-artifacts}"

# time-boxed coverage-guided fuzz sweep over two representative engines; a
# nonzero exit means a reproducible counterexample was found (and written to
# tests/fuzz_corpus by a full run — the smoke uses --no-promote so CI never
# commits corpus entries, it only fails loudly and uploads artifacts/fuzz-out/)
run_fuzz_smoke() {
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python scripts/fuzz.py --budget 8 --seed 0 \
        --engines overlap,overlap_paged --time-box 300 --no-promote \
        --db "$ARTIFACTS/fuzz-out/coverage_db.json" \
        --report "$ARTIFACTS/fuzz-out/report.json"
}
echo "== fuzz smoke: scripts/fuzz.py --budget 8 --time-box 300 =="
stage "fuzz smoke" run_fuzz_smoke

if [ "${#PYTEST_ARGS[@]}" -gt 0 ]; then
    # tier-1 was filtered by pass-through args: still guarantee the serving
    # suites ran (an unfiltered tier-1 run already collects them)
    echo "== serve tests: tests/test_serve_{overlap,paged,spec}.py =="
    stage "serve tests" python -m pytest -q tests/test_serve_overlap.py \
        tests/test_serve_paged.py tests/test_page_allocator.py \
        tests/test_serve_spec.py
fi

exit $status
