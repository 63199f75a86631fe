#!/usr/bin/env bash
# Per-file-chunk tier-1 runner: the documented fallback when a wall
# `pytest tests/` run wedges with ZERO failures on the pre-existing XLA:CPU
# rendezvous idle hang (KNOWN_FAILURES.md "idle hang in hybrid collective
# tests"; its commit-time gate is analysis A102). PRs 9 and 10 both
# re-invented this loop by hand — this is the one copy.
#
# Each test file runs in its OWN pytest process with its own timeout, so a
# wedged process loses one file's budget instead of the whole wall run, and
# the per-file results still sum to the tier-1 verdict (same flags as the
# ROADMAP tier-1 line: -m 'not slow', no cacheprovider/xdist/randomly).
#
# Usage: scripts/run_tier1_chunked.sh [--changed-only [BASE_REF]] [per-file-timeout-seconds]
#   --changed-only        run only the test files touching modified modules:
#                         test files that changed themselves, plus every test
#                         file that imports (or names) a changed mlsl_tpu
#                         module. The pre-commit fast path (KNOWN_FAILURES.md)
#                         — heavy suites like the elastic soak only run when
#                         their layer actually changed. BASE_REF defaults to
#                         HEAD (i.e. the working-tree diff); pass a ref to
#                         diff a branch.
#   MLSL_T1_RETRY_HUNG=1  re-run a timed-out file once before recording it
#                         (the hang is a coin-flip; a clean retry means the
#                         file is green, not wedged)
set -u
cd "$(dirname "$0")/.."

CHANGED_ONLY=0
BASE_REF="HEAD"
if [ "${1:-}" = "--changed-only" ]; then
    CHANGED_ONLY=1
    shift
    case "${1:-}" in
        ''|*[!0-9]*) if [ -n "${1:-}" ]; then BASE_REF="$1"; shift; fi ;;
    esac
fi

PER_FILE_TIMEOUT="${1:-300}"
RETRY_HUNG="${MLSL_T1_RETRY_HUNG:-1}"
LOGDIR="${MLSL_T1_LOGDIR:-/tmp/mlsl_tier1_chunks}"
mkdir -p "$LOGDIR"

select_changed_files() {
    # changed files = working tree vs BASE_REF, plus untracked
    local changed
    changed=$( { git diff --name-only "$BASE_REF" -- 2>/dev/null;
                 git ls-files --others --exclude-standard; } | sort -u)
    [ -z "$changed" ] && return 0
    # module stems a test file might import/name: mlsl_tpu/comm/mesh.py ->
    # "mesh"; changed test files are selected directly
    local stems=""
    local f s
    for f in $changed; do
        case "$f" in
            # fixture/harness config affects EVERY test file — a changed
            # autouse fixture must not sail through with zero tests selected
            tests/conftest.py|pytest.ini|pyproject.toml|setup.cfg)
                ls tests/test_*.py 2>/dev/null
                return 0 ;;
            # a DELETED test file is still listed by the diff; feeding it to
            # pytest would record a spurious failure
            tests/test_*.py) [ -f "$f" ] && echo "$f" ;;
            # the PR 17 kernel family: each ops kernel module is pinned by
            # its test_pallas_* twin AND by the analysis accounting mirror
            # sweep/fixtures — name them explicitly so an import-alias
            # rename in a test file cannot silently drop the pairing
            mlsl_tpu/ops/rhd_kernels.py)
                printf '%s\n' tests/test_pallas_rhd.py tests/test_analysis.py
                stems="$stems rhd_kernels" ;;
            mlsl_tpu/ops/a2a_kernels.py)
                printf '%s\n' tests/test_pallas_a2a.py tests/test_analysis.py
                stems="$stems a2a_kernels" ;;
            mlsl_tpu/ops/ring_kernels.py)
                printf '%s\n' tests/test_pallas_ring.py \
                    tests/test_analysis.py tests/test_overlap_compiled.py
                stems="$stems ring_kernels" ;;
            # the codec lab: registry members and the calibration autotuner
            # are pinned by test_codec_lab AND the A115/A116 geometry sweep
            # in test_analysis — name the twins explicitly so an import
            # alias in a test file cannot silently drop the pairing
            mlsl_tpu/codecs/*.py|mlsl_tpu/tuner/calibrate.py)
                printf '%s\n' tests/test_codec_lab.py tests/test_analysis.py
                stems="$stems codecs" ;;
            # known-bad analysis fixtures are exercised only by test_analysis
            tests/fixtures/*) printf '%s\n' tests/test_analysis.py ;;
            # a kept measurement tool is pinned by the test that names it
            # (algo_sweep_bench -> test_algos)
            benchmarks/*.py) stems="$stems $(basename "$f" .py)" ;;
            mlsl_tpu/*.py|mlsl_tpu/*/*.py|mlsl_tpu/*/*/*.py)
                s=$(basename "$f" .py)
                # a package __init__ is named by its package (tuner, algos)
                [ "$s" = "__init__" ] && s=$(basename "$(dirname "$f")")
                stems="$stems $s" ;;
        esac
    done
    [ -z "$stems" ] && return 0
    local pat=""
    for s in $stems; do
        pat="$pat${pat:+|}$s"
    done
    # a test file is affected when it mentions any changed module stem as a
    # word (import, attribute, or monkeypatch target)
    grep -lE "\b($pat)\b" tests/test_*.py 2>/dev/null || true
}

TEST_FILES="tests/test_*.py"
if [ "$CHANGED_ONLY" = "1" ]; then
    TEST_FILES=$(select_changed_files | sort -u)
    if [ -z "$TEST_FILES" ]; then
        echo "--changed-only: no test files affected by the diff vs $BASE_REF"
        echo "DOTS_PASSED=0"
        exit 0
    fi
    echo "--changed-only vs $BASE_REF: $(echo "$TEST_FILES" | wc -w) file(s)"
fi

failed_files=()
hung_files=()
total_passed=0

run_file() {
    local f="$1" log="$2"
    timeout -k 10 "$PER_FILE_TIMEOUT" \
        env JAX_PLATFORMS=cpu python -m pytest "$f" -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
        -p no:randomly >"$log" 2>&1
}

for f in $TEST_FILES; do
    log="$LOGDIR/$(basename "$f" .py).log"
    run_file "$f" "$log"
    rc=$?
    if [ "$rc" -eq 124 ] && [ "$RETRY_HUNG" = "1" ]; then
        echo "RETRY (timeout) $f" >&2
        run_file "$f" "$log"
        rc=$?
    fi
    passed=$(grep -aEo '[0-9]+ passed' "$log" | tail -1 | grep -aEo '[0-9]+' || echo 0)
    total_passed=$((total_passed + passed))
    if [ "$rc" -eq 124 ]; then
        hung_files+=("$f")
        echo "HUNG   $f (>${PER_FILE_TIMEOUT}s; log: $log)"
    elif [ "$rc" -ne 0 ] && [ "$rc" -ne 5 ]; then
        # rc 5 = no tests collected under the marker filter: not a failure
        failed_files+=("$f")
        echo "FAIL   $f (rc=$rc; log: $log)"
    else
        echo "OK     $f ($passed passed)"
    fi
done

echo "----"
echo "DOTS_PASSED=$total_passed"
if [ "${#failed_files[@]}" -gt 0 ]; then
    echo "FAILED FILES: ${failed_files[*]}"
fi
if [ "${#hung_files[@]}" -gt 0 ]; then
    echo "HUNG FILES (rendezvous-hang suspects; see KNOWN_FAILURES.md):" \
         "${hung_files[*]}"
fi
[ "${#failed_files[@]}" -eq 0 ] && [ "${#hung_files[@]}" -eq 0 ]
