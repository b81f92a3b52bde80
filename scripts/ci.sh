#!/usr/bin/env bash
# Tier-1 gate + benchmark smoke.
#
# Catches both functional regressions and *collection-time* breakage
# (e.g. a module importing a package that does not exist yet — the
# failure mode that once shipped with a missing repro.dist).
#
#   scripts/ci.sh            # full tier-1 + table1 smoke
#   scripts/ci.sh --fast     # tier-1 only
#   scripts/ci.sh --dist     # multi-device lane: test_multidevice on 8
#                            # forced host devices (shard_map seq-sharded
#                            # + 2-D pool-sharded paths run for real, not
#                            # only when a developer remembers the flag),
#                            # plus the combine-topology oracle matrix
#                            # (ring/bidir vs flat vs gather oracle) and
#                            # the int8+EF trajectory-equivalence layer
#                            # (lowered wire step vs fp32 baseline, HLO
#                            # wire proof)
#   scripts/ci.sh --chaos    # fault-injection lane: seeded soak of the
#                            # grow-on-demand serving path (random grant
#                            # denials) asserting
#                            # zero token divergence and zero leaked
#                            # blocks
#   scripts/ci.sh --prefix   # prefix-reuse lane: seeded session traffic
#                            # with an 80%-shared system prompt asserting
#                            # >= 2x fewer prefill calls and pinned
#                            # blocks vs the reuse-off oracle, zero
#                            # divergence, zero leaked refcounts
#   scripts/ci.sh --spill    # multi-tier lane: seeded eviction churn
#                            # parking KV in the host tier, asserting
#                            # more resident KV than the HBM pool holds,
#                            # zero token divergence, zero leaks in
#                            # either tier
#   scripts/ci.sh --disagg   # disaggregated-prefill lane: seeded worker
#                            # SIGKILLs mid-prefill asserting journal
#                            # resume and degrade-to-inline fallback are
#                            # token-identical to the inline oracles,
#                            # with zero leaked blocks
#
# Every lane runs to completion and lands in the per-lane summary at
# the bottom; any failing lane makes the whole run exit nonzero (no
# early bail-out hiding later lanes, no green exit over a red lane).
set -uo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

LANES=()
CODES=()

run_lane() {
    local name="$1"; shift
    echo "== ${name} =="
    "$@"
    local rc=$?
    LANES+=("${name}")
    CODES+=("${rc}")
    if [[ ${rc} -ne 0 ]]; then
        echo "-- lane FAILED (exit ${rc}): ${name}"
    fi
}

summary() {
    local fail=0
    echo
    echo "== lane summary =="
    for i in "${!LANES[@]}"; do
        if [[ ${CODES[$i]} -eq 0 ]]; then
            echo "  PASS  ${LANES[$i]}"
        else
            echo "  FAIL  ${LANES[$i]} (exit ${CODES[$i]})"
            fail=1
        fi
    done
    if [[ ${fail} -ne 0 ]]; then
        echo "CI FAILED"
        exit 1
    fi
    echo "CI OK"
    exit 0
}

if [[ "${1:-}" == "--chaos" ]]; then
    run_lane "chaos: grant-denial soak (seed 0)" \
        python scripts/serve_smoke.py --chaos --seed 0
    run_lane "chaos: grant-denial soak (seed 1)" \
        python scripts/serve_smoke.py --chaos --seed 1
    summary
fi

if [[ "${1:-}" == "--prefix" ]]; then
    run_lane "prefix: shared-system-prompt reuse vs private oracle (seed 0)" \
        python scripts/serve_smoke.py --prefix --seed 0
    run_lane "prefix: shared-system-prompt reuse vs private oracle (seed 1)" \
        python scripts/serve_smoke.py --prefix --seed 1
    summary
fi

if [[ "${1:-}" == "--spill" ]]; then
    run_lane "spill: host-tier park/promote churn (seed 0)" \
        python scripts/serve_smoke.py --spill --seed 0
    run_lane "spill: host-tier park/promote churn (seed 1)" \
        python scripts/serve_smoke.py --spill --seed 1
    summary
fi

if [[ "${1:-}" == "--disagg" ]]; then
    run_lane "disagg: worker kill mid-prefill -> journal resume + degraded fallback (seed 0)" \
        python scripts/serve_smoke.py --disagg --seed 0
    run_lane "disagg: worker kill mid-prefill -> journal resume + degraded fallback (seed 1)" \
        python scripts/serve_smoke.py --disagg --seed 1
    summary
fi

if [[ "${1:-}" == "--dist" ]]; then
    XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
        run_lane "dist: test_multidevice under 8 forced host devices" \
        python -m pytest -x -q tests/test_multidevice.py
    run_lane "dist: combine-topology matrix (ring/bidir vs flat vs oracle)" \
        python -m pytest -x -q tests/test_multidevice.py \
        -k "combine_topology_matrix or ring_combine"
    run_lane "dist: int8+EF trajectory equivalence vs fp32 (2x4 wire)" \
        python -m pytest -x -q tests/test_train_equivalence.py
    summary
fi

run_lane "tier-1: pytest" python -m pytest -x -q

if [[ "${1:-}" != "--fast" ]]; then
    run_lane "smoke: plan-artifact store round-trip (fresh-process reload)" \
        python scripts/plan_roundtrip_smoke.py
    run_lane "smoke: plan-driven serve (from_plan -> staggered -> idle)" \
        python scripts/serve_smoke.py
    run_lane "smoke: paged serve (block pool, bucketed admission, reclaim)" \
        python scripts/serve_smoke.py --paged
    mkdir -p results
    run_lane "smoke: benchmarks table1 (+ machine-readable rows)" \
        python -m benchmarks.run --only table1 --json results/BENCH_table1.json
fi

summary
