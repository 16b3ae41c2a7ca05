#!/usr/bin/env bash
# ci_gate.sh — the one-command CI gate (ISSUE 15 satellite).
#
# Runs, in order:
#   1. tier-1, with the selection, workers and limit of the driver's
#      command (tests/ -m 'not slow', six xdist workers by file, 1470 s;
#      the slow lane is -m slow), and
#   2. the perf-trend regression gate over the checked-in BENCH_*.json
#      and MULTICHIP_r*.json round history (scripts/prove_report.py
#      --trend --gate: last point of every stage/metric series vs the
#      median of its predecessors, 20% + 50 ms noise floor).
#
# With --multihost, a third leg runs the two-process jax.distributed
# parity tests (subprocess pairs over a loopback coordinator — proof
# bytes and Fiat-Shamir checkpoints must be bit-identical gspmd vs
# multi-host shard_map). Slow: real CPU proves per process; not part
# of the default invocation.
#
# With --timeline, a smoke leg drives the distributed-tracing export
# (ISSUE 17): the gateway trace-propagation test produces a traced
# artifact, prove_report.py --check gates it, --timeline --perfetto
# exports Chrome trace-event JSON, and the leg fails when the JSON is
# invalid or the queue-wait span went missing.
#
# With --field, a smoke leg runs the BabyBear backend suite (ISSUE 19)
# plus the FULL-prover babybear parity suite (ISSUE 20): the 2^10
# mini-STARK e2e under BOOJUM_TPU_FIELD=babybear, and the real
# PLONKish prove() at 2^10 on the fma / xor4-lookup / poseidon-rf
# circuits — device vs numpy proof bytes and checkpoint streams
# bit-identical, zero limb conversions, quotient identity at z, the
# half-HBM cost sheet, sha256-over-babybear rejected at synthesis.
#
# Exits nonzero when any requested leg fails. Knobs:
#   CI_GATE_TIMEOUT_S     tier-1 budget in seconds (default 1470, the
#                         driver's; the -k kill grace stays 10 s)
#   CI_GATE_THRESHOLD     relative regression threshold (default 0.2)
#   CI_GATE_MH_TIMEOUT_S  --multihost leg budget in seconds (default 3600)
#   CI_GATE_TL_TIMEOUT_S  --timeline leg budget in seconds (default 300)
#   CI_GATE_FD_TIMEOUT_S  --field leg budget in seconds (default 870)
set -u -o pipefail

root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$root"

timeout_s="${CI_GATE_TIMEOUT_S:-1470}"
threshold="${CI_GATE_THRESHOLD:-0.2}"
mh_timeout_s="${CI_GATE_MH_TIMEOUT_S:-3600}"
tl_timeout_s="${CI_GATE_TL_TIMEOUT_S:-300}"
fd_timeout_s="${CI_GATE_FD_TIMEOUT_S:-870}"
multihost=0
timeline=0
fieldleg=0
for arg in "$@"; do
    case "$arg" in
        --multihost) multihost=1 ;;
        --timeline) timeline=1 ;;
        --field) fieldleg=1 ;;
        *)
            echo "ci_gate: unknown argument $arg" \
                 "(supported: --multihost --timeline --field)" >&2
            exit 2
            ;;
    esac
done
rc=0

echo "== ci_gate: tier-1 tests (budget ${timeout_s}s) =="
timeout -k 10 "$timeout_s" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile -p no:randomly
t1_rc=$?
if [ "$t1_rc" -ne 0 ]; then
    echo "ci_gate: tier-1 tests FAILED (rc=$t1_rc)"
    rc=1
else
    echo "ci_gate: tier-1 tests ok"
fi

echo "== ci_gate: perf trend gate =="
# round history: BENCH wrappers + MULTICHIP wrappers (the trend loader
# orders both by round number and groups by machine identity)
history=()
for f in BENCH_r*.json MULTICHIP_r*.json; do
    [ -e "$f" ] && history+=("$f")
done
if [ "${#history[@]}" -eq 0 ]; then
    echo "ci_gate: no BENCH_*/MULTICHIP_* history checked in; skipping gate"
else
    python scripts/prove_report.py --trend "${history[@]}" \
        --gate --gate-threshold "$threshold"
    gate_rc=$?
    # rc=2 = no usable trend points (e.g. every wrapper predates the
    # metric line) — nothing to gate is not a regression
    if [ "$gate_rc" -eq 1 ]; then
        echo "ci_gate: perf trend gate FAILED"
        rc=1
    elif [ "$gate_rc" -eq 2 ]; then
        echo "ci_gate: no usable trend points; gate skipped"
    else
        echo "ci_gate: perf trend gate ok"
    fi
fi

if [ "$timeline" -eq 1 ]; then
    echo "== ci_gate: timeline export leg (budget ${tl_timeout_s}s) =="
    tl_tmp="$(mktemp -d)"
    # the trace-propagation test leaves its gateway artifact under the
    # pytest basetemp; the CLI then stitches + exports it
    timeout -k 10 "$tl_timeout_s" env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_gateway.py -q \
        -k trace_propagation --basetemp "$tl_tmp/pytest" \
        -p no:cacheprovider -p no:xdist -p no:randomly
    tl_rc=$?
    if [ "$tl_rc" -ne 0 ]; then
        echo "ci_gate: timeline leg: trace-propagation test FAILED (rc=$tl_rc)"
        rc=1
    else
        artifact="$(find "$tl_tmp/pytest" -name 'gw.jsonl' | head -n 1)"
        if [ -z "$artifact" ]; then
            echo "ci_gate: timeline leg: no gateway artifact produced"
            rc=1
        else
            python scripts/prove_report.py --check "$artifact" \
                && python scripts/prove_report.py --timeline "$artifact" \
                       --perfetto "$tl_tmp/perfetto.json" \
                && python - "$tl_tmp/perfetto.json" <<'PYEOF'
import json, sys
with open(sys.argv[1]) as f:
    doc = json.load(f)
evs = doc.get("traceEvents")
assert isinstance(evs, list) and evs, "traceEvents missing/empty"
names = {e.get("name") for e in evs}
assert "queue.wait" in names, "queue.wait span missing from export"
print(f"ci_gate: perfetto export ok ({len(evs)} events)")
PYEOF
            if [ $? -ne 0 ]; then
                echo "ci_gate: timeline export leg FAILED"
                rc=1
            else
                echo "ci_gate: timeline export leg ok"
            fi
        fi
    fi
    rm -rf "$tl_tmp"
fi

if [ "$fieldleg" -eq 1 ]; then
    echo "== ci_gate: BabyBear field backend leg (budget ${fd_timeout_s}s) =="
    # the suite itself sets/clears BOOJUM_TPU_FIELD per test; the env
    # stays unset here so the Goldilocks-default tests in the same file
    # see a clean process
    timeout -k 10 "$fd_timeout_s" env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_babybear.py \
        tests/test_bb_full_prover.py -q \
        --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly
    fd_rc=$?
    if [ "$fd_rc" -ne 0 ]; then
        echo "ci_gate: BabyBear field leg FAILED (rc=$fd_rc)"
        rc=1
    else
        echo "ci_gate: BabyBear field leg ok"
    fi
fi

if [ "$multihost" -eq 1 ]; then
    echo "== ci_gate: multihost parity leg (budget ${mh_timeout_s}s) =="
    # -m multihost selects the jax.distributed subprocess-pair tests
    # (registered in conftest.py); BOOJUM_TPU_TWO_PROC_TESTS lifts
    # their default skip
    timeout -k 10 "$mh_timeout_s" env JAX_PLATFORMS=cpu \
        BOOJUM_TPU_TWO_PROC_TESTS=1 \
        python -m pytest tests/test_multihost.py -q -m multihost \
        --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly
    mh_rc=$?
    if [ "$mh_rc" -ne 0 ]; then
        echo "ci_gate: multihost parity leg FAILED (rc=$mh_rc)"
        rc=1
    else
        echo "ci_gate: multihost parity leg ok"
    fi
fi

exit "$rc"
