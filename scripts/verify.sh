#!/usr/bin/env bash
# One exit-code-honest verification gate (see STATIC_ANALYSIS.md):
#   invariant linter -> ruff -> mypy -> compileall floor -> tier-1 pytest
#
# Every step that RUNS contributes to the exit code; a tool that is not
# installed in this image is skipped LOUDLY (ruff/mypy may be absent in
# the hermetic container — their configs in pyproject.toml apply wherever
# they do exist). `make analyze` (gcc -fanalyzer + cppcheck/clang-tidy)
# is a separate, slower gate: run it when touching _native/.
#
# Usage: scripts/verify.sh          (from anywhere; cd's to the repo root)
set -uo pipefail
cd "$(dirname "$0")/.."

fail=0
step() { printf '\n== %s ==\n' "$1"; }

step "native invariant linter (scripts/check_native.py)"
python scripts/check_native.py || fail=1

step "escape audit (scripts/check_native.py --escapes)"
# Every `eg-lint: allow(...)` escape must still suppress something —
# a stale escape is a waiver nobody is using that will waive the NEXT
# real violation on that line.
python scripts/check_native.py --escapes || fail=1

step "cross-layer contract analyzer (scripts/check_contracts.py)"
# ABI/wire/ledger/config parity + lock discipline + artifact hygiene
# (STATIC_ANALYSIS.md "Cross-layer contracts").
python scripts/check_contracts.py || fail=1

step "ruff"
if command -v ruff >/dev/null 2>&1; then
  ruff check euler_tpu scripts tests examples || fail=1
else
  echo "SKIPPED: ruff not installed in this image (config: pyproject.toml [tool.ruff])"
fi

step "mypy"
if command -v mypy >/dev/null 2>&1; then
  mypy euler_tpu || fail=1
else
  echo "SKIPPED: mypy not installed in this image (config: pyproject.toml [tool.mypy])"
fi

step "remote-bench smoke (scripts/remote_bench.py --smoke)"
# End-to-end remote hot path against a real in-process 2-shard cluster:
# asserts the dedup/cache ledger shows a real ids-on-wire reduction, so
# a silent coalescing regression fails verify before it reaches PERF.md.
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/remote_bench.py --smoke >/dev/null || fail=1

step "chaos soak + failpoint counters (FAULTS.md)"
# Runs the fault-injection suites by name so a transport regression
# fails fast with a targeted log, before the full tier-1 sweep below
# (which includes them again as ordinary members).
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_fault_injection.py tests/test_chaos_soak.py -q \
  -p no:cacheprovider || fail=1

step "telemetry + step-phase profiler suites + scrape/trace smokes (OBSERVABILITY.md)"
# Histograms/trace spans/STATS scrape + the step-phase profiler: the
# deterministic-bucket, stall-attribution, and scrape-parity pins, then
# a real metrics_dump scrape and a trace_dump Perfetto export against a
# live 2-shard cluster — a silent telemetry regression fails verify
# before any perf PR cites its numbers.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_telemetry.py tests/test_phase_profiler.py -q \
  -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/metrics_dump.py --smoke >/dev/null || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/trace_dump.py --smoke >/dev/null || fail=1

step "data-plane heat: sketch exactness + doc-drift gate + skew-report smoke (OBSERVABILITY.md 'Data-plane heat')"
# The eg_heat access profiler: space-saving/count-min exactness pins,
# the ids ledger identity on a live cluster, the metric-name doc-drift
# gate (every eg_* family emitted by metrics_text() must be in the
# OBSERVABILITY.md glossary and vice versa), then a real heat_dump skew
# report against a 2-shard cluster — ROADMAP item 5's pre-measurement
# instrument cannot silently rot.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_heat.py tests/test_metric_docs.py -q \
  -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/heat_dump.py --smoke >/dev/null || fail=1

step "locality: placement routing + frequency-aware caches + A/B smoke (PERF.md 'Locality')"
# The ROADMAP item 5 layer: degree-aware partitioner validation, exact
# TinyLFU admit/reject ledgers, neighbor-cache promotion arithmetic,
# and the live hash-vs-placement A/B (edge-cut strictly down on the
# same graph) — a silent locality regression fails verify before any
# PR cites the edge-cut numbers.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_locality.py -q -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/heat_dump.py --ab-smoke >/dev/null || fail=1

step "blackbox postmortem drill (OBSERVABILITY.md 'Postmortems')"
# The flight-recorder/crash-dump suites by name, then the incident
# drill: a seeded crash failpoint kills a live shard, the postmortem is
# collected and merged with the client trace by trace id — a silent
# regression in the forensic path fails verify before the incident
# that needed it.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_blackbox.py -q -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/postmortem.py --smoke >/dev/null || fail=1

step "rolling-restart drill + connection storm + wire fuzz (DEPLOY.md runbook)"
# Server-side survivability: SIGTERM-drain/restart of every shard
# mid-training with zero failed calls, BUSY load-shedding under a
# 32-client storm, and malformed-frame/wire-version fuzzing against a
# live service.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_rolling_restart.py tests/test_wire_fuzz.py -q \
  -p no:cacheprovider || fail=1

step "snapshot epochs: delta flips + failpoint arithmetic + live flip drill (DEPLOY.md 'Rolling graph refresh')"
# eg_epoch: whole-step consistency under the depth-2 async ring, exact
# delta_load/epoch_flip failpoint counters, contradictory-delta
# refusals, then the live drill — GraphSAGE training while each shard
# flips mid-flight, zero failed calls, loss parity on the unchanged
# subgraph, post-flip reads bit-identical to a fresh merged load.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_epoch.py -q -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/epoch_drill.py --smoke >/dev/null || fail=1

step "serve: micro-batch parity + shedding + closed-loop load drill (DEPLOY.md 'Serving runbook')"
# eg_serve: SLO math + batcher coalescing/shedding/deadline pins, the
# bit-parity contract under concurrent mixed traffic, then the
# closed-loop drill — 16 clients over a live 2-shard cluster, p99
# bounded, shedding proven on a live scrape, served rows bit-identical
# to the direct forward.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_serve.py -q -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/serve_drill.py --smoke >/dev/null || fail=1

step "device plane: recompile attribution + merged-trace drill (OBSERVABILITY.md 'Device plane')"
# eg_devprof: exact recompile arithmetic under injected shape drift,
# kill-switch silence, the serve compile-storm guard on a live drill,
# then the devprof_dump smoke — jit, drift, profiler capture, and a
# validated host+device Perfetto merge — so a silent regression in the
# compile ledger or the trace alignment fails verify first.
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_devprof.py -q -p no:cacheprovider || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  python scripts/devprof_dump.py --smoke >/dev/null || fail=1

step "sanitizer smoke (scripts/sanitize.sh --smoke; SANITIZERS.md)"
# One TSAN round over the fuzz barrage (16 threads of garbage +
# concurrent valid traffic against a live service — the densest
# concurrency per wall-clock second in the tree). The instrumented
# side build under _native/.sanitize/ is incremental, so this is
# seconds once warm; the full round set is scripts/sanitize.sh.
timeout -k 10 600 scripts/sanitize.sh --smoke || fail=1

step "python syntax floor (compileall)"
# stdlib floor under the optional tools above: at minimum, every file parses
python -m compileall -q euler_tpu tests scripts examples || fail=1

step "tier-1 tests (ROADMAP.md)"
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
[ "$rc" -ne 0 ] && fail=1

step "verdict"
if [ "$fail" -ne 0 ]; then
  echo "verify: FAIL"
  exit 1
fi
echo "verify: OK"
