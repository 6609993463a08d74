#!/usr/bin/env bash
# Per-PR gate: tier-1 tests + quick perf smokes (batch server + dataplane).
# Usage: scripts/ci.sh  (from the repo root)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 tests =="
# Fake host devices so the egress pool's shard_map distributed-merge tests
# exercise the real collective path on CPU (subprocess drivers override
# this with their own device counts).  Scoped to the pytest step only, so
# the benchmark steps below keep an unsplit host.
XLA_FLAGS="--xla_force_host_platform_device_count=4 ${XLA_FLAGS:-}" \
    python -m pytest -x -q

echo "== batch benchmark smoke (benchmarks/run.py --quick) =="
python benchmarks/run.py --quick

echo "== device epoch kernels under interpret=True (repro.net.device_epoch) =="
# The whole-epoch device engine's Pallas block-sort kernel, run in
# interpret mode (no TPU in CI), asserted byte-identical to the fused
# engine on a leaf-spine epoch.  A CPU check only: what the
# chip runs is checked by `python chip_smoke.py` on a TPU host.
PYTHONPATH=src python -c \
    "from repro.net import device_self_check; device_self_check(interpret=True)"

echo "== dataplane benchmark smoke (benchmarks/net_bench.py --quick) =="
# --quick shrinks the matrix trace to 100k values; the hop-throughput
# microbench, the server-pool scaling sweep, and the server merge-backend
# sweep still run on full 1M-key traces (the ISSUE 3 / ISSUE 4 / ISSUE 5
# acceptance workloads), and the end-to-end device-residency sweep keeps
# its full 10M-key payload-attached run (ISSUE 8 — per-hop dispatch
# overhead only shows at scale).  The scaling
# sweep's tier-1 twin (tests/test_pool_property.py, ~4x structural margin)
# is marked `slow` so developers can deselect it with -m 'not slow'; the
# tier-1 step above still runs it, and this gate is the deterministic
# 1M-key backstop.
python benchmarks/net_bench.py --quick --faithful-check --out BENCH_net.json

echo "== BENCH_net.json schema + gates (benchmarks/emit.py) =="
# sampled ranges >= 0.8x oracle reduction (ISSUE 2); fused hop engine
# >= 3x the per-segment numpy path (ISSUE 3); the 4-server egress pool
# strictly beats the single server's makespan on 1M keys (ISSUE 4); the
# run-arena merge engine >= 2x the numpy ladder on the same 1M-key
# delivered wire (ISSUE 5); the recording tracer stays near-free over the
# null-tracer end-to-end pipeline on the 1M-key wire (ISSUE 6 — budget
# re-justified at 1.10 from 1.05: the interleaved min-over-repeats ratio
# of two ~0.5s runs swings +-3-5% on the CI container, measured 0.93x at
# PR 6 time and ~1.01-1.05x since; a real leak, e.g. INT stamping's
# ~1.6x, still trips the gate); every
# network-timing-sweep cell (link rate x buffer depth grid under 2% wire
# loss) delivers output byte-identical to the lossless run — loss costs
# time, never keys (ISSUE 7); the whole-epoch device engine >= 2x the
# per-hop fused path's keys/sec on the 10M-key payload-attached tree run
# (ISSUE 8); at J=4 concurrent tenants every job's epoch share reaches
# >= 0.5 of fair (the round-robin scheduler is structurally 1.0) and every
# tenant's output is byte-identical to its solo run (ISSUE 9); every
# fail-open fault-ladder run (degraded/crashed hops, shard failover,
# corrupted range table) is byte-identical to the fault-free run, and one
# hop in pass-through keeps >= 0.5x the fault-free throughput (ISSUE 10 —
# faults cost throughput, never keys, and degradation is graceful down to
# the all-pass-through plain-sort floor).
python benchmarks/emit.py BENCH_net.json --min-sampled-ratio 0.8 \
    --min-hop-speedup 3.0 --min-server-scaling 1.0 \
    --min-server-speedup 2.0 --max-trace-overhead 1.10 \
    --require-lossless-identical --min-e2e-speedup 2.0 \
    --min-tenant-fairness 0.5 --require-fault-identical \
    --min-degraded-ratio 0.5

echo "== benchmark report render (benchmarks/report.py) =="
python benchmarks/report.py BENCH_net.json

echo "CI OK"
