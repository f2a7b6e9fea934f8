#!/usr/bin/env bash
# katib-tpu pre-merge check (ISSUE 6): the static analyzer over the full
# tree, then the lockgraph-instrumented scheduler + telemetry + obslog
# stress smoke. Mirrors what tier-1 enforces (tests/test_static_analysis.py)
# but runs in ~30s for local use:
#
#   scripts/check.sh            # text output
#   scripts/check.sh --json     # analyzer findings as stable-sorted JSON
#
# Exit non-zero on any non-suppressed finding or lock-order cycle.
set -euo pipefail
cd "$(dirname "$0")/.."

FORMAT=text
if [[ "${1:-}" == "--json" ]]; then
    FORMAT=json
fi

echo "== katib-tpu check (static analysis) =="
python -m katib_tpu.analysis.engine katib_tpu --format "$FORMAT"

echo
echo "== katib-tpu analyze smoke (semantic program analysis) =="
JAX_PLATFORMS=cpu python bench.py analyze_latency --smoke

echo
echo "== compile service smoke (AOT amortization) =="
JAX_PLATFORMS=cpu python bench.py compile_amortization --smoke

echo
echo "== fused population smoke (lax.scan PBT sweep vs job-queue driver) =="
JAX_PLATFORMS=cpu python bench.py pbt_fused_throughput --smoke

echo
echo "== vectorized suggestion smoke (batched jitted kernels vs NumPy oracle) =="
JAX_PLATFORMS=cpu python bench.py suggestion_throughput --smoke

echo
echo "== async suggestion pipeline smoke (prefetch buffer vs inline) =="
JAX_PLATFORMS=cpu python bench.py suggestion_pipeline_latency --smoke

echo
echo "== multi-fidelity smoke (ASHA rungs vs flat TPE device-epochs) =="
JAX_PLATFORMS=cpu python bench.py asha_device_seconds --smoke

echo
echo "== model-based multi-fidelity smoke (BOHB KDE vs ASHA, packed promotions, cold-vs-warm) =="
JAX_PLATFORMS=cpu python bench.py bohb_convergence --smoke

echo
echo "== device-plane chaos smoke (seeded wedged probe + mid-sweep revocations, zero lost observations) =="
JAX_PLATFORMS=cpu python bench.py device_chaos_recovery --smoke

echo
echo "== controller-kill chaos smoke (journal-keyed SIGKILLs, lease takeover, checkpoint-preserving recovery) =="
JAX_PLATFORMS=cpu python bench.py controller_kill_recovery --smoke

echo
echo "== sharded control-plane smoke (replica subprocesses over the wire protocol, mid-run SIGKILL failover) =="
JAX_PLATFORMS=cpu python bench.py control_plane_scaling --smoke

echo
echo "== framed control-plane smoke (the same failover phases on the binary ingest plane) =="
JAX_PLATFORMS=cpu KATIB_TPU_INGEST_FRAMED=1 python bench.py control_plane_scaling --smoke

echo
echo "== tenancy control-plane smoke (KATIB_TPU_TENANCY=1 armed under the failover phases: open deployment) =="
JAX_PLATFORMS=cpu KATIB_TPU_TENANCY=1 python bench.py control_plane_scaling --smoke

echo
echo "== distributed-trace smoke (3 tenancy replicas, wire traceparent on both planes, merged cross-replica traces, per-tenant SLO series) =="
JAX_PLATFORMS=cpu BENCH_CP_REPLICAS=3 KATIB_TPU_REPLICAS=3 KATIB_TPU_TENANCY=1 \
    KATIB_TPU_TRACING=1 KATIB_TPU_WIRE_TRACING=1 \
    KATIB_TPU_SLO_OBJECTIVES="default=0.000001" \
    python bench.py control_plane_scaling --smoke


echo
echo "== step-stats smoke (per-step timing plane off vs on, injected gang straggler) =="
JAX_PLATFORMS=cpu python bench.py step_stats_overhead --smoke

echo
echo "== multi-tenant scaling smoke (per-tenant tokens/quotas, adversarial probe, SIGKILL zero-loss) =="
JAX_PLATFORMS=cpu python bench.py multi_tenant_scaling --smoke

echo
echo "== ingest-throughput smoke (streamed observation rows: JSON wire vs framed plane + mid-stream SIGKILL) =="
JAX_PLATFORMS=cpu python bench.py ingest_throughput --smoke

echo
echo "== lockgraph stress smoke (dynamic lock-order) =="
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_scheduler_stress.py::test_parallel_64_throughput_and_cleanup \
    "tests/test_telemetry.py::TestSampler::test_lock_order_under_concurrent_register_sample_scrape" \
    tests/test_obslog_pipeline.py::test_read_your_writes_under_concurrent_writers \
    tests/test_compilesvc.py::test_lockgraph_stress_with_worker_pool_active \
    "tests/test_suggest_vectorized.py::TestAsyncPipeline::test_concurrent_sync_no_duplicates_no_losses" \
    tests/test_static_analysis.py

echo
echo "check.sh: all clean"
