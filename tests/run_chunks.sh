#!/usr/bin/env bash
# Full-green proof in bounded chunks (round 2, item 8).
#
# The suite is compile-bound on a 1-core box: one monolithic pytest run
# exceeds practical tool/CI timeouts, and ad-hoc manual chunking is exactly
# how a red HEAD slipped through in round 1. This script IS the chunking
# discipline: it runs the documented chunks sequentially, each under its
# own timeout, and fails loudly on the first red chunk (or timeout).
#
#   tests/run_chunks.sh            # full suite (not-slow chunks, then slow)
#   tests/run_chunks.sh --fast     # skip the slow chunk (pre-commit loop)
#
# Exit code: 0 = every chunk green; nonzero = the failing chunk's status,
# with the chunk named on stderr. The persistent XLA compile cache
# (conftest.py: $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
# makes warm reruns ~6x faster.
set -u
cd "$(dirname "$0")/.."

FAST=0
[ "${1:-}" = "--fast" ] && FAST=1

# Chunks are groups of test FILES so each stays well under its timeout even
# cold. Every test file must appear in exactly one chunk — verified below
# against the tests/ directory listing, so a new file can't silently dodge
# the runner.
CHUNK_TIMEOUT="${CHUNK_TIMEOUT:-900}"
declare -A CHUNKS
CHUNKS[core]="tests/test_model_mnist.py tests/test_model_zoo.py tests/test_transformer.py tests/test_pallas_flash.py tests/test_pallas_gmm.py tests/test_bench_gate.py tests/test_chip_smoke.py"
CHUNKS[parallel1]="tests/test_collectives.py tests/test_data_parallel.py tests/test_sharding.py tests/test_8b_scale.py tests/test_mesh_attention.py"
CHUNKS[parallel2]="tests/test_context_parallel.py tests/test_pipeline.py tests/test_pipeline_lm.py"
# MoE grew its own chunk in round 5 (ragged grouped-GEMM dispatch tests):
# bundled with parallel2 the pair overran the chunk timeout.
CHUNKS[moe]="tests/test_moe.py tests/test_latent_moe.py"
CHUNKS[train]="tests/test_mnist_convergence.py tests/test_grad_accum.py tests/test_chunked_ce.py tests/test_checkpoint.py tests/test_data.py tests/test_prefetch.py tests/test_metrics.py tests/test_profiling.py tests/test_fusion.py"
CHUNKS[llama]="tests/test_train_llama.py tests/test_generate.py"
CHUNKS[deploy]="tests/test_watch.py tests/test_render.py tests/test_deploy_smoke.py tests/test_elastic.py tests/test_preemption.py tests/test_cluster_e2e.py"
CHUNKS[serve]="tests/test_serve.py tests/test_prefix_cache.py tests/test_telemetry.py tests/test_events_schema.py"
# Multi-tenant scheduler: mostly model-free policy tests plus a handful of
# engine-integration cases (own tiny-model compile), split out so the serve
# chunk stays under its timeout.
CHUNKS[sched]="tests/test_sched.py"
# Paged KV arena: PagePool unit tests plus engine-integration cases that
# compile their own tiny model — split from serve so that chunk stays
# under its timeout.
CHUNKS[paged]="tests/test_paged_kv.py"
# The chaos matrix spawns real training gangs (subprocess per attempt), so
# it gets its own chunk rather than riding in deploy.
CHUNKS[faults]="tests/test_faults.py"
# graftlint (pure-AST, no jax at analysis time): cheap, so it runs first —
# a schema/axis/hot-path regression fails in seconds, not after compiles.
# test_analysis.py auto-parametrizes its fixture matrix and CLI contract
# over PASS_IDS, so the graftguard passes (lock-discipline and
# resource-lifecycle, --changed/--explain/--json) run here too.
CHUNKS[lint]="tests/test_analysis.py"
# graftguard fix regressions (stats/gateway thread-safety races need real
# threads; the import-rollback case compiles its own tiny model) — ride
# with lint so the concurrency layer fails early as one unit.
CHUNKS[guard]="tests/test_graftguard_fixes.py"
# graftscope (telemetry analysis plane): mostly jax-free timeline/parser
# tests plus engine-integration request-trace cases that compile their own
# tiny model — split from serve so that chunk stays under its timeout.
CHUNKS[graftscope]="tests/test_graftscope.py"
# Fleet observability (scraper/aggregator/SLO burn rates): jax-free unit
# tests plus the chaos case's two live in-process exporter replicas —
# real (small) sleeps, so it gets its own chunk.
CHUNKS[fleet]="tests/test_fleet.py"
# Failover gateway chaos matrix (serve/gateway.py): multi-replica engines
# compiling their own tiny models plus breaker-timing sleeps — its own
# chunk so serve/sched stay under their timeouts.
CHUNKS[gateway]="tests/test_gateway.py"
# Speculative decoding bit-parity matrix + the Pallas paged decode-
# attention kernel (interpret mode on CPU): both compile their own draft/
# target engines, so they get their own chunk. test_tpu_compile.py compiles
# that kernel for a described v5e (Mosaic, no chip): it loads the TPU's
# library, so it stays the one file of its kind.
CHUNKS[spec]="tests/test_spec.py tests/test_pallas_paged_attn.py tests/test_tpu_compile.py"
# graftflight (flight recorder / page ledger / trace stitching): mostly
# jax-free unit tests plus engine+gateway chaos cases that compile their
# own tiny models — its own chunk so serve/gateway stay under timeout.
CHUNKS[flight]="tests/test_flight.py"
# graftwire (cross-process replica transport): jitter/fault-site units run
# jax-free, but the remote-gateway parity and replica-kill cases compile
# real engines behind ReplicaServer threads — its own chunk, and the slow
# marker holds the subprocess SIGTERM-drain e2e (three CLI processes).
CHUNKS[transport]="tests/test_transport.py"
# graftpilot (serve/autoscale.py fleet controller): fake-clock chaos matrix
# runs jax-free, but the bit-identical mid-decode removal case compiles a
# real multi-replica fleet — its own chunk so gateway stays under timeout.
CHUNKS[autoscale]="tests/test_autoscale.py"
# graftsplit (serve/disagg.py disaggregated prefill/decode): codec and
# coordinator-routing units run jax-free, but the parity/chaos matrix
# compiles prefill+decode engines (some behind ReplicaServer threads) —
# its own chunk so transport/gateway stay under their timeouts.
CHUNKS[disagg]="tests/test_disagg.py"
# graftstorm (serve/storm.py chaos soak): seeded-replay and invariant-
# monitor tests run on scripted jax-free engines, plus one real-engine
# disagg soak that compiles its own tiny model — its own chunk so
# gateway/disagg stay under their timeouts.
CHUNKS[storm]="tests/test_storm.py"
# graftmesh (tensor-parallel serving): the tp=2 parity matrix compiles
# every engine program three times (tp 0/1/2) under shard_map — its own
# chunk so serve/spec stay under their timeouts.
CHUNKS[tp]="tests/test_tp_serve.py"
# graftquant (int8 KV pages + int8 serving weights): kernel-vs-reference
# numerics, the greedy-agreement gate, and a composition matrix (spec/
# prefix/chunked/disagg/tp=2) that compiles several quant engines — its
# own chunk so serve/spec/tp stay under their timeouts.
CHUNKS[quant]="tests/test_quant.py"
CHUNKS[slow1]="tests/test_train_e2e.py tests/test_multiprocess.py"
CHUNKS[slow2]="tests/test_multihost_train.py tests/test_multihost_llama.py tests/test_train_zoo.py"
ORDER=(lint guard core parallel1 parallel2 moe train llama deploy serve sched paged faults graftscope fleet gateway spec flight transport autoscale disagg storm tp quant slow1 slow2)

# --- completeness check: every tests/test_*.py in EXACTLY one chunk ------
# ...and every declared chunk actually in ORDER: a chunk missing from the
# run order would exit green while silently never executing its files
# (caught by review in round 5 — the freshly-split moe chunk did exactly
# that for one run).
for name in "${!CHUNKS[@]}"; do
    case " ${ORDER[*]} " in
        *" $name "*) ;;
        *) echo "run_chunks.sh: chunk '$name' declared but not in ORDER" >&2
           exit 3;;
    esac
done
listed=$(echo "${CHUNKS[@]}" | tr ' ' '\n' | sort)
actual=$(ls tests/test_*.py | sort)
missing=$(comm -23 <(echo "$actual") <(echo "$listed"))
if [ -n "$missing" ]; then
    echo "run_chunks.sh: test files not assigned to any chunk:" >&2
    echo "$missing" >&2
    exit 3
fi
dupes=$(echo "$listed" | uniq -d)
if [ -n "$dupes" ]; then
    echo "run_chunks.sh: test files assigned to MULTIPLE chunks (would run twice):" >&2
    echo "$dupes" >&2
    exit 3
fi

# Two passes over EVERY chunk: fast tests first (-m "not slow"), then —
# unless --fast — the slow-marked tests of the same files. Slow tests live
# in many files (8B compile checks, CLI e2e, long-context CP), so scoping
# the slow pass to designated "slow files" would silently skip the rest.
run_chunk() {  # $1 = chunk name, $2 = marker expression, $3 = label
    echo "=== chunk: $3 ==="
    timeout "$CHUNK_TIMEOUT" python -m pytest ${CHUNKS[$1]} -q -m "$2"
    rc=$?
    [ $rc -eq 5 ] && rc=0   # pytest 5 = no tests matched the marker: fine
    if [ $rc -ne 0 ]; then
        if [ $rc -eq 124 ]; then
            echo "run_chunks.sh: chunk '$3' TIMED OUT (${CHUNK_TIMEOUT}s)" >&2
        elif [ $rc -gt 128 ]; then
            echo "run_chunks.sh: chunk '$3' KILLED by signal $((rc - 128))" >&2
        else
            echo "run_chunks.sh: chunk '$3' FAILED (rc=$rc)" >&2
        fi
    fi
    return $rc
}

overall=0
for name in "${ORDER[@]}"; do
    run_chunk "$name" "not slow" "$name" || { overall=$?; break; }
done
if [ $overall -eq 0 ] && [ "$FAST" != 1 ]; then
    for name in "${ORDER[@]}"; do
        run_chunk "$name" "slow" "$name (slow)" || { overall=$?; break; }
    done
fi

if [ $overall -eq 0 ]; then
    echo "run_chunks.sh: all chunks green$([ "$FAST" = 1 ] && echo ' (fast mode: slow chunks skipped)')"
fi
exit $overall
