"""Benchmark driver — prints ONE JSON line with the headline metric.

Headline: end-to-end `train` throughput (rows/sec) of the flagship NN trainer
on a synthetic fraud-style dataset, vs the YARN-cluster-derived baseline.
Runs on whatever jax.devices() offers (one real TPU chip under the driver).

``--plane tail`` runs ONLY the disk-tail streamed-GBT benchmark (the
out-of-core ingest path) — seconds instead of minutes, for iterating on
the spill-cache / H2D pipeline in isolation.

``--compare OLD.json NEW.json [--threshold 0.9]`` runs NO benchmark:
it diffs two recorded payloads (raw bench output or the driver's
BENCH_r0N wrappers) metric-by-metric, prints a regression table, and
exits 2 when any tracked throughput metric fell below threshold x old
or any tracked latency metric (*_p50*/*_p99* — lower is better) rose
above old / threshold — the reader for the in-repo BENCH_r01..
trajectory.

With SHIFU_TPU_TELEMETRY=1 the per-plane numbers also land as a telemetry
JSONL block under ./telemetry/ (same schema as the pipeline steps — the
schema-version handshake is enforced inside run_benchmark, which fails
loudly on a bench/obs schema mismatch).
"""

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--plane",
                    choices=("all", "tail", "rf-repeat", "e2e", "resume",
                             "varsel", "serve", "fleet", "overload",
                             "multihost", "refresh", "quality",
                             "ingest"),
                    default="all",
                    help="'tail' = quick disk-tail streamed-GBT bench; "
                         "'rf-repeat' = RF variance triage (cold-compile "
                         "vs warm-window decomposition); 'e2e' = scripted "
                         "init->stats->norm->train(GBT+NN)->eval rehearsal "
                         "(SHIFU_BENCH_E2E_ROWS sets the row count, "
                         "default 10M); 'resume' = restart-recovery "
                         "overhead (time-to-first-tree from a mid-forest "
                         "checkpoint vs cold/warm starts); 'varsel' = "
                         "streamed mask-batched SE sensitivity vs the "
                         "single-worker per-column loop at identical "
                         "selections; 'serve' = online-serving plane "
                         "(AOT padded-bucket scorer + micro-batcher: "
                         "sustained QPS, p50/p99 per offered load, "
                         "zero-recompile guard); 'fleet' = subprocess "
                         "replica fleets behind the HTTP router "
                         "(1/2/4-replica aggregate QPS + the replica-"
                         "SIGKILL requeue drill); 'overload' = overload-"
                         "protection plane (bounded-admission server at "
                         "1x/2x/4x of measured saturation: goodput "
                         "guarded >= 0.8x saturation at 2x offered "
                         "load, coded sheds, zero hung clients); "
                         "'multihost' = elastic "
                         "multi-controller plane (1/2/4-process quorum-"
                         "gated scaling curve + time-to-recover after a "
                         "mid-train controller kill); 'refresh' = "
                         "continual-refresh plane (drift-triggered warm "
                         "retrain time-to-promoted vs a cold full-"
                         "pipeline retrain on the same drifted stream, "
                         "with a no-SLO-page-during-swap guard); "
                         "'quality' = model-quality observability plane "
                         "(scorelog on-vs-off saturation QPS, guarded "
                         ">= 0.95x, + time-to-detect a synthetic "
                         "label flip via the live-AUC monitor); "
                         "'ingest' = one-parse offline pipeline "
                         "(serial-vs-pooled stats+norm wall-clock on "
                         "the same generated shards: stats_throughput/"
                         "norm_throughput are the pooled raw-rows/sec, "
                         "SHIFU_BENCH_INGEST_ROWS sets the row count, "
                         "default 2M)")
    ap.add_argument("--compare", nargs="*", metavar="PAYLOAD.json",
                    default=None,
                    help="regression-diff two bench payloads (raw JSON "
                         "lines or BENCH_r0N wrappers) metric-by-metric; "
                         "exits 2 when any tracked throughput metric "
                         "falls below --threshold x old — runs NO "
                         "benchmark.  With NO arguments, auto-diffs the "
                         "two newest BENCH_r*.json in the repo root "
                         "(errors cleanly when fewer than two exist)")
    ap.add_argument("--threshold", type=float, default=0.9,
                    help="--compare regression threshold (default 0.9: "
                         "new >= 0.9 x old passes)")
    args = ap.parse_args()

    from shifu_tpu import compile_cache
    compile_cache.configure()

    if args.compare is not None:
        from shifu_tpu.bench import resolve_compare_paths, run_compare
        try:
            old_path, new_path = resolve_compare_paths(args.compare)
        except ValueError as e:
            print(f"bench: {e}", file=sys.stderr)
            sys.exit(2)
        sys.exit(run_compare(old_path, new_path,
                             threshold=args.threshold))

    from shifu_tpu import obs
    from shifu_tpu.bench import run_benchmark

    try:
        result = run_benchmark(plane=args.plane)
    except RuntimeError as e:
        # schema-version handshake failure (bench/obs drift) must land as
        # a nonzero exit for CI, not a stack trace mistaken for a crash
        if "schema" in str(e):
            print(f"bench: {e}", file=sys.stderr)
            sys.exit(2)
        raise
    if obs.enabled():
        # the bench gauges land in BOTH formats: the JSONL trace block
        # and the same OpenMetrics/JSON snapshot the steps export, so an
        # external scraper and BENCH_r0N consumers read one schema
        obs.write_metrics_files("telemetry", step="BENCH")
        obs.flush("telemetry/trace.jsonl", step="BENCH",
                  extra_meta={"headline": result["metric"]})
    print(json.dumps(result))


if __name__ == "__main__":
    main()
