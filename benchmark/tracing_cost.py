"""What the program's tracing costs when it is on: the wall of a training
cell's job, plain, with ``--telemetry``, and with ``--telemetry`` inside a
``jax.profiler`` session (python tracer off, as a traced run sets it).

    python3 -m benchmark.tracing_cost --workload nn-train --seed <n> --rounds 4

One set-up, one unrecorded job of each kind (each builds other programs),
then ``rounds`` rounds of the three in turn, so that a drift of the machine
falls on all alike.  The last stdout line is one JSON object with every job's
wall and the three medians.  Not a cell and not a metric: the number belongs
in ``PERF.md`` section 3, beside the traced run it explains.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

from . import jobs, run

KINDS = ("plain", "telemetry", "telemetry_profiler")


def one_job(cell: jobs.TrainCell, kind: str, trace_dir: str) -> float:
    import jax
    from shifu_tpu import obs
    if kind == "telemetry_profiler":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        return cell.job(telemetry=kind != "plain")
    finally:
        if kind == "telemetry_profiler":
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            cell.ctx.say(f"stop_trace (outside the job's wall) {time.perf_counter() - t0:.2f}s")
            shutil.rmtree(trace_dir, ignore_errors=True)
        # --telemetry switches the process on and nothing switches it off
        obs.set_enabled(None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.tracing_cost")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell_doc = run.load_cell(args.workload, args.rehearse)
    run.setup_jax(args.rehearse, int(cell_doc["chips"]))
    ctx = run.Ctx(cell_doc, args.seed, 0.0, False, args.rehearse)
    cell = jobs.TrainCell(ctx)
    trace_dir = os.path.join(ctx.work, "cost_trace")
    walls = {k: [] for k in KINDS}
    try:
        cell.build()
        cell.full_planes()
        for kind in KINDS:
            ctx.say(f"unrecorded {kind} job {one_job(cell, kind, trace_dir):.2f}s")
        for i in range(args.rounds):
            for kind in KINDS:
                walls[kind].append(one_job(cell, kind, trace_dir))
            ctx.say(f"round {i + 1}: " + json.dumps({k: round(v[-1], 3) for k, v in walls.items()}))
    finally:
        shutil.rmtree(ctx.work, ignore_errors=True)
    print(json.dumps({"cell": cell_doc["name"], "rows": cell.rows, "iterations": cell.iters,
                      "job_wall_s": walls,
                      "median_s": {k: statistics.median(v) for k, v in walls.items()},
                      "device": run.device_doc()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
