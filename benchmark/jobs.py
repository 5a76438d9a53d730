"""Closed-loop training cells: a job is ``shifu_tpu.cli.main([... "train"])``
in the process that holds the chip, on a model set the program's own steps
made and full-size planes drawn from the seed.

Shared by ``drivers/train_tree.py`` and ``drivers/train_nn.py``, which add
only their ``correct``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

from . import modelset as ms
from . import trace as trace_mod
from .gen import Table


class TrainCell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell["config_doc"]
        self.traffic = ctx.cell["traffic_doc"]
        self.kind = self.config["train"]["plane"]
        self.iter_key = self.config["train"]["iterations_key"]
        self.iters = int(self.traffic["iterations_per_job"])
        self.rows = int(self.traffic["rows"])
        self.overrides = list(ctx.cell.get("overrides", []))
        self.table = Table(self.config["table"])
        self.baseline = None            # the warm-up job's progress lines

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        """Sample -> the program's small steps -> full-size planes."""
        ctx = self.ctx
        ctx.reset_work()
        with ctx.part("sample"):
            self.text = self.table.write_text(os.path.join(ctx.work, "data"),
                                              int(ctx.cell["sample_rows"]), ctx.seed)
        with ctx.part("steps"):
            self.mdir = ms.make_model_set(ctx.work, "main", self.text, self.config)
        self.schema = ms.read_schema(self.mdir, self.kind)
        # the sample's own rows as the steps wrote them: eval's input, binned
        # / normalised by the program, for the reference to score
        from shifu_tpu.data.shards import Shards
        n_eval = int(ctx.cell["correct"]["eval_rows"])
        head = Shards.open(ms.plane_dir(self.mdir, self.kind)).load_all()
        self.head = {k: np.asarray(v[:n_eval]) for k, v in head.items()}
        self.eval_text = ms.head_of_text(self.text, n_eval,
                                         os.path.join(ctx.work, "data", "eval.csv"))
        self.set_iterations(self.mdir)
        ms._edit_json(os.path.join(self.mdir, "ModelConfig.json"),
                      lambda d: d["evals"][0]["dataSet"].update(dataPath=self.eval_text))

    def set_iterations(self, mdir: str) -> None:
        if self.iter_key == "numTrainEpochs":
            ms.set_train(mdir, numTrainEpochs=self.iters)
        else:
            ms.set_train(mdir, params={self.iter_key: self.iters})

    def full_planes(self) -> None:
        with self.ctx.part("planes"):
            ms.write_planes(self.mdir, self.table, self.kind, self.rows,
                            self.ctx.seed, self.schema)

    def check_set(self, **train_patch) -> Dict[str, np.ndarray]:
        """The check's second, small model set: same configs and column
        statistics, ``sample_job_rows`` seeded rows, the timed job's
        parameters but for ``train_patch``.  Returns the rows."""
        self.cdir = ms.clone_model_set(self.mdir, os.path.join(self.ctx.work, "check"))
        params = train_patch.pop("params", None)
        ms.set_train(self.cdir, params=params, **train_patch)
        rows = int(self.ctx.cell["correct"]["sample_job_rows"])
        return ms.write_planes(self.cdir, self.table, self.kind, rows,
                               self.ctx.seed, self.schema, tag=1, keep=True)

    # -------------------------------------------------------------- jobs
    def job(self, mdir: str = None, telemetry: bool = False) -> float:
        args = self.overrides + ["--dir", mdir or self.mdir, "train"]
        return ms.cli(*args, *(["--telemetry"] if telemetry else []))

    def eval_step(self, mdir: str) -> np.ndarray:
        ms.cli(*self.overrides, "--dir", mdir, "eval", "-run")
        return ms.eval_scores(mdir, int(self.ctx.cell["correct"]["eval_rows"]))

    def warm_up(self) -> None:
        with self.ctx.part("warm_up"):
            dt = self.job(telemetry=self.ctx.trace)   # telemetry changes what is built
        self.baseline = ms.progress_lines(self.mdir)
        self.ctx.say(f"warm-up job {dt:.2f}s, {len(self.baseline)} progress lines, "
                     f"last {self.baseline[-1] if self.baseline else None}")
        self.ctx.check("job.iterations", len(self.baseline) == self.iters,
                       f"warm-up wrote {len(self.baseline)} progress lines, expected {self.iters}")

    def same_as_baseline(self, what: str) -> None:
        """Same seed, same data, same arithmetic: a timed job reports what
        the warm-up job did, to a relative 1e-3 (f32 rounding is three
        orders below; a resumed, skipped or dropped iteration far above)."""
        got = ms.progress_lines(self.mdir)
        if not self.ctx.check(f"{what}.iterations", len(got) == len(self.baseline),
                              f"{len(got)} progress lines, the warm-up job {len(self.baseline)}"):
            return
        for col, label in ((0, "train_err"), (1, "valid_err")):
            a, b = got[-1][col], self.baseline[-1][col]
            if np.isfinite(a) and np.isfinite(b):
                self.ctx.margin(f"job.{label}_vs_warm_up", abs(a - b), 1e-3 * max(abs(b), 1e-9))

    # ------------------------------------------------------------ window
    def window(self, t_start: float) -> dict:
        """Jobs back to back until ``seconds`` have passed (a job that has
        begun runs to its end).  train_rate = sum(rows x iterations) of the
        completed jobs / time from the window's start to the end of the
        last completed job."""
        ctx = self.ctx
        built0, compiled0 = ctx.compiles.built, ctx.compiles.compiled
        ends: List[float] = []
        failed = attempted = 0
        tracer = None
        setup_s = time.perf_counter() - t_start
        t0 = time.perf_counter()
        if ctx.trace:
            # one whole job (load, H2D, training, the model written), so that
            # the idle share is a job's; the timer only caps a job gone wrong
            tracer = trace_mod.Capture(os.path.join(ctx.work, "trace"),
                                       float(ctx.cell.get("trace_cap_seconds", 150)))
            tracer.start()
        while time.perf_counter() - t0 < ctx.seconds:
            attempted += 1
            t_job = time.perf_counter()
            try:
                self.job(telemetry=ctx.trace)
            except Exception as e:          # a failed job counts, the run goes on
                failed += 1
                ctx.say(f"job failed: {type(e).__name__}: {e}")
                continue
            ends.append(time.perf_counter())
            if tracer:
                tracer.host_spans.append(("bench:job", t_job, ends[-1]))
                tracer.stop()               # the first whole job is what is traced
            self.same_as_baseline(f"job{attempted}")
        window_built = ctx.compiles.built - built0
        window_compiles = ctx.compiles.compiled - compiled0
        if tracer:
            tracer.stop()
        done = len(ends)
        elapsed = (ends[-1] - t0) if ends else float("nan")
        rate = done * self.rows * self.iters / elapsed if ends else 0.0
        ctx.say(f"window: {done} jobs in {elapsed:.2f}s, train_rate {rate:.6g} rows.iters/s, "
                f"job walls {[round(b - a, 2) for a, b in zip([t0] + ends, ends)]}, "
                f"inside the window {window_built} programs built, {window_compiles} compiled")
        ctx.check("window.compiles", window_compiles == 0,
                  f"{window_compiles} programs were compiled inside the window")
        ctx.check("window.jobs", done >= 1, "no job completed")
        ctx.counters.update(jobs=done, iterations=done * self.iters,
                            window_compiles=window_compiles, window_built=window_built)
        return {"setup_s": setup_s, "train_rate": rate, "attempted": attempted,
                "failed": failed, "tracer": tracer}


def finish(ctx, win: dict, end_to_end: Dict[str, tuple]) -> dict:
    """The final line.  ``end_to_end``: name -> (value, unit).  With
    ``--trace 1`` the metrics are the cell's per-layer metrics instead,
    each from its own reader."""
    ctx.say("set-up parts (s): " + json.dumps(
        {**ctx.parts, "compile_or_load": round(ctx.compiles.seconds, 2),
         "programs_built": ctx.compiles.built, "compiled": ctx.compiles.compiled}))
    ctx.say("margins (tolerance / closest case): " +
            json.dumps({k: round(v, 2) for k, v in ctx.margins.items()}))
    out = {"correct": not ctx.problems, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": {}, "device": {}}
    if ctx.problems:
        out["problems"] = ctx.problems[:8]
    if not ctx.trace:
        out["metrics"] = {k: {"value": v, "unit": u} for k, (v, u) in end_to_end.items()}
        return out
    from .run import layer_metrics_for
    from .readers import read_metric
    summary = win["tracer"].reduce() if win.get("tracer") else None
    if summary is not None:
        out["device"] = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        out["breakdown"] = summary.breakdown()
    for doc in layer_metrics_for(ctx.cell["name"]):
        value = read_metric(doc, summary, ctx)
        if value is not None:
            out["metrics"][doc["name"]] = {"value": value, "unit": doc["unit"]}
    return out
