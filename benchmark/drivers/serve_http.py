"""Raw-record serving cells: ``ServeServer`` + its HTTP handler in the process
that holds the chip, open-loop load from a child that never imports jax."""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from .. import jobs, modelset as ms, stats
from .. import trace as trace_mod
from ..gen import Table, json_records
from ..reference import gbt as ref
from ..run import ROOT


class ServeCell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell["config_doc"]
        self.traffic = ctx.cell["traffic_doc"]
        self.table = Table(self.config["table"])

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        """The model set by the program's own steps, ``train`` included
        (the configuration's full TreeNum on the text sample: serving work
        depends on record width, forest size and traffic, not on training
        rows), then ``eval`` of the fixed requests' records."""
        ctx = self.ctx
        ctx.reset_work()
        with ctx.part("sample"):
            self.text = self.table.write_text(os.path.join(ctx.work, "data"),
                                              int(ctx.cell["sample_rows"]), ctx.seed)
        with ctx.part("steps"):
            self.mdir = ms.make_model_set(ctx.work, "main", self.text, self.config)
            n_fixed = max(ctx.cell["correct"]["fixed_requests"])
            from shifu_tpu.data.shards import Shards
            head = Shards.open(ms.plane_dir(self.mdir, "binned")).load_all()
            self.head_bins = np.asarray(head["bins"][:n_fixed])
            if "trees" in ctx.cell:
                ms.set_train(self.mdir, params={"TreeNum": int(ctx.cell["trees"])})
            ms.cli("--dir", self.mdir, "train")
            eval_text = ms.head_of_text(self.text, n_fixed,
                                        os.path.join(ctx.work, "data", "eval.csv"))
            ms._edit_json(os.path.join(self.mdir, "ModelConfig.json"),
                          lambda d: d["evals"][0]["dataSet"].update(dataPath=eval_text))
            ms.cli("--dir", self.mdir, "eval", "-run")
            self.offline = ms.eval_scores(self.mdir, n_fixed)
        self.records, _ = json_records(self.text["path"], n_fixed)

    def start(self) -> None:
        from http.server import ThreadingHTTPServer

        from shifu_tpu.serve.scorer import serve_recompile_count
        from shifu_tpu.serve.server import ServeServer, _make_handler
        with self.ctx.part("warm_up"):
            self.server = ServeServer(self.mdir)       # loads, AOT-compiles, warms the ladder
            self.server.start()
            self.recompile_count = serve_recompile_count
            self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(self.server))
            self.httpd.daemon_threads = True
            self.thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
            self.thread.start()
            self.port = self.httpd.server_address[1]
            scorer = self.server.registry.get(self.server.key)
            self.ctx.say(f"serving on port {self.port}, buckets {list(scorer.buckets)}")

    def stop(self) -> None:
        self.httpd.shutdown()
        self.thread.join(timeout=30)
        self.httpd.server_close()
        self.server.stop()

    # ----------------------------------------------------------- correct
    def fixed_requests(self, when: str) -> None:
        """Requests of 1, 8 and 100 records: scores equal ``eval``'s offline
        scores of the same records (the smoke's tolerance) and the plain
        reference's walk of the written forest."""
        from shifu_tpu.models.tree import load_model
        spec, trees = load_model(os.path.join(self.mdir, "models", "model0.gbt"))
        walk = 1000.0 * ref.forest_score(trees, self.head_bins, spec.init_score,
                                         spec.learning_rate)
        tol = float(self.ctx.cell["correct"]["score_tol"])
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            for n in self.ctx.cell["correct"]["fixed_requests"]:
                conn.request("POST", "/score", json.dumps({"records": self.records[:n]}),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                doc = json.loads(resp.read())
                if not self.ctx.check(f"fixed.{when}.{n}", resp.status == 200 and not doc.get("errors"),
                                      f"status {resp.status}: {str(doc)[:200]}"):
                    continue
                got = np.asarray(doc["scores"], np.float64)
                self.ctx.margin("fixed.serve_vs_eval", float(np.abs(got - self.offline[:n]).max()), tol)
                self.ctx.margin("fixed.serve_vs_reference_walk",
                                float(np.abs(got - walk[:n]).max()), tol)
        finally:
            conn.close()

    # ------------------------------------------------------------ window
    def window(self, t_start: float) -> dict:
        ctx = self.ctx
        plan = {"port": self.port, "text": self.text["path"], "traffic": self.traffic,
                "seed": ctx.seed, "seconds": ctx.seconds, "skip": 128,
                "out": os.path.join(ctx.work, "loadgen.json")}
        plan_path = os.path.join(ctx.work, "plan.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        child = subprocess.Popen([sys.executable, "-m", "benchmark.loadgen", plan_path],
                                 cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                                 text=True)
        try:
            ready = child.stdout.readline().strip()
            ctx.say(f"load generator: {ready}")
            recompiles0 = self.recompile_count()
            built0, compiled0 = ctx.compiles.built, ctx.compiles.compiled
            stats0 = dict(self.server.batcher.stats)
            tracer = None
            setup_s = time.perf_counter() - t_start
            if ctx.trace:
                tracer = trace_mod.Capture(os.path.join(ctx.work, "trace"),
                                           float(ctx.cell.get("trace_seconds", 5)))
                tracer.start()
            child.stdin.write("go\n")
            child.stdin.flush()
            done = child.stdout.readline().strip()
            child.wait(timeout=60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if tracer:
            tracer.stop()
        ctx.check("loadgen.done", done == "DONE" and child.returncode == 0,
                  f"the load generator ended with {done!r}, code {child.returncode}")
        with open(plan["out"]) as f:
            log = json.load(f)
        reqs = log["requests"]
        stats1 = dict(self.server.batcher.stats)
        recompiles = self.recompile_count() - recompiles0
        window_compiles = ctx.compiles.compiled - compiled0
        limit_ms = float(self.traffic["limit_ms"])
        lat = stats.request_latencies_ms(reqs, ctx.seconds)
        failed = sum(1 for r in reqs if not (r["status"] == 200 and r["scores_ok"]))
        late = stats.lateness_ms(reqs)
        padded = stats1.get("rows_padded", 0) - stats0.get("rows_padded", 0)
        scored = stats1.get("rows", 0) - stats0.get("rows", 0)
        out = {"setup_s": setup_s, "attempted": len(reqs), "failed": failed, "tracer": tracer,
               "score_p95_ms": stats.percentile(lat, 95), "score_p50_ms": stats.percentile(lat, 50),
               "score_goodput": stats.goodput(reqs, ctx.seconds, limit_ms),
               "backlog_mid": stats.backlog(reqs, ctx.seconds / 2),
               "backlog_end": stats.backlog(reqs, ctx.seconds),
               "records": sum(r["records"] for r in reqs)}
        ctx.counters.update(serve_recompiles=recompiles, window_compiles=window_compiles,
                            loadgen_late_p95_ms=stats.percentile(late, 95),
                            pad_share=100.0 * padded / max(padded + scored, 1),
                            batches=stats1.get("batches", 0) - stats0.get("batches", 0))
        ctx.say(f"window: {len(reqs)} requests ({out['records']} records, rate "
                f"{self.traffic['rate_per_s']}/s), failed {failed}, p50 {out['score_p50_ms']:.2f} ms, "
                f"p95 {out['score_p95_ms']:.2f} ms, goodput {out['score_goodput']:.1f} records/s "
                f"(limit {limit_ms} ms), backlog mid/end {out['backlog_mid']}/{out['backlog_end']}, "
                f"generator late p95 {ctx.counters['loadgen_late_p95_ms']:.2f} ms, pad share "
                f"{ctx.counters['pad_share']:.1f} %, batches {ctx.counters['batches']}, recompiles "
                f"{recompiles}, compiled {window_compiles}, built {ctx.compiles.built - built0}")
        ctx.check("window.serve_recompiles", recompiles == 0, f"{recompiles} serve recompiles")
        ctx.check("window.compiles", window_compiles == 0, f"{window_compiles} programs compiled")
        ctx.check("window.requests", len(reqs) > 0, "no request was sent")
        return out


def _prepare(ctx) -> ServeCell:
    cell = ServeCell(ctx)
    cell.build()
    cell.start()
    with ctx.part("correct"):
        cell.fixed_requests("before")
    return cell


def run(ctx, t_start: float) -> dict:
    cell = _prepare(ctx)
    try:
        win = cell.window(t_start)
        cell.fixed_requests("after")
    finally:
        cell.stop()
    return jobs.finish(ctx, win, {"score_p95_ms": (win["score_p95_ms"], "ms"),
                                  "score_goodput": (win["score_goodput"], "records/s"),
                                  "setup_s": (win["setup_s"], "s")})


def check_only(ctx, full_jobs: int = 0) -> None:
    cell = _prepare(ctx)
    try:
        if full_jobs:
            ctx.seconds = min(ctx.seconds, 5.0)
            cell.window(time.perf_counter())
        cell.fixed_requests("after")
    finally:
        cell.stop()


def sweep(ctx, rates) -> None:
    """Finding the knee, once: one set-up, then a window at each rate.  The
    knee is the highest rate at which no request fails and the backlog at
    the window's end is no larger than at its middle."""
    cell = _prepare(ctx)
    try:
        for i, rate in enumerate(rates):
            cell.traffic["rate_per_s"] = rate
            ctx.seed += 1
            win = cell.window(time.perf_counter())
            print(json.dumps({"sweep_rate_per_s": rate, "requests": win["attempted"],
                              "records_per_s": win["records"] / ctx.seconds,
                              "failed": win["failed"], "p50_ms": win["score_p50_ms"],
                              "p95_ms": win["score_p95_ms"], "backlog_mid": win["backlog_mid"],
                              "backlog_end": win["backlog_end"],
                              "late_p95_ms": ctx.counters["loadgen_late_p95_ms"],
                              "keeps_up": win["failed"] == 0 and
                              win["backlog_end"] <= max(win["backlog_mid"], 1)}), flush=True)
    finally:
        cell.stop()
