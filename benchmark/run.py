"""The benchmark's entry point.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m benchmark.run --workload <cell> --check-seeds a-b [--full-jobs k]
    ... --rehearse            (CPU, toy sizes from the cell's ``rehearse`` block)

Everything that belongs to one cell, configuration, traffic mix, driver or
per-layer metric is a file found by its name (``workloads/``, ``configs/``,
``traffic/``, ``drivers/``, ``layer_metrics/`` + ``readers/``); adding one
needs no edit here.  The last stdout line of a run is one JSON object with
the contract's keys; everything else goes on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_ROOT = os.path.join(ROOT, ".bench_work")      # git-ignored, per cell
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool = False) -> dict:
    """The cell's file with its configuration and traffic mix resolved by
    name; ``--rehearse`` overlays the cell's toy ``rehearse`` block."""
    cell = load_json("workloads", name + ".json")
    cell["config_doc"] = load_json("configs", cell["config"] + ".json")
    cell["traffic_doc"] = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        over = cell.get("rehearse") or {}
        cell["traffic_doc"].update(over.get("traffic", {}))
        cell.update({k: v for k, v in over.items() if k != "traffic"})
    return cell


def layer_metrics_for(cell_name: str) -> list:
    """Every ``layer_metrics/*.json`` that lists this cell (or no cells)."""
    out = []
    d = os.path.join(HERE, "layer_metrics")
    for f in sorted(os.listdir(d)):
        if f.endswith(".json"):
            doc = load_json("layer_metrics", f)
            if not doc.get("workloads") or cell_name in doc["workloads"]:
                out.append(doc)
    return out


class Ctx:
    """What a driver gets: the cell, the run's arguments, a work
    directory, a clock for set-up's parts, and the verdict so far."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 rehearse: bool):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.rehearse = bool(trace), bool(rehearse)
        self.work = os.path.join(WORK_ROOT, cell["name"])
        self.parts: dict = {}            # set-up's parts, seconds
        self.margins: dict = {}          # comparison -> tolerance / closest case
        self.problems: list = []         # why correct is False
        self.counters: dict = {}         # program counters, for the readers
        self.compiles = COMPILES

    @property
    def device_kind(self) -> str:
        import jax
        return jax.devices()[0].device_kind

    def say(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)

    @contextlib.contextmanager
    def part(self, name: str):
        """Time one of set-up's parts (printed on an earlier line)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.parts[name] = round(self.parts.get(name, 0.0) + time.perf_counter() - t0, 2)

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        if not ok:
            self.problems.append(f"{name}: {detail}")
            self.say(f"CHECK FAILED {name}: {detail}")
        return ok

    def margin(self, name: str, value: float, tolerance: float, detail: str = "") -> bool:
        """One comparison: ``value`` must stay within ``tolerance``.  The
        margin is how many times the closest case fits into the tolerance;
        the smallest per name is kept (``--check-seeds`` prints them)."""
        m = float("inf") if value == 0 else tolerance / abs(value)
        self.margins[name] = min(self.margins.get(name, float("inf")), m)
        return self.check(name, abs(value) <= tolerance,
                          f"{value:.6g} against tolerance {tolerance:.6g} {detail}")

    def reset_work(self) -> None:
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)


class _CompileCounter:
    """jax's own monitoring events, on whether or not the program's
    telemetry is switched on.  ``built``: programs traced, lowered and
    handed to the backend (the event ``xla.compile_count`` counts; a load
    from the persistent cache is one too).  ``hits``: of those, loaded from
    the persistent cache.  ``compiled`` = built - hits: what XLA really
    compiled.  A trainer that makes its jitted closures anew in every job
    re-builds them in every job and loads them from the cache — as a
    user's fresh process does; what may not happen inside a window is a
    compilation."""

    def __init__(self):
        self.built, self.hits, self.seconds = 0, 0, 0.0

    @property
    def compiled(self) -> int:
        return self.built - self.hits

    def install(self) -> None:
        from jax import monitoring

        def on_duration(name: str, secs: float, **kw) -> None:
            if name.startswith("/jax/core/compile/"):
                self.seconds += secs
                if name.endswith("/backend_compile_duration"):
                    self.built += 1

        def on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


COMPILES = _CompileCounter()         # one per process; installed once jax is set up


def device_doc() -> dict:
    import jax
    devs = jax.devices()
    peak = limit = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    print(f"[bench] device memory: peak {peak} of {limit} bytes on the fullest chip", flush=True)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def setup_jax(rehearse: bool, chips: int) -> None:
    """The compile cache and the device: a fixed cache directory inside the
    checkout unless the environment names one, every program kept (jax's
    defaults drop what compiles in under a second), and no fallback — a
    run that finds no TPU, or fewer chips than the cell asks for, exits 3
    with no result line."""
    os.environ.setdefault(CACHE_ENV, os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={chips}")
    import jax
    jax.config.update("jax_compilation_cache_dir", os.environ[CACHE_ENV])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        sys.exit(3)
    if not rehearse and devs[0].platform != "tpu":
        print(f"benchmark: platform is {devs[0].platform!r}, not 'tpu'; a "
              "measurement never falls back (use --rehearse for the CPU)",
              file=sys.stderr)
        sys.exit(3)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, jax finds "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(3)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy sizes; the final line's device says cpu")
    ap.add_argument("--check-seeds", default=None, metavar="A-B[,C-D]",
                    help="set-up and correct only, for each seed in turn")
    ap.add_argument("--full-jobs", type=int, default=0,
                    help="with --check-seeds: on the first K seeds also run "
                    "two full-size jobs for the job-to-job checks")
    ap.add_argument("--sweep", default=None, metavar="R1,R2,...",
                    help="serving cells: one set-up, then a window at each of these "
                    "request rates (finding the knee; prints one line a rate)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload, args.rehearse)
    if args.seconds is None:
        args.seconds = float(load_bench()["run_seconds"])
    setup_jax(args.rehearse, int(cell["chips"]))
    COMPILES.install()
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])

    if args.check_seeds:
        seeds = []
        for part in args.check_seeds.split(","):
            lo, _, hi = part.partition("-")
            seeds.extend(range(int(lo), int(hi or lo) + 1))
        worst: dict = {}
        bad = 0
        for i, seed in enumerate(seeds):
            ctx = Ctx(cell, seed, args.seconds, False, args.rehearse)
            try:
                driver.check_only(ctx, full_jobs=2 if i < args.full_jobs else 0)
            except Exception as e:              # one seed's fault must not end the sweep
                ctx.check("raised", False, f"{type(e).__name__}: {e}")
            bad += bool(ctx.problems)
            for k, v in ctx.margins.items():
                worst[k] = min(worst.get(k, float("inf")), v)
            print(json.dumps({"seed": seed, "correct": not ctx.problems,
                              "problems": ctx.problems,
                              "margins": {k: round(v, 3) for k, v in ctx.margins.items()}}),
                  flush=True)
            shutil.rmtree(ctx.work, ignore_errors=True)
        print(json.dumps({"check_seeds": args.check_seeds, "cell": cell["name"],
                          "seeds": len(seeds), "failed": bad,
                          "closest_margin": {k: round(v, 3) for k, v in worst.items()},
                          "device": device_doc()}), flush=True)
        return 1 if bad else 0

    ctx = Ctx(cell, args.seed, args.seconds, bool(args.trace), args.rehearse)
    if args.sweep:
        try:
            driver.sweep(ctx, [float(r) for r in args.sweep.split(",")])
        finally:
            shutil.rmtree(ctx.work, ignore_errors=True)
        return 0
    try:
        result = driver.run(ctx, T_START)
    finally:
        shutil.rmtree(ctx.work, ignore_errors=True)
    result["device"] = {**device_doc(), **result.get("device", {})}
    print(json.dumps(result), flush=True)
    return 0


def load_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


if __name__ == "__main__":
    sys.exit(main())
