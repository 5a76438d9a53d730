"""shifu-tpu CLI — the reference's ``shifu`` launcher + ``ShifuCLI``.

Commands mirror reference ``ShifuCLI.java:818-866``:
``new | init | stats | norm | varselect | train | posttrain | eval | export |
test | encode | combo | convert``.  ``-Dkey=value`` properties go to the
Environment tier (reference ``ShifuCLI.java:430-453``).
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from .config import environment


def _split_props(argv: List[str]) -> List[str]:
    """Pull ``-Dk=v`` pairs out of argv into Environment, return the rest."""
    rest = []
    for a in argv:
        if a.startswith("-D") and "=" in a:
            k, _, v = a[2:].partition("=")
            environment.set_property(k, v)
        else:
            rest.append(a)
    return rest


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="shifu-tpu",
        description="TPU-native tabular ML pipeline (new→init→stats→norm→varselect"
                    "→train→posttrain→eval→export)")
    p.add_argument("--dir", default=".", help="model-set directory (default: cwd)")
    p.add_argument("-v", "--verbose", action="store_true")
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("new", help="create a new model-set scaffold")
    sp.add_argument("name")
    sp.add_argument("--alg", "-t", default="NN", dest="alg",
                    help="NN|LR|GBT|RF|DT|WDL|SVM (reference `new -t`)")
    sp.add_argument("-m", dest="description", default=None,
                    help="model-set description (reference `new -m`)")

    sp = sub.add_parser("init",
                        help="build initial ColumnConfig.json from header")
    sp.add_argument("-model", dest="init_model", action="store_true",
                    help="fill the algorithm's default train#params into "
                    "ModelConfig.json (reference `init -model`)")

    sp = sub.add_parser("stats", help="per-column stats + binning (+psi/correlation)")
    sp.add_argument("-correlation", "-c", dest="correlation", action="store_true")
    sp.add_argument("-psi", dest="psi", action="store_true")
    sp.add_argument("-rebin", dest="rebin", action="store_true")
    sp.add_argument("-vars", dest="rebin_vars", metavar="A,B",
                    help="rebin only these columns (reference -vars)")
    sp.add_argument("-ivr", dest="rebin_ivr", type=float, default=None,
                    help="rebin IV keep ratio (reference -ivr)")
    sp.add_argument("-bic", dest="rebin_bic", type=int, default=None,
                    help="rebin minimum bin instance count (reference -bic)")

    sp = sub.add_parser("norm", aliases=["normalize", "transform"],
                        help="normalize training data")
    sp.add_argument("-shuffle", dest="shuffle", action="store_true")

    sp = sub.add_parser("varselect", aliases=["varsel"], help="variable selection")
    sp.add_argument("-list", dest="list", action="store_true")
    sp.add_argument("-reset", dest="reset", action="store_true")
    sp.add_argument("-recover", dest="recover", action="store_true")
    sp.add_argument("-recursive", dest="recursive", type=int, default=1,
                    metavar="N", help="SE/ST wrapper rounds: each round "
                    "re-norms + retrains on the current selection, then "
                    "re-scores sensitivity")
    sp.add_argument("-autofilter", dest="autofilter", action="store_true",
                    help="apply only the missing-rate/KS/IV/correlation "
                    "auto filter to the current selection")
    sp.add_argument("-recoverauto", dest="recoverauto", action="store_true",
                    help="restore variables removed by the last -autofilter")

    sp = sub.add_parser("train", help="train model(s)")
    sp.add_argument("-dry", dest="dry", action="store_true")
    sp.add_argument("-shuffle", dest="shuffle", action="store_true")
    sp.add_argument("-resume", dest="resume", action="store_true",
                    help="resume from the latest trainer-state checkpoint")

    sub.add_parser("posttrain", help="bin-average scores + feature importance")

    sp = sub.add_parser("eval", help="evaluate model on eval sets")
    sp.add_argument("-run", dest="run_eval", metavar="EVALSET", nargs="?", const="")
    sp.add_argument("-score", dest="score", metavar="EVALSET", nargs="?", const="")
    sp.add_argument("-nosort", dest="nosort", action="store_true",
                    help="-score: keep input row order (default sorts the "
                    "score file by the selected score column — "
                    "performanceScoreSelector, or the winning class score "
                    "for multi-class; reference `eval -score`)")
    sp.add_argument("-perf", dest="perf", metavar="EVALSET", nargs="?", const="")
    sp.add_argument("-confmat", dest="confmat", metavar="EVALSET", nargs="?", const="")
    sp.add_argument("-norm", dest="norm_eval", metavar="EVALSET", nargs="?",
                    const="")
    sp.add_argument("-new", dest="new_eval", metavar="EVALSET")
    sp.add_argument("-delete", dest="delete_eval", metavar="EVALSET")
    sp.add_argument("-list", dest="list", action="store_true")

    sp = sub.add_parser("export", help="export model "
                        "(pmml|baggingpmml|bagging|columnstats|woemapping|corr)")
    sp.add_argument("type_pos", nargs="?", default=None, metavar="TYPE",
                    help="same as -t (`shifu export pmml`)")
    sp.add_argument("-t", "--type", default="pmml")
    sp.add_argument("-c", dest="concise", action="store_true",
                    help="concise PMML: trim per-bin stats extensions "
                    "(reference `export -c`)")

    sp = sub.add_parser("analysis", help="model spec analysis "
                        "(-fi MODEL: tree feature importance; --telemetry: "
                        "render the last run's span/metric trace; "
                        "--telemetry --timeline OUT: export a Chrome/"
                        "Perfetto trace_event timeline; --telemetry "
                        "--utilization: cost-attribution / roofline "
                        "report)")
    sp.add_argument("-fi", dest="fi_model", metavar="MODELPATH")
    sp.add_argument("-telemetry", "--telemetry", dest="telemetry_report",
                    action="store_true",
                    help="render <modelset>/telemetry/trace.jsonl as a "
                    "per-step span tree with self-time and rows/sec")
    sp.add_argument("-timeline", "--timeline", dest="timeline_out",
                    metavar="OUT.json", default=None,
                    help="with --telemetry: convert the trace to Chrome "
                    "trace_event JSON (load in chrome://tracing or "
                    "ui.perfetto.dev; ingest-thread spans get their own "
                    "track)")
    sp.add_argument("-utilization", "--utilization", dest="utilization",
                    action="store_true",
                    help="with --telemetry: join executable FLOPs/bytes "
                    "(obs cost records) against span wall times — "
                    "achieved FLOP/s, bytes/s, percent-of-peak and a "
                    "roofline verdict per plane (peaks override: "
                    "SHIFU_TPU_PEAK_FLOPS / SHIFU_TPU_PEAK_BW)")
    sp.add_argument("-aggregate", "--aggregate", dest="analysis_aggregate",
                    nargs="+", metavar="DIR", default=None,
                    help="with --telemetry [--timeline]: merge the "
                    "telemetry dirs of N processes (replaces --dir) "
                    "into one report / one trace — per-proc tracks, "
                    "clock-offset normalization from heartbeats, "
                    "per-proc step-lag table")

    sp = sub.add_parser("monitor", help="live health monitor: tail "
                        "<modelset>/telemetry/health/ heartbeats and "
                        "render per-process step/phase/progress with "
                        "staleness flags")
    sp.add_argument("--interval", dest="monitor_interval", type=float,
                    default=2.0, metavar="S",
                    help="seconds between frames (default 2)")
    sp.add_argument("--once", dest="monitor_once", action="store_true",
                    help="render one frame and exit")
    sp.add_argument("--json", dest="monitor_json", action="store_true",
                    help="with --once: print ONE machine-readable JSON "
                    "doc (per-proc health + quorum summary) instead of "
                    "the table; exit 0 healthy, 3 when any process is "
                    "stalled or stale — for CI and cron consumers")
    sp.add_argument("--aggregate", dest="monitor_aggregate", nargs="+",
                    metavar="DIR", default=None,
                    help="merge the health planes of N process telemetry "
                    "dirs (replaces --dir) into one report: tagged "
                    "table, merged quorum, per-proc step-lag table, "
                    "heartbeat clock-offset normalization")

    sp = sub.add_parser("serve", help="online scoring server: the trained "
                        "ensemble AOT-compiled + HBM-pinned behind a "
                        "padded-bucket micro-batcher (knobs: "
                        "-Dshifu.serve.buckets, -Dshifu.serve.maxDelayMs, "
                        "-Dshifu.serve.traceSampleRate per-request "
                        "tracing, -Dshifu.serve.sloP99Ms / "
                        "-Dshifu.serve.sloAvailability SLO objectives; "
                        "GET /slo serves live burn-rate alerts)")
    sp.add_argument("--port", dest="serve_port", type=int, default=8188,
                    help="HTTP port for POST /score + GET /healthz "
                    "(default 8188)")
    sp.add_argument("--max-delay-ms", dest="serve_max_delay_ms",
                    type=float, default=None, metavar="MS",
                    help="deadline flush bound (overrides "
                    "-Dshifu.serve.maxDelayMs; default 2)")
    sp.add_argument("--selfcheck", dest="serve_selfcheck", type=int,
                    nargs="?", const=8, default=0, metavar="N",
                    help="score N synthetic rows in-process and exit "
                    "(no port; CI smoke)")
    sp.add_argument("--replicas", dest="serve_replicas", type=int,
                    default=1, metavar="N",
                    help="fleet mode: spawn N serve workers behind a "
                    "health-/SLO-aware routing front on --port — "
                    "POST /swap coordinates a fleet-wide hot-swap with "
                    "no mixed-model window (knobs: "
                    "-Dshifu.serve.fleetPollMs health-poll cadence, "
                    "-Dshifu.serve.fleetStaleS stale-replica cutoff, "
                    "-Dshifu.serve.canaryFrac canary commit slice)")
    # internal fleet-worker flags (run_fleet passes them when spawning)
    sp.add_argument("--replica", dest="serve_replica", default=None,
                    help=argparse.SUPPRESS)
    sp.add_argument("--announce", dest="serve_announce", default=None,
                    help=argparse.SUPPRESS)

    sp = sub.add_parser("refresh", help="continual refresh: drift-gated "
                        "warm retrain -> AUC-gated hot-swap promotion -> "
                        "SLO-observed probation with automatic rollback "
                        "(knobs: -Dshifu.refresh.psiThreshold, "
                        "-Dshifu.refresh.intervalS, "
                        "-Dshifu.refresh.cooldownS, "
                        "-Dshifu.refresh.minAucDelta, "
                        "-Dshifu.refresh.probationS, "
                        "-Dshifu.refresh.units; one cycle attempt by "
                        "default)")
    sp.add_argument("--daemon", dest="refresh_daemon", action="store_true",
                    help="stay resident: poll the drift artifact / "
                    "schedule forever (the always-on production loop)")
    sp.add_argument("--poll", dest="refresh_poll", type=float,
                    default=2.0, metavar="S",
                    help="seconds between controller ticks (default 2)")

    sp = sub.add_parser("lint", help="AST-based convention checker: "
                        "host-sync/recompile/knob-registry/atomic-write/"
                        "telemetry-guard/manifest rules over shifu_tpu/ "
                        "(exit 0 clean, 2 findings; "
                        "# shifu-lint: disable=RULE suppresses inline; "
                        "lint-baseline.json grandfathers old debt)")
    from .lint.cli import add_lint_args
    add_lint_args(sp)

    sp = sub.add_parser("test", help="pipeline smoke test on a data sample")
    sp.add_argument("-filter", dest="filter_target", nargs="?", const="",
                    default=None, metavar="EVALSET",
                    help="test only the filter expressions: no value = "
                    "training set, '*' = all sets, a name = that eval set")
    sp = sub.add_parser("encode", help="encode dataset by tree-leaf index")
    sp.add_argument("-evalset", dest="evalset", default=None)
    sp.add_argument("-ref", dest="ref_model", default=None, metavar="DIR",
                    help="encode with the tree model of another model-set "
                    "dir (reference ENCODE_REF_MODEL)")

    sp = sub.add_parser("combo", help="multi-algorithm ensemble")
    sp.add_argument("action", choices=["new", "init", "run", "eval"])
    sp.add_argument("-resume", dest="resume", action="store_true",
                    help="skip members already trained")
    sp.add_argument("-alg", dest="algs", default=None,
                    help="colon-separated list, e.g. NN:GBT:LR")

    sp = sub.add_parser("convert", help="convert model spec zip<->binary")
    sp.add_argument("-tozipb", dest="tozipb", action="store_true")
    sp.add_argument("-tob", "-totreeb", dest="tob", action="store_true",
                    help="(reference TO_TREEB)")

    sp = sub.add_parser("save", help="snapshot model-set version")
    sp.add_argument("name", nargs="?", default=None)
    sp = sub.add_parser("switch", help="restore a saved model-set version")
    sp.add_argument("name")
    sub.add_parser("history", help="list saved model-set versions")
    sub.add_parser("show", help="print the current model-set version")
    sp = sub.add_parser("delete", help="delete a saved model-set version")
    sp.add_argument("name")
    sp = sub.add_parser("cp", help="clone this model set's configs into a "
                        "new scaffold dir")
    sp.add_argument("dest")

    # telemetry/profiling knobs on EVERY step (`shifu-tpu train --profile`):
    # --telemetry enables the span/metric trace for this run (same as
    # SHIFU_TPU_TELEMETRY=1); --profile [dir] captures a jax.profiler
    # device timeline per step (same as -Dshifu.profile=dir)
    seen = set()                        # aliases share one parser object
    for name, spx in sub.choices.items():
        if id(spx) in seen:
            continue
        seen.add(id(spx))
        spx.add_argument("--profile", dest="profile_dir", nargs="?",
                         const="profile", default=None, metavar="DIR",
                         help="capture a jax.profiler trace under DIR "
                         "(default ./profile)")
        if name != "analysis":          # analysis --telemetry = the report
            spx.add_argument("--telemetry", dest="telemetry",
                             action="store_true",
                             help="record span/metric telemetry to "
                             "<modelset>/telemetry/trace.jsonl")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    try:
        return _dispatch(argv)
    except Exception as e:
        from .config.errors import ShifuError
        if isinstance(e, ShifuError):
            # coded user errors: message, not traceback (reference ShifuCLI
            # prints ShifuException messages plainly)
            print(str(e), file=sys.stderr)
            return 1
        raise


def _dispatch(argv: Optional[List[str]] = None) -> int:
    from . import compile_cache
    compile_cache.configure()
    argv = _split_props(list(argv if argv is not None else sys.argv[1:]))
    args = build_parser().parse_args(argv)
    from . import configure_logging
    configure_logging(verbose=args.verbose)   # honors SHIFU_TPU_LOG

    if getattr(args, "telemetry", False):
        from . import obs
        obs.set_enabled(True)
    if getattr(args, "profile_dir", None):
        environment.set_property("shifu.profile", args.profile_dir)

    # multi-host bootstrap: no-op unless the launcher set SHIFU_COORDINATOR
    # (one process per host; jax.devices() then spans the fleet)
    from .parallel.mesh import initialize_distributed
    initialize_distributed()

    cmd = args.command
    if cmd == "new":
        from .pipeline.create import create_new_model
        create_new_model(args.name, base_dir=args.dir, algorithm=args.alg,
                         description=args.description)
        return 0
    if cmd == "init":
        if getattr(args, "init_model", False):
            from .pipeline.create import check_algorithm_param
            return check_algorithm_param(args.dir)
        from .pipeline.create import InitProcessor
        return InitProcessor(args.dir).run()
    if cmd == "stats":
        from .pipeline.stats import StatsProcessor
        return StatsProcessor(args.dir, params=vars(args)).run()
    if cmd in ("norm", "normalize", "transform"):
        from .pipeline.norm import NormalizeProcessor
        return NormalizeProcessor(args.dir, params=vars(args)).run()
    if cmd in ("varselect", "varsel"):
        from .pipeline.varselect import VarSelectProcessor
        return VarSelectProcessor(args.dir, params=vars(args)).run()
    if cmd == "train":
        from .pipeline.train import TrainProcessor
        return TrainProcessor(args.dir, params=vars(args)).run()
    if cmd == "posttrain":
        from .pipeline.posttrain import PostTrainProcessor
        return PostTrainProcessor(args.dir, params=vars(args)).run()
    if cmd == "eval":
        from .pipeline.evaluate import EvalProcessor
        return EvalProcessor(args.dir, params=vars(args)).run()
    if cmd == "export":
        from .pipeline.export import ExportProcessor
        if getattr(args, "type_pos", None):
            args.type = args.type_pos
        return ExportProcessor(args.dir, params=vars(args)).run()
    if cmd == "analysis":
        if getattr(args, "telemetry_report", False) \
                or getattr(args, "utilization", False):
            agg = getattr(args, "analysis_aggregate", None)
            if getattr(args, "utilization", False):
                from .obs.utilization import render_utilization
                print(render_utilization(args.dir))
                return 0
            if getattr(args, "timeline_out", None):
                from .obs.report import NO_TELEMETRY_HINT
                from .obs.timeline import (export_merged_timeline,
                                           export_timeline)
                skipped: list = []
                if agg:
                    out = export_merged_timeline(agg, args.timeline_out,
                                                 skipped=skipped)
                else:
                    out = export_timeline(args.dir, args.timeline_out,
                                          skipped=skipped)
                if out is None:
                    print(NO_TELEMETRY_HINT)
                else:
                    print(f"timeline -> {out}  (load in chrome://tracing "
                          "or https://ui.perfetto.dev)")
                    if skipped:
                        print(f"warning: {len(skipped)} torn trace "
                              "line(s) skipped (crashed run mid-write?)")
                return 0
            if agg:
                from .obs.report import render_telemetry_merged
                print(render_telemetry_merged(agg))
                return 0
            from .obs.report import render_telemetry
            print(render_telemetry(args.dir))
            return 0
        from .pipeline.analysis import analyze_model_fi
        return analyze_model_fi(args.fi_model)
    if cmd == "monitor":
        from .obs.monitor import run_monitor
        return run_monitor(args.dir, interval_s=args.monitor_interval,
                           once=args.monitor_once,
                           json_mode=getattr(args, "monitor_json", False),
                           aggregate_dirs=getattr(args,
                                                  "monitor_aggregate",
                                                  None))
    if cmd == "serve":
        from .models.towers import refuse_dir
        refuse_dir(args.dir, "serve")
        if getattr(args, "serve_replicas", 1) > 1:
            from .serve.router import run_fleet
            return run_fleet(args.dir, replicas=args.serve_replicas,
                             port=args.serve_port,
                             max_delay_ms=args.serve_max_delay_ms)
        from .serve.server import run_serve
        return run_serve(args.dir, port=args.serve_port,
                         selfcheck=args.serve_selfcheck,
                         max_delay_ms=args.serve_max_delay_ms,
                         replica=getattr(args, "serve_replica", None),
                         announce=getattr(args, "serve_announce", None))
    if cmd == "refresh":
        from .pipeline.refresh import RefreshProcessor
        return RefreshProcessor(args.dir, params={
            "daemon": getattr(args, "refresh_daemon", False),
            "poll": getattr(args, "refresh_poll", 2.0)}).run()
    if cmd == "lint":
        from .lint.cli import run_lint_cli
        return run_lint_cli(args)
    if cmd == "test":
        from .pipeline.smoke import SmokeTestProcessor
        return SmokeTestProcessor(args.dir, params=vars(args)).run()
    if cmd == "encode":
        from .pipeline.encode import EncodeProcessor
        return EncodeProcessor(args.dir, params=vars(args)).run()
    if cmd == "combo":
        from .models.towers import refuse_dir
        from .pipeline.combo import run_combo
        refuse_dir(args.dir, "combo")
        return run_combo(args.dir, args.action, args.algs,
                         resume=getattr(args, "resume", False))
    if cmd == "convert":
        from .pipeline.convert import run_convert
        return run_convert(args.dir, vars(args))
    if cmd == "save":
        from .pipeline.manage import save_version
        return save_version(args.dir, args.name)
    if cmd == "show":
        from .pipeline.manage import show_current
        return show_current(args.dir)
    if cmd == "delete":
        from .pipeline.manage import delete_version
        return delete_version(args.dir, args.name)
    if cmd == "cp":
        from .pipeline.manage import copy_model_set
        return copy_model_set(args.dir, args.dest)
    if cmd == "switch":
        from .pipeline.manage import switch_version
        return switch_version(args.dir, args.name)
    if cmd == "history":
        from .pipeline.manage import show_history
        return show_history(args.dir)
    raise SystemExit(f"unknown command {cmd}")


if __name__ == "__main__":
    raise SystemExit(main())
