"""Pipeline processors — step orchestration.

Analogue of the reference's processor layer (``core/processor/``): one
processor per CLI step with shared setup/teardown (config load, validation,
ColumnConfig save) in ``BasicProcessor`` (reference
``BasicModelProcessor.java``).
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import time
from typing import List, Optional

from .. import faults, ioutil, obs
from ..config import (ColumnConfig, ModelConfig, PathFinder, jsonbean,
                      load_column_configs, save_column_configs)
from ..config.validator import ModelStep, probe
from .journal import StepJournal

log = logging.getLogger(__name__)


class BasicProcessor:
    """Shared step setup/teardown (reference ``BasicModelProcessor.java``)."""

    step: ModelStep = ModelStep.NEW
    require_columns: bool = True       # INIT creates ColumnConfig itself

    @property
    def profile_name(self) -> str:
        """profile.json key — override when several processors share a
        ModelStep (encode runs under EVAL validation rules)."""
        return self.step.name

    def __init__(self, model_set_dir: str = ".", params: Optional[dict] = None):
        self.dir = os.path.abspath(model_set_dir)
        self.params = params or {}
        self.model_config: Optional[ModelConfig] = None
        self.column_configs: List[ColumnConfig] = []
        self.paths: Optional[PathFinder] = None
        self.journal: Optional[StepJournal] = None

    # ------------------------------------------------------------ lifecycle
    def setup(self, require_columns: Optional[bool] = None) -> None:
        if require_columns is None:
            require_columns = self.require_columns
        mc_path = os.path.join(self.dir, "ModelConfig.json")
        if not os.path.isfile(mc_path):
            raise FileNotFoundError(
                f"{mc_path} not found — run `shifu-tpu new <name>` first")
        # what a job pays before its step body, by name: children of the
        # step's `setup` span (attrs are counts the code holds)
        with obs.span("setup.config"):
            self.model_config = ModelConfig.load(mc_path)
            self.paths = PathFinder(self.model_config, self.dir)
        with obs.span("setup.probe"):
            probe(self.model_config, self.step, self.dir)
        cc_path = self.paths.column_config_path
        if os.path.isfile(cc_path):
            with obs.span("setup.columns") as sp:
                plans = jsonbean.plans_built()
                self.column_configs = load_column_configs(cc_path)
                sp.set(columns=len(self.column_configs),
                       bytes=os.path.getsize(cc_path),
                       plans_built=jsonbean.plans_built() - plans)
        elif require_columns:
            raise FileNotFoundError(
                f"{cc_path} not found — run `shifu-tpu init` first")
        with obs.span("setup.journal"):
            self.paths.ensure_dirs()
            self.journal = StepJournal(
                self.paths.journal_path(self.profile_name), self.profile_name,
                self.dir)
        with obs.span("setup.precheck") as sp:
            sp.set(shards=self._check_step_preconditions())

    def _check_step_preconditions(self) -> int:
        """Ordered-pipeline guard: running a step before its inputs exist
        fails with a coded hint instead of a raw traceback deep in the
        step (stats -> norm -> train dependency chain).  Returns the
        journaled norm shards whose files it verified (`train` alone)."""
        from ..config.errors import ErrorCode, ShifuError
        s = self.step
        if s in (ModelStep.NORMALIZE, ModelStep.VARSELECT, ModelStep.TRAIN):
            cand = [c for c in self.column_configs or [] if c.is_candidate()]
            if cand and not any((c.num_bins() or 0) > 0
                                or c.columnStats.mean is not None
                                for c in cand):
                raise ShifuError(
                    ErrorCode.ERROR_STEP_PRECONDITION,
                    f"`{s.value.lower()}` needs column statistics — run "
                    "`stats` first")
        if s == ModelStep.TRAIN:
            if not (os.path.isfile(os.path.join(self.paths.norm_dir,
                                                "schema.json"))
                    or os.path.isfile(os.path.join(self.paths.clean_dir,
                                                   "schema.json"))):
                raise ShifuError(
                    ErrorCode.ERROR_STEP_PRECONDITION,
                    "`train` needs the materialized data plane — run "
                    "`norm` first")
            # journal completeness, not just file existence: a norm run
            # that died mid-step (or whose committed shards were later
            # truncated) must not feed the trainers half a dataset.
            # Absence of a journal = pre-journal artifacts, trust files.
            nj = StepJournal(self.paths.journal_path("NORMALIZE"),
                             "NORMALIZE", self.dir)
            if nj.is_torn():
                raise ShifuError(
                    ErrorCode.ERROR_TORN_ARTIFACT,
                    "the last `norm` run did not complete (journal "
                    "status=running) — re-run `norm` (it resumes at the "
                    "first uncommitted shard)")
            if nj.status and not nj.verify_all():
                raise ShifuError(
                    ErrorCode.ERROR_TORN_ARTIFACT,
                    "materialized norm shards no longer match their "
                    "journaled sizes (torn/corrupted artifact) — re-run "
                    "`norm`")
            if nj.status:
                return len(nj.doc.get("items") or {})
        return 0

    def _abs(self, p: Optional[str]) -> Optional[str]:
        """Resolve a config-relative path against the model-set dir.
        Scheme'd URIs (hdfs://, s3://, ...) pass through untouched so the
        data layer can reject them with the proper error code."""
        if p is None:
            return None
        if "://" in p:
            return p
        return p if os.path.isabs(p) else os.path.normpath(
            os.path.join(self.dir, p))

    def save_column_configs(self) -> None:
        save_column_configs(self.column_configs, self.paths.column_config_path)

    def save_model_config(self) -> None:
        self.model_config.save(self.paths.model_config_path)

    def run(self) -> int:
        t0 = time.time()
        log.info("step %s start", self.step.name)
        telemetry = obs.enabled()
        if telemetry:
            obs.ensure_compile_listener()
        heartbeat = exporter = None
        code: Optional[int] = None
        try:
            with obs.span(self.profile_name, kind="step") as root:
                with obs.span("setup", kind="phase"):
                    self.setup()
                # live observability plane: per-process heartbeats under
                # <modelset>/telemetry/health/ (the `monitor` CLI tails
                # them) + periodic OpenMetrics/JSON registry snapshots —
                # both factories return None when telemetry is off, so
                # the disabled path starts no thread and touches no file
                heartbeat = obs.start_heartbeat(self.paths.health_dir,
                                                step=self.profile_name)
                exporter = obs.start_exporter(self.paths.telemetry_dir,
                                              step=self.profile_name)
                # torn-run detection: the journal stays "running" until
                # the step commits, so a crash anywhere below leaves the
                # marker the next run (and downstream preconditions) read
                self.journal.open_run()
                with self._device_trace(), \
                        obs.span("process", kind="phase"):
                    code = self.process()
                root.set(exit_code=code)
                if code == 0:
                    self.journal.complete(exit_code=0)
        finally:
            # retire the live plane, then flush — even when the step
            # raised: a crashed run's partial trace (with the error-
            # marked span) is exactly the one you want to read, and the
            # final heartbeat (state=exited) is how the monitor tells a
            # clean exit from a silent death
            if heartbeat is not None:
                heartbeat.stop(exit_code=code)
            if exporter is not None:
                exporter.stop()
            if telemetry:
                self._flush_telemetry()
        total = time.time() - t0
        log.info("step %s done in %.2fs", self.step.name, total)
        self._write_profile(total)
        return code

    def _device_trace(self):
        """``shifu-tpu <step> --profile [dir]`` / ``-Dshifu.profile=<dir>``:
        wrap the step in a ``jax.profiler`` trace (XLA device timeline,
        viewable in TensorBoard/Perfetto) — see ``obs/profiler.py``.  The
        wall-clock ``phase()`` spans stay always-on (when telemetry is);
        this knob adds the compiled-op view when asked."""
        from ..obs.profiler import profile_step
        return profile_step(self.step.name.lower())

    def _flush_telemetry(self) -> None:
        """Append this run's spans/events + metrics snapshot to
        ``<modelset>/telemetry/trace.jsonl`` — the file ``analysis
        --telemetry`` renders.  Device-memory high-water samples here, at
        the step boundary (the per-step peak is the YARN-container-memory
        counter analogue)."""
        try:
            obs.sample_device_memory()
            # step-level surface of the shape-churn sentinel: recompiles
            # accumulated during THIS step (the registry resets at flush)
            # get one loud summary line beside the per-name warn-once
            rec = next((m.get("value") for m in obs.snapshot()
                        if m.get("name") == "xla.recompiles"), None)
            if rec:
                log.warning(
                    "step %s rebuilt %d executable(s) for new input "
                    "signatures (shape churn defeats the compile cache "
                    "— see `analysis --telemetry --utilization`)",
                    self.profile_name, int(rec))
            path = self.paths.telemetry_trace_path if self.paths else \
                os.path.join(self.dir, "telemetry", "trace.jsonl")
            obs.flush(path, step=self.profile_name)
        except Exception:                   # telemetry must never fail a step
            log.debug("telemetry flush failed", exc_info=True)

    # ------------------------------------------------------------ profiling
    def phase(self, name: str):
        """Time a named phase inside the step (reference aux tracing role,
        SURVEY §5): accumulates into ``tmp/profile.json`` per step AND
        opens a telemetry span nested under the step's root (no-op when
        telemetry is off)."""
        return _PhaseSpan(self._phases, name)

    @property
    def _phases(self) -> dict:
        if not hasattr(self, "_phase_spans"):
            self._phase_spans = {}
        return self._phase_spans

    def _write_profile(self, total_s: float) -> None:
        try:
            path = os.path.join(self.paths.tmp_dir, "profile.json")
            doc = {}
            if os.path.isfile(path):
                try:
                    with open(path) as f:
                        doc = json.load(f)
                except (json.JSONDecodeError, OSError):
                    doc = {}            # self-heal a truncated file
            doc[self.profile_name] = {
                "total_s": round(total_s, 3),
                "phases_s": {k: round(v, 3)
                             for k, v in self._phases.items()}}
            os.makedirs(self.paths.tmp_dir, exist_ok=True)
            ioutil.atomic_write_json(path, doc)
        except Exception:                       # profiling must never fail
            log.debug("profile write failed", exc_info=True)

    def process(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    # -------------------------------------------------------------- helpers
    def backup(self, path: str) -> None:
        """Keep one backup generation of a config file before overwrite."""
        if os.path.isfile(path):
            bdir = self.paths.backup_dir
            os.makedirs(bdir, exist_ok=True)
            shutil.copy2(path, os.path.join(bdir, os.path.basename(path)))


class _PhaseSpan:
    def __init__(self, store: dict, name: str):
        self.store = store
        self.name = name
        self._obs = None
        self._pending: dict = {}

    def __enter__(self):
        faults.fire("step", "phase", self.name)
        self._obs = obs.span(self.name, kind="phase", **self._pending)
        self._obs.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.store[self.name] = self.store.get(self.name, 0.0) \
            + (time.perf_counter() - self.t0)
        self._obs.__exit__(*exc)
        return False

    def set(self, **attrs):
        """Attach telemetry attributes (e.g. ``rows=`` for rows/sec in
        the report); usable before or inside the ``with``; no-op when
        telemetry is off."""
        if self._obs is None:
            self._pending.update(attrs)
        else:
            self._obs.set(**attrs)
        return self
