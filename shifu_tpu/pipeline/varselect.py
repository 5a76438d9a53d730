"""`varselect` step — reference ``VarSelectModelProcessor.java:95`` +
``core/VariableSelector.java`` + the sensitivity MR job (``core/varselect/``).

Paths implemented:
- filter-based ranking by KS / IV / MIX / PARETO over the stats already in
  ColumnConfig (``VarSelectModelProcessor.java:181-199``);
- auto-filter: missing-rate, min KS/IV, and pairwise-correlation pruning
  (drop the lower-ranked of any pair above ``correlationThreshold``);
- SE / ST sensitivity: the reference trains an NN then runs an MR job that
  re-scores every record with feature i frozen to its mean
  (``core/varselect/VarSelectMapper.java:93-120``) — here that whole job is
  the STREAMED, mask-batched device program of
  :mod:`shifu_tpu.ops.sensitivity`: the norm plane streams window-by-window
  (never host-resident), each window evaluates ``MaskBatch`` frozen-column
  masks per vmapped launch, scores fetch ONCE at the end; score[i] = MSE
  rise when column i's feature block is frozen;
- force-select / force-remove name files; ``-list`` / ``-reset`` /
  ``-recover`` bookkeeping with a varsel history file.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..config import ColumnConfig
from ..config.model_config import FilterBy
from ..config.validator import ModelStep
from .processor import BasicProcessor

log = logging.getLogger(__name__)


def pareto_front_ranks(ks: np.ndarray, iv: np.ndarray) -> np.ndarray:
    """Iterative Pareto fronts over (ks, iv): rank 0 = first front
    (reference PARETO filter).  Each front computes ONE broadcast
    domination matrix (dominated[i] = any j with k_j>=k_i, v_j>=v_i and a
    strict edge) instead of the former per-point Python scan."""
    n = len(ks)
    remaining = np.arange(n)
    ranks = np.zeros(n, int)
    r = 0
    while len(remaining):
        k, v = ks[remaining], iv[remaining]
        ge = (k[:, None] >= k[None, :]) & (v[:, None] >= v[None, :])
        gt = (k[:, None] > k[None, :]) | (v[:, None] > v[None, :])
        dominated = np.any(ge & gt, axis=0)
        front = remaining[~dominated]
        ranks[front] = r
        remaining = remaining[dominated]
        r += 1
    return ranks


class VarSelectProcessor(BasicProcessor):
    step = ModelStep.VARSELECT

    def process(self) -> int:
        if self.params.get("list"):
            return self._list()
        if self.params.get("reset"):
            return self._reset()
        if self.params.get("recover"):
            return self._recover()
        if self.params.get("autofilter"):
            return self._autofilter_only()
        if self.params.get("recoverauto"):
            return self._recover_auto()
        return self._select()

    # ---------------------------------------------------------- bookkeeping
    def _selected(self) -> List[ColumnConfig]:
        return [c for c in self.column_configs if c.finalSelect]

    def _list(self) -> int:
        for c in self._selected():
            log.info("selected: %3d %s (ks=%.4f iv=%.4f)", c.columnNum,
                     c.columnName, c.columnStats.ks or 0, c.columnStats.iv or 0)
        log.info("%d columns selected", len(self._selected()))
        return 0

    def _reset(self) -> int:
        self._push_history()
        for c in self.column_configs:
            c.finalSelect = False
        self.save_column_configs()
        log.info("selection reset")
        return 0

    @staticmethod
    def _pop_last_history(path: str, what: str, apply_fn) -> bool:
        """Parse the last JSONL entry of a history file, run ``apply_fn``
        on it, and only THEN truncate the file — a failure while parsing
        or applying leaves the undo entry intact for a retry."""
        if not os.path.isfile(path):
            log.error("no %s history to recover from", what)
            return False
        lines = open(path).read().strip().splitlines()
        if not lines:
            log.error("%s history empty", what)
            return False
        apply_fn(json.loads(lines[-1]))
        # atomic truncation: a crash mid-rewrite must not tear the
        # remaining history (the torn-write hazard PR 4 eliminated for
        # every other artifact)
        from ..ioutil import atomic_write_text
        atomic_write_text(path, "\n".join(lines[:-1])
                          + ("\n" if lines[:-1] else ""))
        return True

    def _recover(self) -> int:
        def apply(last):
            sel = set(last["selected"])
            for c in self.column_configs:
                c.finalSelect = c.columnNum in sel
            self.save_column_configs()
            log.info("recovered selection of %d columns (ts %s)",
                     len(sel), last.get("ts"))
        return 0 if self._pop_last_history(
            self.paths.varsel_history_path, "varsel", apply) else 1

    def _push_history(self) -> None:
        os.makedirs(self.paths.varsel_dir, exist_ok=True)
        entry = {"ts": time.strftime("%Y-%m-%d %H:%M:%S"),
                 "selected": [c.columnNum for c in self._selected()]}
        # append-only history ledger: readers tolerate a torn tail
        with open(self.paths.varsel_history_path, "a") as f:  # shifu-lint: disable=atomic-write
            f.write(json.dumps(entry) + "\n")

    # ------------------------------------------------- standalone autofilter
    def _autofilter_only(self) -> int:
        """``varselect -autofilter`` (reference ``ShifuCLI.java:836``):
        apply ONLY the missing-rate/KS/IV/correlation auto filter to the
        currently selected columns, recording what it turned off so
        ``-recoverauto`` can undo it."""
        vs = self.model_config.varSelect
        selected = [c for c in self.column_configs
                    if c.finalSelect and not c.is_force_select()]
        if not selected:
            log.error("no selected columns to auto-filter — run a "
                      "selection first")
            return 1
        kept = {c.columnNum for c in self._auto_filter(selected, vs)}
        removed = [c.columnNum for c in selected if c.columnNum not in kept]
        if not removed:
            log.info("autofilter: nothing to remove (%d columns pass)",
                     len(kept))
            return 0
        for c in selected:
            c.finalSelect = c.columnNum in kept
        os.makedirs(self.paths.varsel_dir, exist_ok=True)
        # append-only history ledger: readers tolerate a torn tail
        with open(self._autofilter_history_path(), "a") as f:  # shifu-lint: disable=atomic-write
            f.write(json.dumps({"ts": time.strftime("%Y-%m-%d %H:%M:%S"),
                                "removed": removed}) + "\n")
        self.save_column_configs()
        log.info("autofilter: %d kept, %d removed", len(kept), len(removed))
        return 0

    def _recover_auto(self) -> int:
        """``varselect -recoverauto``: restore the variables the last
        ``-autofilter`` run turned off (reference ``ShifuCLI.java:837``)."""
        def apply(last):
            removed = set(last["removed"])
            n = 0
            for c in self.column_configs:
                if c.columnNum in removed:
                    c.finalSelect = True
                    n += 1
            self.save_column_configs()
            log.info("recovered %d auto-filtered columns (ts %s)", n,
                     last.get("ts"))
        return 0 if self._pop_last_history(
            self._autofilter_history_path(), "autofilter", apply) else 1

    def _autofilter_history_path(self) -> str:
        return os.path.join(self.paths.varsel_dir, "autofilter.history")

    # ------------------------------------------------------------- selection
    def _check_filterby_algorithm(self) -> None:
        """filterBy vs train.algorithm compatibility (reference
        ``VarSelectModelProcessor.java:188-200``) — checked BEFORE any side
        effect (history push, recursive retrain rounds)."""
        vs = self.model_config.varSelect
        if not vs.filterEnable:
            return
        fb, alg = vs.filterBy, self.model_config.train.algorithm.name
        from ..config.validator import ValidationError
        if fb in (FilterBy.SE, FilterBy.ST):
            from ..models.towers import refuse
            refuse(self.model_config, "varselect -wrapper")   # sensitivity re-scores an MLP
        if fb in (FilterBy.SE, FilterBy.ST) and \
                alg not in ("NN", "LR", "SVM", "TENSORFLOW"):
            raise ValidationError(
                [f"varSelect.filterBy {fb.name} needs an NN/LR model "
                 f"(train.algorithm is {alg}) — use filterBy FI for "
                 "tree models"])
        if fb == FilterBy.FI and alg not in ("GBT", "RF", "DT"):
            raise ValidationError(
                [f"varSelect.filterBy FI needs a tree model "
                 f"(train.algorithm is {alg}) — use SE/ST for NN/LR"])

    def _select(self) -> int:
        vs = self.model_config.varSelect
        self._check_filterby_algorithm()
        rounds = int(self.params.get("recursive") or 1)
        if rounds > 1:
            if vs.filterBy not in (FilterBy.SE, FilterBy.ST):
                log.error("varselect -recursive needs filterBy SE/ST "
                          "(wrapper re-scoring); got %s", vs.filterBy.name)
                return 1
            return self._recursive_select(rounds)
        return self._select_once()

    def _recursive_select(self, rounds: int) -> int:
        """SE/ST wrapper recursion (reference
        ``VarSelectModelProcessor.java:201-227``): each round re-norms and
        retrains on the CURRENT selection, re-scores sensitivity against
        the fresh model, re-selects, and snapshots ``ColumnConfig.json.{i}``
        + ``se.{i}.json`` into varsels/ for audit (reference varsel dir
        history + ``se.x`` copies)."""
        from .norm import NormalizeProcessor
        from .train import TrainProcessor
        os.makedirs(self.paths.varsel_dir, exist_ok=True)
        self._snapshot_round(0)
        for i in range(rounds):
            self.save_column_configs()   # current selection feeds norm/train
            for proc_cls in (NormalizeProcessor, TrainProcessor):
                rc = proc_cls(self.dir, {}).run()
                if rc != 0:
                    log.error("recursive varselect round %d: %s failed "
                              "(rc=%d)", i + 1, proc_cls.__name__, rc)
                    return rc
            rc = self._select_once()
            if rc != 0:
                return rc
            self._snapshot_round(i + 1)
            se_src = os.path.join(self.paths.varsel_dir, "se.json")
            if os.path.isfile(se_src):
                _atomic_copy(se_src, os.path.join(self.paths.varsel_dir,
                                                  f"se.{i}.json"))
            log.info("recursive varselect round %d/%d: %d selected",
                     i + 1, rounds, len(self._selected()))
        return 0

    def _snapshot_round(self, i: int) -> None:
        src = self.paths.column_config_path
        if os.path.isfile(src):
            _atomic_copy(src, os.path.join(self.paths.varsel_dir,
                                           f"ColumnConfig.json.{i}"))

    def _select_once(self) -> int:
        vs = self.model_config.varSelect
        self._push_history()
        self._apply_force_files(vs)
        candidates = [c for c in self.column_configs
                      if c.is_candidate() and not c.is_force_select()
                      and c.columnStats.ks is not None]
        if vs.autoFilterEnable:
            candidates = self._auto_filter(candidates, vs)
        # clear stale selection on every non-forced column first: columns
        # pruned from `candidates` this run must not keep finalSelect from a
        # previous run
        for c in self.column_configs:
            if not c.is_force_select():
                c.finalSelect = False
        if not vs.filterEnable:
            for c in candidates:
                c.finalSelect = True
            self.save_column_configs()
            return 0

        fb = vs.filterBy
        if fb in (FilterBy.SE, FilterBy.ST):
            scores = self._sensitivity_scores(candidates, fb)
        elif fb == FilterBy.GENETIC:
            scores = self._genetic_scores(candidates, vs)
        elif fb == FilterBy.FI:
            scores = self._fi_scores(candidates)
        elif fb == FilterBy.IV:
            scores = {c.columnNum: c.columnStats.iv or 0 for c in candidates}
        elif fb == FilterBy.MIX:
            # MIX: mean of per-metric ranks (reference mixed KS+IV rank)
            ks_rank = _rank_of({c.columnNum: c.columnStats.ks or 0
                                for c in candidates})
            iv_rank = _rank_of({c.columnNum: c.columnStats.iv or 0
                                for c in candidates})
            scores = {k: -(ks_rank[k] + iv_rank[k]) / 2 for k in ks_rank}
        elif fb == FilterBy.PARETO:
            ks = np.array([c.columnStats.ks or 0 for c in candidates])
            iv = np.array([c.columnStats.iv or 0 for c in candidates])
            ranks = pareto_front_ranks(ks, iv)
            scores = {c.columnNum: -float(r)
                      for c, r in zip(candidates, ranks)}
        else:  # KS default
            scores = {c.columnNum: c.columnStats.ks or 0 for c in candidates}

        # -inf marks columns the scoring model never saw (dropped in an
        # earlier recursive round): never selectable, not merely last —
        # and excluded BEFORE the filterOutRatio math so the ratio applies
        # to the selectable set
        candidates = [c for c in candidates
                      if scores[c.columnNum] != float("-inf")]
        n_keep = vs.filterNum
        if vs.filterOutRatio is not None:
            n_keep = min(n_keep,
                         int(len(candidates) * (1 - vs.filterOutRatio)))
        ranked = sorted(candidates, key=lambda c: -scores[c.columnNum])
        keep = set(c.columnNum for c in ranked[:n_keep])
        for c in candidates:
            c.finalSelect = c.columnNum in keep
        self.save_column_configs()
        n_force = sum(1 for c in self.column_configs if c.is_force_select())
        log.info("varselect by %s: %d selected (+%d force), from %d candidates",
                 fb.name, len(keep), n_force, len(candidates))
        return 0

    def _apply_force_files(self, vs) -> None:
        from ..config.column_config import ColumnFlag, ns_in
        force_sel = _read_names(self._abs(vs.forceSelectColumnNameFile))
        force_rem = _read_names(self._abs(vs.forceRemoveColumnNameFile))
        for c in self.column_configs:
            # NSColumn matching: bare names in force files match namespaced
            # header columns (reference column/NSColumn.java equality)
            if ns_in(c.columnName, force_rem):
                c.columnFlag = ColumnFlag.ForceRemove
                c.finalSelect = False
            elif ns_in(c.columnName, force_sel) and c.is_candidate():
                c.columnFlag = ColumnFlag.ForceSelect
                c.finalSelect = True

    def _auto_filter(self, candidates: List[ColumnConfig], vs
                     ) -> List[ColumnConfig]:
        """Missing-rate + min KS/IV + correlation pruning (reference
        autoFilter / ``VarSelectModelProcessor.java:208``)."""
        out = []
        for c in candidates:
            miss = c.columnStats.missingPercentage or 0.0
            if miss > vs.missingRateThreshold:
                continue
            if (c.columnStats.ks or 0) < vs.minKsThreshold:
                continue
            if (c.columnStats.iv or 0) < vs.minIvThreshold:
                continue
            out.append(c)
        dropped = len(candidates) - len(out)
        if vs.correlationThreshold < 1.0:
            out, corr_dropped = self._correlation_prune(out, vs)
            dropped += corr_dropped
        if dropped:
            log.info("auto-filter removed %d columns", dropped)
        return out

    def _correlation_prune(self, cols: List[ColumnConfig], vs
                           ) -> Tuple[List[ColumnConfig], int]:
        corr_path = self.paths.correlation_path
        if not os.path.isfile(corr_path):
            log.warning("correlation matrix missing — run `stats -correlation`"
                        " first; skipping correlation pruning")
            return cols, 0
        # csv written by stats: header row + name-keyed rows
        with open(corr_path) as f:
            header = f.readline().strip().split(",")[1:]
            mat = np.array([[float(v) for v in line.strip().split(",")[1:]]
                            for line in f])
        idx = {n: i for i, n in enumerate(header)}
        ranked = sorted(cols, key=lambda c: -(c.columnStats.ks or 0))
        # index the matrix ONCE per candidate and compare against all kept
        # rows with a numpy mask (the former kept-vs-candidate inner loop
        # was nested dict lookups per pair)
        abs_mat = np.abs(mat)
        kept: List[ColumnConfig] = []
        kept_rows: List[int] = []            # matrix rows of kept columns
        for c in ranked:
            i = idx.get(c.columnName)
            if i is None or not kept_rows or \
                    not np.any(abs_mat[i, kept_rows]
                               > vs.correlationThreshold):
                kept.append(c)
                if i is not None:
                    kept_rows.append(i)
        kept_names = {c.columnName for c in kept}
        return [c for c in cols if c.columnName in kept_names], \
            len(cols) - len(kept)

    # ---------------------------------------------------------- sensitivity
    def _sensitivity_scores(self, candidates: List[ColumnConfig],
                            fb: FilterBy) -> Dict[int, float]:
        """SE/ST: ΔMSE when a column's feature block is frozen to its mean.

        The reference trains one NN then fans out an MR job
        (``VarSelectMapper.java:66``); here the whole job is the streamed,
        mask-batched device program of :mod:`shifu_tpu.ops.sensitivity`:
        the norm plane streams window-by-window (never resident on host),
        each window evaluates ``MaskBatch`` candidate masks per vmapped
        launch, and the scores come back in ONE end-of-job fetch.
        ``-Dshifu.varsel.batched=false`` restores the seed's resident
        per-column loop (the parity oracle)."""
        from .. import obs
        from ..config import environment
        from ..data.shards import Shards
        from ..ioutil import atomic_write_json
        from ..models import nn as nn_model
        from ..ops import sensitivity as sens

        model_path = self.paths.model_path(0, None)
        if not os.path.isfile(model_path):
            raise FileNotFoundError(
                f"{model_path} not found — SE/ST varselect needs a trained "
                "model; run `train` first (reference trains one inline)")
        spec, params = nn_model.load_model(model_path)
        shards = Shards.open(self.paths.norm_dir)
        names = shards.schema["outputNames"]
        col_nums = shards.schema["columnNums"]

        # map candidate column -> its feature indices (onehot/woe blocks,
        # frozen as WHOLE blocks)
        blocks = _column_blocks(names, col_nums, candidates)
        in_plane = [c for c in candidates if blocks.get(c.columnNum)]
        if not in_plane:
            raise RuntimeError("SE/ST varselect: no candidate feature "
                               "blocks in the normalized plane — run `norm`")
        masks = sens.mask_matrix(
            len(names), [blocks[c.columnNum] for c in in_plane])

        t0 = time.perf_counter()
        with obs.span("varselect.sensitivity", kind="phase"):
            if environment.get_bool("shifu.varsel.batched", True):
                n_rows = self._run_streamed_sensitivity(
                    shards, spec, params, masks)
                mse, base_mse = self._sens_result
            else:               # escape hatch: the seed's resident loop
                data = shards.load_all()
                mse, base_mse = sens.per_column_scores(
                    spec, params, data["x"], data["y"], masks)
                n_rows = len(data["y"])
                self._sens_result = (mse, base_mse)
        dt = max(time.perf_counter() - t0, 1e-9)
        obs.gauge("varsel.rows_per_sec").set(n_rows * len(in_plane) / dt)
        obs.gauge("varsel.candidates").set(float(len(in_plane)))
        log.info("sensitivity: %d candidates x %d rows in %.2fs "
                 "(%.0f rows*cols/s)", len(in_plane), n_rows, dt,
                 n_rows * len(in_plane) / dt)

        scores = _scores_from_mse(candidates,
                                  [c.columnNum for c in in_plane],
                                  mse, base_mse, fb)
        os.makedirs(self.paths.varsel_dir, exist_ok=True)
        atomic_write_json(
            os.path.join(self.paths.varsel_dir, "se.json"),
            {str(k): v for k, v in
             sorted(scores.items(), key=lambda kv: -kv[1])
             if v != float("-inf")})
        return scores

    def _run_streamed_sensitivity(self, shards, spec, params,
                                  masks) -> int:
        """Window geometry + stream wiring for the mask-batched job;
        stashes (mse, base_mse) on ``self._sens_result`` and returns the
        row count."""
        from ..data.streaming import ShardStream, stream_window_rows
        from ..ops import sensitivity as sens
        from ..parallel.mesh import device_mesh

        vs = self.model_config.varSelect
        B = sens.mask_batch_size(vs.params)
        mesh = device_mesh()
        d = len(shards.schema["outputNames"])
        # the vmapped launch holds ~B frozen window copies: account B in
        # the row-bytes estimate so the auto window shrinks with the batch
        window_rows = stream_window_rows(4 * (d + 2) * max(1, B // 4),
                                         int(mesh.shape["data"]), shards)
        stream = ShardStream(shards, ("x", "y"), window_rows)
        log.info("sensitivity STREAMED: window %d rows, mask batch %d "
                 "(%d programs/window)", window_rows, B,
                 -(-len(masks) // B))
        mse, base_mse, n_rows = sens.streamed_sensitivity(
            stream, spec, params, masks, mesh=mesh, mask_batch=B)
        self._sens_result = (mse, base_mse)
        return n_rows

    def _genetic_scores(self, candidates: List[ColumnConfig],
                        vs) -> Dict[int, float]:
        """dvarsel wrapper search: a population of column subsets evolves by
        inherit/crossover/mutation, fitness = masked-NN validation loss, all
        candidates trained as one vmapped run (reference ``core/dvarsel/``;
        see ``train/dvarsel.py``).  Needs `norm` to have run.  Data mode
        follows the shared streaming decision (``should_stream``): planes
        past the memory budget evaluate fitness as minibatch scans over
        prepared windows instead of loading the matrix."""
        from ..data.shards import Shards
        from ..data.streaming import (ShardStream, should_stream,
                                      stream_window_rows)
        from ..ioutil import atomic_write_json
        from ..train.dvarsel import (WrapperSettings, genetic_varselect,
                                     genetic_varselect_streamed)

        shards = Shards.open(self.paths.norm_dir)
        names = shards.schema["outputNames"]
        col_nums = shards.schema["columnNums"]
        blocks = _column_blocks(names, col_nums, candidates)
        blocks = {cn: idx for cn, idx in blocks.items() if idx}
        if not blocks:
            raise RuntimeError("genetic varselect: no candidate feature "
                               "blocks in the normalized plane — run `norm`")
        settings = WrapperSettings.from_params(
            vs.params, n_select=min(vs.filterNum, len(blocks)),
            valid_rate=self.model_config.train.validSetRate)
        if should_stream(shards):
            from ..parallel.mesh import device_mesh
            mesh = device_mesh(n_ensemble=settings.population)
            window_rows = stream_window_rows(4 * (len(names) + 2),
                                             int(mesh.shape["data"]),
                                             shards)
            stream = ShardStream(shards, ("x", "y", "w"), window_rows)
            log.info("genetic varselect STREAMED: window %d rows, "
                     "population %d", window_rows, settings.population)
            scores, history = genetic_varselect_streamed(
                stream, blocks, settings, mesh=mesh)
        else:
            data = shards.load_all()
            scores, history = genetic_varselect(
                data["x"], data["y"], data["w"], blocks, settings)
        os.makedirs(self.paths.varsel_dir, exist_ok=True)
        atomic_write_json(
            os.path.join(self.paths.varsel_dir, "genetic.json"),
            {"history": history,
             "credit": {str(k): v for k, v in sorted(
                 scores.items(), key=lambda kv: -kv[1])}})
        # columns with no feature block rank last
        for c in candidates:
            scores.setdefault(c.columnNum, -1.0)
        return scores

    def _fi_scores(self, candidates: List[ColumnConfig]) -> Dict[int, float]:
        """FI filter: posttrain featureImportance output (tree FI or NN
        spread)."""
        fi_path = self.paths.feature_importance_path
        if not os.path.isfile(fi_path):
            raise FileNotFoundError(
                f"{fi_path} not found — FI varselect needs `posttrain` first")
        by_name = {}
        for line in open(fi_path):
            name, v = line.rsplit("\t", 1)
            by_name[name] = float(v)
        return {c.columnNum: by_name.get(c.columnName, 0.0)
                for c in candidates}


def _column_blocks(names: List[str], col_nums: List[int],
                   candidates: List[ColumnConfig]) -> Dict[int, List[int]]:
    """Feature indices per source column: output names are generated per
    column in order, prefixed by the column name (onehot expands)."""
    by_name = {c.columnName: c.columnNum for c in candidates}
    blocks: Dict[int, List[int]] = {}
    for i, n in enumerate(names):
        # output names are the FULL column name (namespaced names included)
        # plus an optional onehot suffix "_k"
        base = n
        if base not in by_name and "_" in base:
            stem = base.rsplit("_", 1)[0]
            if stem in by_name and base.rsplit("_", 1)[1].isdigit():
                base = stem
        cn = by_name.get(base)
        if cn is not None:
            blocks.setdefault(cn, []).append(i)
    return blocks


def _scores_from_mse(candidates: List[ColumnConfig],
                     in_plane_ids: List[int], mse: np.ndarray,
                     base_mse: float, fb: FilterBy) -> Dict[int, float]:
    """Frozen-MSE vector -> per-column SE/ST scores.  Candidates absent
    from the trained model's feature plane (e.g. dropped in an earlier
    recursive round) score ``-inf``: never selectable, not merely last —
    a 0.0 would outrank in-model columns with negative sensitivity and
    re-select a column the scoring model never saw."""
    scores = {c.columnNum: float("-inf") for c in candidates}
    for cn, m in zip(in_plane_ids, mse):
        # SE: absolute sensitivity; ST: relative rise over base
        scores[cn] = (float(m) - base_mse) if fb == FilterBy.SE \
            else (float(m) - base_mse) / max(base_mse, 1e-12)
    return scores


def _atomic_copy(src: str, dst: str) -> None:
    """Whole-or-nothing snapshot copy (``shutil.copy`` can leave a torn
    destination on a crash mid-write)."""
    from ..ioutil import atomic_write_bytes
    with open(src, "rb") as f:
        atomic_write_bytes(dst, f.read())


def _rank_of(scores: Dict[int, float]) -> Dict[int, int]:
    order = sorted(scores, key=lambda k: -scores[k])
    return {k: i for i, k in enumerate(order)}


def _read_names(path: Optional[str]) -> set:
    from ..config.column_config import read_column_name_file
    return read_column_name_file(path)
