"""`train` step — reference ``TrainModelProcessor.java:105`` re-imagined.

Loads the materialized norm (NN/LR/WDL) or cleaned-binned (GBT/RF) shards,
expands grid-search trials, builds bagging/k-fold row-weight matrices, and
runs the vmapped SPMD ensemble trainer.  The reference's N-YARN-job fan-out
(``runDistributedTrain``, ``:661-1029``) becomes ensemble members on the mesh;
progress lines replace the HDFS progress file + TailThread (``:1862``);
per-N-epoch tmp models land in ``models/tmp`` like ``NNOutput.postIteration``.
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import faults, obs
from ..config.model_config import Algorithm
from ..config.validator import ModelStep
from ..data.shards import Shards
from ..models import nn as nn_model
from ..train import grid_search
from ..train.nn_trainer import (TrainSettings, plane_layout,
                                train_ensemble)
from ..train.sampling import member_masks
from .processor import BasicProcessor

log = logging.getLogger(__name__)


def settings_from_params(params: Dict[str, Any], train_conf,
                         defaults: Optional[Dict[str, Any]] = None) -> TrainSettings:
    """Map reference ``train#params`` keys (``GridSearch``-compatible names:
    Propagation/LearningRate/RegularizedConstant/DropoutRate/...) onto
    TrainSettings."""
    p = dict(defaults or {})
    p.update(params or {})
    return TrainSettings(
        optimizer=str(p.get("Propagation", p.get("Optimizer", "R"))),
        learning_rate=float(p.get("LearningRate", 0.1)),
        learning_decay=float(p.get("LearningDecay", 0.0)),
        l2=float(p.get("RegularizedConstant", p.get("L2Const", 0.0))),
        l1=float(p.get("L1Const", 0.0)),
        dropout_rate=float(p.get("DropoutRate", 0.0)),
        epochs=int(train_conf.numTrainEpochs),
        batch_size=int(p.get("MiniBatchs", 0) or 0),
        early_stop_window=int(p.get("WindowSize", 10)
                              if train_conf.earlyStopEnable else 0),
        weight_initializer=str(p.get("WeightInitializer", "xavier")),
        seed=int(p.get("Seed", 0)),
        tmp_model_every=int(p.get("TmpModelEpochs", 0) or 0),
        checkpoint_every=int(p.get("CheckpointInterval", 25)),
        fixed_layers=tuple(int(v) for v in p.get("FixedLayers", []) or []),
        fixed_bias=bool(p.get("FixedBias", False)),
        matmul_precision=str(p.get("Precision", "") or ""),
        # training-precision ladder (f32 | bf16 | mixed); "" defers to
        # the -Dshifu.train.precision property, default f32
        precision=str(p.get("TrainPrecision", "") or ""),
    )


def nn_spec_from_params(input_dim: int, params: Dict[str, Any],
                        column_nums: List[int],
                        feature_names: List[str]) -> nn_model.NNModelSpec:
    """Reference NN shape keys: NumHiddenLayers / NumHiddenNodes /
    ActivationFunc (``NNMaster``/``DTrainUtils`` param names)."""
    nodes = params.get("NumHiddenNodes", [50])
    acts = params.get("ActivationFunc", ["tanh"] * len(nodes))
    n_layers = int(params.get("NumHiddenLayers", len(nodes)))
    nodes = [int(v) for v in nodes][:n_layers] or [50]
    acts = [str(a).lower() for a in acts][:n_layers] or ["tanh"]
    while len(acts) < len(nodes):
        acts.append(acts[-1])
    return nn_model.NNModelSpec(
        input_dim=input_dim, hidden_nodes=nodes, activations=acts,
        output_dim=1, output_activation="sigmoid",
        loss=str(params.get("Loss", "squared")).lower(),
        column_nums=column_nums, feature_names=feature_names)


def lr_spec(input_dim: int, params: Dict[str, Any], column_nums: List[int],
            feature_names: List[str]) -> nn_model.NNModelSpec:
    """LR as the degenerate 0-hidden-layer net: one sigmoid(xW+b) matmul —
    exactly ``LogisticRegressionWorker.java:302-346``'s model."""
    return nn_model.NNModelSpec(
        input_dim=input_dim, hidden_nodes=[], activations=[],
        output_dim=1, output_activation="sigmoid", loss="log",
        column_nums=column_nums, feature_names=feature_names,
        extra={"algorithm": "LR"})


def svm_spec(input_dim: int, params: Dict[str, Any], column_nums: List[int],
             feature_names: List[str]) -> nn_model.NNModelSpec:
    """Linear SVM: hinge loss on a linear head (reference
    ``core/alg/SVMTrainer.java`` Kernel/Gamma/Const params).  Nonlinear
    kernels (rbf/poly/sigmoid) train through the kernel-matrix dual solver
    (``train/svm_trainer.py``) and never reach this spec — except in
    STREAMED mode, where the kernel matrix cannot be materialized
    (coded error; the reference's libsvm SVM is local-only too).
    ``Const`` (the C penalty) maps to L2 ``1/(2C)`` on the weights — the
    textbook soft-margin objective scaled by C."""
    kernel = str(params.get("Kernel", "linear")).lower()
    if kernel != "linear":
        from ..config.errors import ErrorCode, ShifuError
        raise ShifuError(ErrorCode.ERROR_MODELCONFIG_NOT_VALIDATION,
                         f"SVM Kernel={kernel!r} cannot run in streamed/"
                         "out-of-core mode (the kernel matrix is "
                         "local-scale by nature); drop "
                         "-Dshifu.train.streaming or use NN/GBT")
    c_penalty = float(params.get("Const", 1.0))
    return nn_model.NNModelSpec(
        input_dim=input_dim, hidden_nodes=[], activations=[],
        output_dim=1, output_activation="linear", loss="hinge",
        column_nums=column_nums, feature_names=feature_names,
        extra={"algorithm": "SVM", "svm_const": c_penalty})


def _apply_svm_objective(settings, alg: Algorithm,
                         run_params: Dict[str, Any]) -> None:
    """Soft-margin C -> L2 1/(2C), default C=1.0 (svm_spec docstring) —
    the ONE place the SVM objective maps onto TrainSettings."""
    if alg == Algorithm.SVM:
        settings.l2 = 1.0 / (2.0 * float(run_params.get("Const", 1.0)))


class TrainProcessor(BasicProcessor):
    step = ModelStep.TRAIN

    def process(self) -> int:
        mc = self.model_config
        alg = mc.train.algorithm
        if self.params.get("dry"):
            log.info("dry run: algorithm=%s bags=%d epochs=%d", alg.name,
                     mc.train.baggingNum, mc.train.numTrainEpochs)
            return 0
        if self.journal.was_torn and not self.params.get("resume"):
            # the previous train died mid-step (journal never committed):
            # auto-resume from the trainer-state checkpoints — exactly
            # what an explicit `train -resume` would do; with no
            # checkpoint on disk the trainers fall back to fresh init
            log.info("train: previous run was interrupted — resuming "
                     "from trainer checkpoints")
            self.params["resume"] = True
        if alg in (Algorithm.NN, Algorithm.LR, Algorithm.SVM,
                   Algorithm.TENSORFLOW):
            # TENSORFLOW: the reference bridges to TF-on-YARN
            # (TrainModelProcessor.java:395-449); tpu-native IS the bridge —
            # the same net trains as the jitted NN path
            if alg == Algorithm.TENSORFLOW and (mc.train.params or {}).get("Tower"):
                # the slot's own use: an arbitrary deep tower, trained over
                # the binned plane by its own trainer
                from ..train.tower_trainer import run_tower_training
                return run_tower_training(self)
            if alg == Algorithm.TENSORFLOW:
                # the probe step enforces this too; the direct-API path
                # (callers constructing TrainProcessor without probe)
                # must hit the same coded wall, not a silent remap
                from ..config.meta import tf_ignored_param_problems
                from ..config.validator import ValidationError
                tf_problems = tf_ignored_param_problems(mc.train)
                if tf_problems:
                    raise ValidationError(tf_problems)
                log.info("algorithm TENSORFLOW: training the same network "
                         "on the native jitted NN path (documented "
                         "deviation — no TF interop; the reference's "
                         "TF-on-YARN bridge role is served by XLA)")
            return self._train_nn_family(
                Algorithm.NN if alg == Algorithm.TENSORFLOW else alg)
        if alg in (Algorithm.GBT, Algorithm.RF, Algorithm.DT):
            from ..train.dt_trainer import run_tree_training
            return run_tree_training(self)
        if alg == Algorithm.WDL:
            from ..train.wdl_trainer import run_wdl_training
            return run_wdl_training(self)
        raise ValueError(f"unsupported algorithm {alg}")


    def _trials(self, params: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Grid trials: explicit per-line file (train.gridConfigFile,
        validated per trial via the meta schema) or cartesian expansion of
        list-valued params; file trials inherit unlisted keys from
        train#params."""
        gcf = self.model_config.train.gridConfigFile
        if gcf:
            file_trials = grid_search.load_grid_config(self._abs(gcf))
            # list-valued params are grid axes in their own right — expand
            # them first so a file trial that doesn't mention the key
            # doesn't inherit a raw list (cartesian product of both)
            base_trials = grid_search.expand(params) \
                if grid_search.is_grid_search(params) else [params]
            merged = [{**b, **t} for b in base_trials for t in file_trials]
            # a file trial that sets an expanded key collapses that axis —
            # drop the resulting exact duplicates (keep first occurrence)
            seen, trials = set(), []
            for t in merged:
                key = tuple(sorted((k, repr(v)) for k, v in t.items()))
                if key not in seen:
                    seen.add(key)
                    trials.append(t)
            from ..config.meta import validate_train_params
            problems = []
            for i, t in enumerate(trials):
                for p in validate_train_params(
                        t, self.model_config.train.algorithm):
                    problems.append(f"gridConfigFile trial {i + 1}: {p}")
            if problems:
                from ..config.validator import ValidationError
                raise ValidationError(problems)
            return trials
        if grid_search.is_grid_search(params):
            return grid_search.expand(params)
        return [params]

    # ------------------------------------------------------------ NN / LR
    def _train_nn_family(self, alg: Algorithm) -> int:
        from ..config.model_config import MultipleClassification
        mc = self.model_config
        K = len(mc.dataSet.posTags) if mc.is_multi_class() else 0
        ova = K > 2 and mc.train.multiClassifyMethod == \
            MultipleClassification.ONEVSALL
        if ova and (mc.train.gridConfigFile or
                    grid_search.is_grid_search(mc.train.params or {})):
            # ONE guard for both the in-RAM and streamed paths
            raise ValueError("grid search is not supported with "
                             "ONEVSALL multi-class")
        shards = self._open_shards(self.paths.norm_dir)
        if self._use_streaming(shards, shards.schema):
            return self._train_nn_streamed(alg, shards, n_classes=K,
                                           ova=ova)
        params = dict(mc.train.params or {})
        trials = self._trials(params)
        is_gs = len(trials) > 1
        kfold = mc.train.numKFold if mc.train.isCrossValidation else -1
        bags = 1 if is_gs else max(1, mc.train.baggingNum)
        kernel_svm = alg == Algorithm.SVM and str(params.get(
            "Kernel", "linear")).lower() != "linear"
        shuffle = bool(self.params.get("shuffle"))
        on_device = None
        if not (is_gs or shuffle or kernel_svm):
            # one run, and nothing here selects rows of x (the split and
            # the bags are row weights): the trainer is x's only consumer
            # and wants it on the device, so the loader builds it there
            members = (kfold if kfold > 1 else bags) * (K if ova else 1)
            _, _, x_layout = plane_layout(
                settings_from_params(params, mc.train), members)
            on_device = {"x": x_layout}
        with self.phase("load_data"):
            data = shards.load_all(on_device)
        x, y, w = data["x"], data["y"], data["w"]
        if shuffle:
            # reference `train -shuffle` re-randomizes row order before
            # training (MapReduceShuffle re-run)
            perm = np.random.default_rng(0).permutation(len(y))
            x, y, w = x[perm], y[perm], w[perm]
        schema = shards.schema
        column_nums = schema.get("columnNums", [])
        feature_names = schema.get("outputNames", [])
        n, d = len(y), x.shape[1]
        log.info("train %s: %d rows x %d features", alg.name, n, d)

        if kernel_svm:
            # nonlinear kernels leave the shared NN machinery: the
            # reference's libsvm C-SVC becomes an MXU kernel-matrix dual
            # solve (train/svm_trainer.py)
            return self._train_kernel_svm(x, y, w, column_nums,
                                          feature_names)

        os.makedirs(self.paths.tmp_models_dir, exist_ok=True)
        progress_path = self.paths.progress_path
        t0 = time.time()

        results = []
        # live progress stream, tailed by operators; a torn tail is
        # tolerated and resume replays it from the journal (PR 4)
        with open(progress_path, "w") as pf:  # shifu-lint: disable=atomic-write
            # grid trials group by structural shape: same-shape trials train
            # as ONE vmapped run with per-member hyper arrays; non-grid =
            # one run with all bagging members vmapped together
            runs = grid_search.stackable_groups(trials) if is_gs \
                else [list(range(bags))]
            for run in runs:
                run_params = trials[run[0]] if is_gs else dict(params)
                spec = self._make_spec(alg, d, run_params, column_nums,
                                       feature_names)
                if K > 2 and not ova:
                    # NATIVE multiclass: one softmax head over K classes
                    spec.output_dim = K
                    spec.output_activation = "softmax"
                    spec.extra["n_classes"] = K
                settings = settings_from_params(run_params, mc.train)
                _apply_svm_objective(settings, alg, run_params)
                if not is_gs:
                    # trainer-state fail-over checkpoints (grid trials are
                    # cheap; only full runs checkpoint/resume)
                    settings.checkpoint_dir = self.paths.checkpoint_dir
                    settings.resume = bool(self.params.get("resume"))
                    # refresh warm-start: N MORE epochs past the
                    # restored state (plain resume keeps the budget)
                    settings.resume_extra = int(
                        self.params.get("refresh_extra") or 0)
                run_kfold = kfold if not is_gs else -1
                up_w = mc.train.upSampleWeight
                if K > 2 and up_w != 1.0:
                    # up-sampling is a binary notion (reference restricts it
                    # to regression/binary); class indices would skew
                    # arbitrary classes
                    log.warning("upSampleWeight ignored for multi-class")
                    up_w = 1.0
                with obs.span("train.split", rows=n):
                    train_w, valid_w = member_masks(
                        n, 1 if is_gs else bags,
                        valid_rate=mc.train.validSetRate,
                        kfold=run_kfold,
                        sample_rate=mc.train.baggingSampleRate,
                        replacement=mc.train.baggingWithReplacement,
                        stratified=mc.train.stratifiedSample,
                        up_sample_weight=up_w,
                        targets=y, seed=settings.seed)
                    if is_gs:
                        # every trial in the group sees the SAME split —
                        # they must differ only in hypers, never in data
                        # draw
                        train_w = np.tile(train_w, (len(run), 1))
                        valid_w = np.tile(valid_w, (len(run), 1))
                    y_members = None
                    if ova:
                        # fan each bagging member out per class: member
                        # b*K+k trains class k's binary task on bag b's mask
                        b0 = train_w.shape[0]
                        train_w = np.repeat(train_w, K, axis=0)
                        valid_w = np.repeat(valid_w, K, axis=0)
                        y_members = np.tile(
                            np.stack([(y == k).astype(np.float32)
                                      for k in range(K)]), (b0, 1))
                        spec.extra.update(
                            {"ova_classes": K, "n_classes": K})
                    # kfold mode yields numKFold members
                    n_members = train_w.shape[0]
                    train_w = train_w * w[None, :]
                    valid_w = valid_w * w[None, :]
                init_list = self._continuous_init(spec, n_members, alg,
                                                  settings)

                member_hypers = None
                if is_gs and len(run) > 1:
                    # the group's trials differ only in stackable scalars —
                    # feed them as per-member arrays, one compiled run;
                    # identical init so the comparison isolates the hypers
                    if init_list is None:
                        import jax
                        p0 = nn_model.init_params(
                            jax.random.PRNGKey(settings.seed), spec,
                            settings.weight_initializer)
                        init_list = [p0] * len(run)
                    else:
                        # continuous warm-start: every trial resumes from
                        # the SAME saved model, not one bagged model each
                        init_list = [init_list[0]] * len(run)
                    tsl = [settings_from_params(trials[t], mc.train)
                           for t in run]
                    base_lr = settings.learning_rate
                    member_hypers = {
                        "lr_scale": np.array([s.learning_rate / base_lr
                                              for s in tsl]),
                        "l2": np.array([s.l2 for s in tsl]),
                        "l1": np.array([s.l1 for s in tsl]),
                        "dropout": np.array([s.dropout_rate for s in tsl]),
                    }
                with self.phase("train"):
                    res = train_ensemble(
                        x, y, train_w, valid_w, spec, settings,
                        init_params_list=init_list,
                        progress=self._progress_fn(pf, run),
                        checkpoint=self._checkpoint_fn(spec, alg),
                        y_members=y_members,
                        member_hypers=member_hypers)
                results.append((run, spec, res,
                                [trials[t] for t in run] if is_gs
                                else run_params))

        with self.phase("save_models"):
            self._write_models(results, alg, is_gs)
        log.info("train done in %.1fs", time.time() - t0)
        return 0

    # -------------------------------------------------------- streaming
    def _train_kernel_svm(self, x, y, w, column_nums, feature_names) -> int:
        """Nonlinear-kernel SVM bags (reference ``SVMTrainer.java``
        Kernel/Gamma/Const; local-scale by design — see
        ``train/svm_trainer.py`` for the dual formulation)."""
        from ..models.svm import SVMModelSpec, save_model
        from ..train.svm_trainer import train_kernel_svm
        from ..train.sampling import member_masks

        mc = self.model_config
        params = dict(mc.train.params or {})
        if grid_search.is_grid_search(params):
            raise ValueError("grid search is not supported for kernel SVM "
                             "(single local-scale solve per bag)")
        n, d = x.shape
        kernel = str(params.get("Kernel", "linear")).lower()
        kernel = {"radialbasisfunction": "rbf"}.get(kernel, kernel)
        spec = SVMModelSpec(
            input_dim=d, kernel=kernel,
            gamma=float(params.get("Gamma", 1.0 / max(d, 1))),
            coef0=float(params.get("Coef0", 0.0)),
            degree=int(params.get("Degree", 3)),
            column_nums=column_nums, feature_names=feature_names,
            extra={"algorithm": "SVM"})
        c_penalty = float(params.get("Const", 1.0))
        bags = max(1, mc.train.baggingNum)
        os.makedirs(self.paths.models_dir, exist_ok=True)
        # per-bag commit hooks: each solved bag journals its model, so an
        # interrupted multi-bag run resumes at the first unsolved bag
        # (the kernel SVM's "epoch" is the whole dual solve)
        items = self.journal.arm({"alg": "SVM", "kernel": spec.kernel,
                                  "const": c_penalty, "bags": bags},
                                 resume=bool(self.params.get("resume")))
        with open(self.paths.progress_path, "a" if items else "w") as pf:
            for b in range(bags):
                path = os.path.join(self.paths.models_dir, f"model{b}.svm")
                if items.get(f"bag-{b}"):
                    log.info("svm bag %d: already solved, skipping", b)
                    continue
                faults.fire("train", "bag", b, path=path)
                tw, _ = member_masks(
                    n, 1, valid_rate=mc.train.validSetRate,
                    sample_rate=mc.train.baggingSampleRate,
                    replacement=mc.train.baggingWithReplacement,
                    targets=y, seed=b)
                train_mask = (tw[0] > 0) & (w > 0)
                sv_x, alpha_y, tr, va, n_sv = train_kernel_svm(
                    x, y, train_mask, spec, c_penalty)
                save_model(path, spec, sv_x, alpha_y)
                self.journal.commit_item(f"bag-{b}", files=[path],
                                         valid_err=float(va))
                pf.write(f"Trainer #{b} Train Error: {tr:.6f} "
                         f"Validation Error: {va:.6f} ({n_sv} SVs)\n")
                log.info("svm bag %d: %d SVs -> %s", b, n_sv, path)
        return 0

    def _open_shards(self, directory: str) -> Shards:
        """The step's view of the materialized plane.  The refresh loop
        passes ``window_cursor`` (rows earlier trainings consumed) so a
        warm retrain streams only the NEW data windows — shard-aligned,
        see :meth:`Shards.from_row`."""
        shards = Shards.open(directory)
        cur = int(self.params.get("window_cursor") or 0)
        if cur:
            view = shards.from_row(cur)
            log.info("data-window cursor %d: training on %d of %d rows "
                     "(%d of %d shards)", cur, view.num_rows,
                     shards.num_rows, view.n_shards, shards.n_shards)
            return view
        return shards

    def _use_streaming(self, shards: Shards, schema: dict) -> bool:
        """Out-of-core mode when the materialized data exceeds the memory
        budget (reference ``guagua.data.memoryFraction`` role) or when
        forced via ``-Dshifu.train.streaming=on`` — the shared
        :func:`data.streaming.should_stream` decision (varselect's
        sensitivity/genetic planes consult the same one)."""
        from ..data.streaming import should_stream
        return should_stream(shards, schema)

    def _train_nn_streamed(self, alg: Algorithm, shards: Shards,
                           n_classes: int = 0, ova: bool = False) -> int:
        """Streamed counterpart of the in-RAM branch: windows flow through
        ``train_ensemble_streamed``; sampling masks are stateless hashes of
        the global row index (``data.streaming``)."""
        from ..config import environment
        from ..data.streaming import (ShardStream, mask_fn_from_settings,
                                      stream_window_rows)
        from ..parallel.mesh import device_mesh
        from ..train.nn_trainer import train_ensemble_streamed

        mc = self.model_config
        schema = shards.schema
        column_nums = schema.get("columnNums", [])
        feature_names = schema.get("outputNames", [])
        d = len(feature_names)
        n_rows = schema.get("numRows") or shards.num_rows

        params = dict(mc.train.params or {})
        trials = self._trials(params)
        is_gs = len(trials) > 1
        kfold = mc.train.numKFold if mc.train.isCrossValidation else -1
        bags = 1 if is_gs else max(1, mc.train.baggingNum)
        if mc.train.stratifiedSample:
            log.warning("streaming: stratified validation degrades to "
                        "Bernoulli split (needs a global pass)")
        if self.params.get("shuffle"):
            log.warning("streaming: `train -shuffle` ignored; use "
                        "`norm -shuffle` to reshuffle the materialized shards")

        K = n_classes if ova else 0
        # members on the ensemble axis: k-fold overrides bagging count;
        # OVA fans each bag out per class (member b*K + k trains class k,
        # the in-RAM y_members convention)
        mesh_members = kfold if (not is_gs and kfold and kfold > 1) else bags
        if ova:
            mesh_members = mesh_members * K
        mesh = device_mesh(n_ensemble=mesh_members)
        data_size = mesh.shape["data"]
        window_rows = stream_window_rows(4 * (d + 2), data_size, shards)
        log.info("train %s STREAMED: %d rows x %d features, window %d rows",
                 alg.name, n_rows, d, window_rows)

        # elastic multi-controller mode (-Dshifu.dcn.elastic + a stable
        # SHIFU_PROCESS_ID): the cross-process combine rides the quorum
        # step protocol instead of the in-mesh psum — grid-search trials
        # keep the synchronous path (their step namespaces would collide)
        ectx = None
        if not is_gs:
            from ..parallel.elastic import elastic_context_for
            ectx = elastic_context_for(self.dir, step_name="TRAIN")
            if ectx is not None:
                ectx.start()

        os.makedirs(self.paths.tmp_models_dir, exist_ok=True)
        t0 = time.time()
        results = []
        with open(self.paths.progress_path, "w") as pf:  # shifu-lint: disable=atomic-write
            runs = [[t] for t in range(len(trials))] if is_gs \
                else [list(range(bags))]
            for run in runs:
                run_params = trials[run[0]] if is_gs else dict(params)
                spec = self._make_spec(alg, d, run_params, column_nums,
                                       feature_names)
                if n_classes > 2 and not ova:
                    spec.output_dim = n_classes
                    spec.output_activation = "softmax"
                    spec.extra["n_classes"] = n_classes
                if ova:
                    spec.extra.update({"ova_classes": K, "n_classes": K})
                settings = settings_from_params(run_params, mc.train)
                _apply_svm_objective(settings, alg, run_params)
                if not is_gs:
                    settings.checkpoint_dir = self.paths.checkpoint_dir
                    settings.resume = bool(self.params.get("resume"))
                    settings.resume_extra = int(
                        self.params.get("refresh_extra") or 0)
                run_kfold = kfold if not is_gs else -1
                n_members = run_kfold if (run_kfold and run_kfold > 1) \
                    else (len(run) if is_gs else bags)
                up_w = mc.train.upSampleWeight
                if n_classes > 2 and up_w != 1.0:
                    log.warning("upSampleWeight ignored for multi-class")
                    up_w = 1.0
                mask_fn = mask_fn_from_settings(
                    n_members, valid_rate=mc.train.validSetRate,
                    kfold=run_kfold,
                    sample_rate=mc.train.baggingSampleRate,
                    replacement=mc.train.baggingWithReplacement,
                    up_sample_weight=up_w,
                    seed=settings.seed)
                member_classes = None
                if ova:
                    # repeat each bag's masks per class; member b*K + k
                    # binarizes class k ON DEVICE in the streamed trainer.
                    # The K host copies of each bag mask cost K*4B/row vs
                    # the window's d*4B/row feature transfer — a few
                    # percent for typical K; indexing base masks on
                    # device (m // K) would remove it if K grows
                    base_fn, b0 = mask_fn, n_members
                    def mask_fn(idx, targets, base_fn=base_fn):
                        tm, vm = base_fn(idx, targets)
                        return (np.repeat(tm, K, axis=0),
                                np.repeat(vm, K, axis=0))
                    member_classes = [k for _ in range(b0)
                                      for k in range(K)]
                    n_members = b0 * K
                # full-batch streams take the shape-stable remainder
                # ladder (tail window shrinks instead of padding to W);
                # the minibatch mode slices windows by fixed W-derived
                # edges, so it keeps the uniform shape
                stream = ShardStream(
                    shards, ("x", "y", "w"), window_rows,
                    remainder_multiple=data_size
                    if settings.batch_size == 0 else 0)
                init_list = self._continuous_init(spec, n_members, alg,
                                                  settings)
                run_elastic = ectx
                if ectx is not None and settings.batch_size != 0:
                    log.warning("elastic mode needs full-batch streaming "
                                "(MiniBatchs=0); this run stays "
                                "synchronous")
                    run_elastic = None
                try:
                    res = train_ensemble_streamed(
                        stream, spec, settings, n_members, mask_fn,
                        init_params_list=init_list,
                        progress=self._progress_fn(pf, run),
                        checkpoint=self._checkpoint_fn(spec, alg),
                        mesh=mesh, member_classes=member_classes,
                        elastic=run_elastic)
                except BaseException:
                    if ectx is not None:
                        ectx.stop(exit_code=1)
                        ectx = None
                    raise
                results.append((run, spec, res, run_params))
        if ectx is not None:
            ectx.stop(exit_code=0)

        self._write_models(results, alg, is_gs)
        log.info("train done in %.1fs (streamed)", time.time() - t0)
        return 0

    # ---------------------------------------------------- shared run setup
    def _make_spec(self, alg: Algorithm, d: int, run_params: Dict[str, Any],
                   column_nums, feature_names):
        if alg == Algorithm.SVM:
            return svm_spec(d, run_params, column_nums, feature_names)
        if alg == Algorithm.LR:
            return lr_spec(d, run_params, column_nums, feature_names)
        return nn_spec_from_params(d, run_params, column_nums, feature_names)

    def _progress_fn(self, pf, run):
        def progress(epoch, tr, va):
            line = (f"Trial {run} Epoch #{epoch + 1} "
                    f"Train Error: {tr:.6f} Validation Error: {va:.6f}")
            pf.write(line + "\n")
            pf.flush()
            faults.fire("train", "epoch", epoch + 1)
            log.info(line)
        return progress

    def _checkpoint_fn(self, spec, alg: Algorithm):
        def checkpoint(epoch, params_list):
            for i, p in enumerate(params_list):
                path = self.paths.tmp_model_path(i, epoch + 1,
                                                 alg.name.lower())
                nn_model.save_model(path, spec, p)
        return checkpoint

    def _continuous_init(self, spec, n_members: int, alg: Algorithm,
                         settings=None):
        """Continuous training: warm-start members from existing final
        models; a GROWN configuration fits the saved net into the larger
        structure (reference ``NNMaster.java:331-362,605-645``)."""
        if not self.model_config.train.isContinuous:
            return None
        import jax
        seed = settings.seed if settings else 0
        initializer = settings.weight_initializer if settings else "xavier"
        ext = alg.name.lower()
        init = []
        grown = 0
        for i in range(n_members):
            path = self.paths.model_path(i, ext)
            if not os.path.isfile(path):
                return None
            old_spec, params = nn_model.load_model(path)
            if old_spec.layer_dims() != spec.layer_dims():
                params = nn_model.fit_params_into(
                    old_spec, params, spec,
                    jax.random.fold_in(jax.random.PRNGKey(seed), i),
                    initializer)
                if params is None:
                    log.warning("continuous: model%d does not embed in the "
                                "new structure, fresh init", i)
                    return None
                grown += 1
            init.append(params)
        log.info("continuous training: warm-started %d members%s", n_members,
                 f" ({grown} grown via structure fit-in)" if grown else "")
        return init

    @staticmethod
    def _scoring_spec(spec):
        """The SPEC a model file ships with: SVM trains on a linear head
        (hinge needs raw margins) but scores through sigmoid so eval stays
        in the documented [0, 1]*1000 range — monotone, rank metrics
        unchanged."""
        if (spec.extra or {}).get("algorithm") == "SVM":
            import dataclasses
            return dataclasses.replace(
                spec, output_activation="sigmoid",
                extra={**spec.extra, "margin_sigmoid": True})
        return spec

    def _write_models(self, results, alg: Algorithm, is_gs: bool) -> None:
        ext = alg.name.lower()
        os.makedirs(self.paths.models_dir, exist_ok=True)
        # clear stale models from previous runs (fewer bags / other algs) so
        # eval's glob never mixes ensembles
        for f in os.listdir(self.paths.models_dir):
            if f.startswith("model"):
                os.remove(os.path.join(self.paths.models_dir, f))
        if is_gs:
            # grid search: pick the best trial by validation error
            # (reference re-trains the winner; our members ARE full runs)
            flat = []
            for run, spec, res, run_params in results:
                for j, trial_idx in enumerate(run):
                    tp = run_params[j] if isinstance(run_params, list) \
                        else run_params
                    flat.append((res.valid_errors[j], trial_idx, spec,
                                 res.params[j], tp))
            from ..train.grid_search import rank_and_report
            by_idx = {t[1]: t for t in flat}
            idxs = sorted(by_idx)
            order = rank_and_report(
                self.paths.tmp_dir, [by_idx[i][0] for i in idxs],
                [by_idx[i][4] for i in idxs])
            best = by_idx[idxs[order[0]]]
            log.info("grid search: best trial #%d valid error %.6f params %s",
                     best[1], best[0], best[4])
            nn_model.save_model(self.paths.model_path(0, ext),
                                self._scoring_spec(best[2]), best[3])
            return
        run, spec, res, _ = results[0]
        ova_k = (spec.extra or {}).get("ova_classes")
        for i, p in enumerate(res.params):
            sp = spec
            if ova_k:
                # member b*K+k scores class k — stamp the class identity
                import dataclasses
                sp = dataclasses.replace(
                    spec, extra={**spec.extra, "class_index": i % ova_k})
            nn_model.save_model(self.paths.model_path(i, ext),
                                self._scoring_spec(sp), p)
        log.info("saved %d model(s); valid errors %s", len(res.params),
                 np.round(res.valid_errors, 6).tolist())
