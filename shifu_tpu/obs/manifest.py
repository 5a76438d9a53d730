"""Metric-name manifest — the ONE registry of declared instrument names.

Every ``obs.counter("...")`` / ``obs.gauge("...")`` /
``obs.histogram("...")`` call site anywhere in ``shifu_tpu/`` must name a
metric declared here (or start with a declared dynamic-family prefix).
A lint-style test (``tests/test_obs_plane.py``) greps the source tree
and enforces it, because the registry's create-on-first-use convenience
has a failure mode that is otherwise silent: a typo'd name at one call
site quietly creates a NEW metric, the dashboards / report joins keep
reading the old (now frozen) one, and nothing errors anywhere.

Declaring a metric: ``MANIFEST[name] = (type, help)``.  Families whose
member names are data-dependent (per-eval-set AUC) declare
a prefix in ``PREFIXES`` instead — f-string call sites must start with
one of them.

SPAN names get the same treatment (``SPANS``; no dynamic families: an
f-string span name is a finding): the timeline/report joins key on
span-name literals, so a typo'd span name would silently vanish from
every report.  A span that only splits another is named after it
(``setup.columns`` under ``setup``, ``tower.save.fetch`` under
``tower.save``, ``nn.epoch.fetch`` under ``nn.epoch``): the parent keeps
its name and its readers, the children say where its seconds went.  Root spans named after the
step (``obs.span(self.profile_name, ...)``) are variables, not
literals, and ride outside the lint.
"""

from __future__ import annotations

from typing import Dict, Tuple

# name -> (instrument type, one-line help)
MANIFEST: Dict[str, Tuple[str, str]] = {
    # ---- ingest plane (spill cache / window prep / H2D pipeline)
    "ingest.bytes_read": ("counter", "bytes materialized into windows"),
    "ingest.windows_emitted": ("counter", "windows yielded to consumers"),
    "ingest.rows_emitted": ("counter", "valid rows in emitted windows"),
    "ingest.h2d_wait_seconds": ("counter",
                                "consumer time blocked on window prep/H2D"),
    "ingest.disk_passes": ("counter", "full/tail stream traversals"),
    "ingest.spill_hits": ("counter", "sweeps served from the mmap spill"),
    "ingest.spill_misses": ("counter", "sweeps that re-read npz shards"),
    "ingest.retries": ("counter", "transient IO errors absorbed by retry"),
    "ingest.rows_padded": ("counter",
                           "zero-weight pad rows added to fill windows"),
    "ingest.parse_stall_frac": ("gauge",
                                "fraction of the parse-pool consumer "
                                "loop spent blocked on parse futures "
                                "(~0 = parse hidden, ~1 = parse-bound)"),
    # ---- one-parse raw cache (data/rawcache)
    "rawcache.hits": ("counter",
                      "raw passes served from the columnar raw cache "
                      "(zero string-plane touch)"),
    "rawcache.misses": ("counter",
                        "raw passes that parsed the string plane with "
                        "a cache root configured"),
    "rawcache.bytes_written": ("counter",
                               "decoded-column bytes committed into "
                               "the raw cache"),
    # ---- data hygiene
    "data.quarantined_rows": ("counter", "rows quarantined as unreadable"),
    "data.quarantined_shards": ("counter", "shards quarantined as torn"),
    # ---- stats plane
    "stats.rows": ("counter", "rows swept by the stats accumulators"),
    "stats.columns": ("gauge", "columns in the stats sweep"),
    "stats.rows_per_sec": ("gauge", "stats sweep throughput"),
    "stats.resumed_chunks": ("counter", "chunks skipped via mid-sweep resume"),
    # ---- norm plane
    "norm.rows": ("counter", "rows materialized by norm"),
    "norm.shards": ("gauge", "shards written by norm"),
    "norm.rows_per_sec": ("gauge", "norm throughput"),
    "norm.resumed_shards": ("counter", "committed shards verified on resume"),
    # ---- train plane
    "train.epochs": ("counter", "epochs completed (NN/LR/WDL/SVM)"),
    "train.epoch_s": ("histogram", "per-epoch wall-clock"),
    "train.trees": ("counter", "trees built (GBT/RF/DT)"),
    "train.trees_built": ("gauge", "final forest size of the last trainer"),
    "train.valid_err": ("gauge", "last validation error"),
    # ---- tower trainer: MoE routing and masking, fetched with the epoch's loss
    "tower.moe_pairs_max_expert": ("counter", "per epoch, the most (token, choice) "
                                   "pairs one held expert of one layer took"),
    "tower.moe_pairs_mean_expert": ("counter", "per epoch, the mean pairs a held "
                                    "expert took"),
    "tower.moe_rows_computed": ("counter", "buffer rows the held experts' chunk walks ran "
                                "(chunks run x a chunk's rows, all layers); routed pairs over it = "
                                "the walk's occupancy"),
    "tower.programs_reused": ("counter", "jobs that found their three programs held by the "
                              "process (compile_cache.PROGRAMS) and built none"),
    "nn.programs_reused": ("counter", "resident NN jobs that found step, epoch_steps and "
                           "eval_errors held by the process (compile_cache.PROGRAMS) and "
                           "made none"),
    "tower.dropped_pairs": ("counter", "pairs routed to a held expert that no "
                            "grouped product covered (must stay 0)"),
    "tower.masked_positions": ("counter", "masked non-PAD positions trained on (sdar_moe)"),
    "tower.positions": ("counter", "positions of the training microbatches that carry a "
                        "target (sdar_moe: the non-PAD ones)"),
    "tower.mtp_loss_sum": ("counter", "the MTP module's cross-entropy summed over its "
                           "targets, unscaled (nemotron_h)"),
    "tower.ssm_chunks": ("counter", "chunks the Mamba-2 layers' scans ran: rows x layers "
                         "x ceil(positions / chunk_size) (nemotron_h)"),
    "tower.attn_key_blocks": ("counter", "key blocks the attention kernels' forward visits: "
                              "sequences x heads x layers' visits (afmoe, lfm2_moe, sdar_moe)"),
    "tower.attn_key_blocks_dense": ("counter", "what a full sweep of every layer would visit "
                                    "(afmoe, lfm2_moe: causal; sdar_moe: every block pair)"),
    "tower.attn_pad_positions": ("counter", "positions the attention pads a half of [x_t ; x_0] "
                                 "with to whole blocks, over the rows trained on (sdar_moe; "
                                 "not PAD tokens: tower.pad_positions)"),
    "tower.pad_positions": ("counter", "PAD positions of the packed training sequences "
                            "(afmoe, lfm2_moe)"),
    "tower.sequence_positions": ("counter", "positions of the packed training sequences, "
                                 "PAD included (afmoe, lfm2_moe)"),
    "tower.router_bias_absmax": ("counter", "how far the largest |selection bias| moved: "
                                 "summed since a zero start, the largest |b| (afmoe, lfm2_moe, "
                                 "deepseek_v3)"),
    "tower.moe_balance_loss_sum": ("counter", "the sequence-wise balance loss's sum_e f_e P_e, "
                                   "summed over the MoE layers and the sequences, unscaled "
                                   "(deepseek_v3)"),
    "train.host_syncs": ("counter", "device->host value-forcing fetches"),
    "train.tail_sweeps": ("counter", "disk-tail re-streams paid"),
    "train.tail_repairs": ("counter", "c2f speculation repairs"),
    "train.tail_repair_levels": ("counter", "levels regrown by repairs"),
    "train.tail_c2f_fallbacks": ("counter",
                                 "c2f auto-fallbacks to the exact schedule"),
    # ---- WDL sharded categorical plane (train/wdl_shard)
    "wdl.shard_devices": ("gauge", "data-axis shards each WDL table "
                                   "splits over"),
    "wdl.shard_table_bytes": ("gauge", "per-device bytes of table params "
                                       "+ optimizer moments"),
    "wdl.hash_buckets": ("gauge", "hashed-ID bucket space (0 = exact ids)"),
    "wdl.hashed_cols": ("gauge", "categorical columns on the hashed-ID "
                                 "path"),
    "wdl.serve_shard_devices": ("gauge", "devices the serve-time sharded "
                                         "table copy spans"),
    # ---- eval plane (per-set AUC gauges ride the eval. prefix)
    "eval.rows_scored": ("counter", "eval rows scored"),
    "eval.rows_per_sec": ("gauge", "eval scoring throughput"),
    # ---- varselect plane
    "varsel.host_syncs": ("counter", "varselect packed fetches"),
    "varsel.mask_batches": ("counter", "mask-batched programs dispatched"),
    "varsel.windows": ("counter", "windows swept by varselect"),
    "varsel.rows_per_sec": ("gauge", "varselect throughput"),
    "varsel.candidates": ("gauge", "candidate columns scored"),
    # ---- device / XLA accounting (registry-internal writers)
    "device.bytes_in_use": ("gauge", "HBM in use (high-water sampled)"),
    "device.peak_bytes_in_use": ("gauge", "HBM peak"),
    "device.bytes_limit": ("gauge", "HBM capacity"),
    "xla.compile_count": ("counter", "XLA compilations observed"),
    "xla.compile_time_s": ("counter", "XLA compile wall-clock"),
    # ---- cost-attribution plane (obs/costs)
    "xla.recompiles": ("counter",
                       "costed executables rebuilt for a NEW input "
                       "signature (the shape-churn sentinel)"),
    "xla.launches": ("counter", "costed executable launches"),
    # ---- serving plane (serve/)
    "serve.requests": ("counter",
                       "scoring requests accepted (one per submit; "
                       "row volume is serve.rows_scored)"),
    "serve.rows_scored": ("counter", "request rows scored"),
    "serve.batches": ("counter", "padded-bucket device launches"),
    "serve.rows_padded": ("counter",
                          "pad rows added to fill serve buckets"),
    "serve.flush_full": ("counter", "flushes triggered by a full bucket"),
    "serve.flush_deadline": ("counter",
                             "flushes triggered by the maxDelayMs "
                             "deadline"),
    "serve.request_errors": ("counter", "batches failed in-flight"),
    "serve.swaps": ("counter", "model hot-swaps promoted"),
    "serve.rollbacks": ("counter",
                        "registry re-flips to the previous generation "
                        "(probation failure or operator rollback)"),
    "serve.trace_sampled": ("counter",
                            "requests head-sampled into per-request "
                            "tracing (shifu.serve.traceSampleRate)"),
    "serve.queue_depth": ("gauge",
                          "rows currently queued (set at each flush and "
                          "sampled into SERVE heartbeats/healthz — the "
                          "queue-buildup early warning)"),
    "serve.bucket_occupancy": ("histogram",
                               "real rows / bucket size per launch "
                               "(p50/p99 land in metrics.prom; was a "
                               "last-batch-only gauge before round 12)"),
    "serve.bucket_rungs_added": ("counter",
                                 "ladder rungs added by occupancy-"
                                 "driven refinement (compiled ahead of "
                                 "use)"),
    "serve.batch_latency_ms": ("histogram",
                               "oldest-request latency per batch"),
    # ---- overload protection (serve/overload; bounded admission +
    # deadline shedding + brownout)
    "serve.shed_overload": ("counter",
                            "submits rejected at the maxQueueRows "
                            "admission cap (coded 429/overloaded)"),
    "serve.shed_expired": ("counter",
                           "queued requests shed because their deadline "
                           "passed before pad/launch (coded 504)"),
    "serve.cancelled": ("counter",
                        "client-abandoned tickets (wait timed out) shed "
                        "from the queue before launch"),
    "serve.mode": ("gauge",
                   "serving mode: 0 normal, 1 brownout (degraded under "
                   "sustained burn/queue stress)"),
    "serve.brownouts": ("counter", "brownout-mode entries (lifetime)"),
    # ---- raw-record serving (serve/transform fused into the scorer)
    "serve.raw_requests": ("counter",
                           "raw-record scoring requests accepted "
                           "(POST /score with records)"),
    "serve.raw_rows": ("counter",
                       "raw records parsed and scored through the "
                       "fused-transform executable"),
    "serve.raw_rejects": ("counter",
                          "malformed raw records rejected per-record "
                          "with a coded error (the rest of the request "
                          "still scores)"),
    # ---- serving fleet (serve/router)
    "serve.fleet_replicas_up": ("gauge",
                                "replicas in rotation after the last "
                                "health sweep"),
    "serve.fleet_requeues": ("counter",
                             "requests requeued on a peer after a "
                             "replica died mid-flight"),
    "serve.fleet_drains": ("counter",
                           "replicas pulled from rotation (SLO burn, "
                           "stale heartbeat, or death)"),
    "serve.fleet_swaps": ("counter",
                          "coordinated fleet-wide hot-swaps driven "
                          "through the router"),
    "serve.fleet_hedges": ("counter",
                           "hedged second dispatches fired after the "
                           "p99 hedge delay (first response wins)"),
    "serve.fleet_breaker_opens": ("counter",
                                  "replica circuit breakers opened on "
                                  "consecutive transport/5xx failures"),
    "serve.fleet_retry_denied": ("counter",
                                 "requeues shed because the retry "
                                 "budget was exhausted (coded 429)"),
    # ---- live SLO plane (obs/slo; mirrored into metrics.prom each beat)
    "slo.p50_ms": ("gauge", "sliding-window latency p50 (log sketch)"),
    "slo.p99_ms": ("gauge", "sliding-window latency p99 (log sketch)"),
    "slo.availability": ("gauge", "observed availability over the ring"),
    "slo.burn_rate_short": ("gauge",
                            "max error-budget burn over the short "
                            "(current-window) horizon"),
    "slo.burn_rate_long": ("gauge",
                           "max error-budget burn over the long "
                           "(whole-ring) horizon"),
    "slo.alerts_firing": ("gauge", "burn-rate alert rules currently firing"),
    # ---- elastic DCN plane (parallel/elastic, parallel/mesh)
    "dcn.connect_retries": ("counter",
                            "coordinator connect failures absorbed by "
                            "the bounded backoff ladder"),
    "dcn.steps_closed": ("counter", "elastic steps this controller "
                                    "closed (won the exclusive commit)"),
    "dcn.step_timeouts": ("counter",
                          "elastic steps closed on stepTimeoutMs with "
                          "stragglers outstanding"),
    "dcn.step_wait_seconds": ("counter",
                              "time blocked waiting for quorum/close "
                              "(the straggler-masking cost)"),
    "dcn.late_applied": ("counter",
                         "late contributions folded into a later close "
                         "within the staleness window"),
    "dcn.late_dropped": ("counter",
                         "late contributions dropped past the staleness "
                         "window (quorum mode drops all)"),
    "dcn.catchup_steps": ("counter",
                          "steps replayed from the close journal "
                          "instead of recomputed"),
    "dcn.rejoins": ("counter",
                    "controller restarts that rejoined a live job "
                    "(incarnation > 1)"),
    "dcn.membership_epoch": ("gauge",
                             "current membership epoch (bumps on "
                             "join/leave/rejoin)"),
    "dcn.live_members": ("gauge",
                         "controllers the heartbeat staleness rule "
                         "considers alive"),
    # ---- continual refresh plane (refresh/)
    "refresh.triggers": ("counter",
                         "refresh cycles started (PSI breach or "
                         "schedule)"),
    "refresh.skips": ("counter",
                      "triggers suppressed by the cooldown guard"),
    "refresh.retrains": ("counter", "warm retrains run"),
    "refresh.promotions": ("counter",
                           "candidates hot-swapped into serving after "
                           "passing the AUC gate"),
    "refresh.rejections": ("counter",
                           "candidates archived on AUC regression "
                           "(incumbent stays live)"),
    "refresh.rollbacks": ("counter",
                          "promotions rolled back in probation (SLO "
                          "burn / canary parity)"),
    "refresh.state": ("gauge",
                      "controller state: 0 idle, 1 training, "
                      "2 probation"),
    "refresh.generation": ("gauge", "serving generation under refresh"),
    "refresh.cycle": ("gauge", "refresh cycles begun (lifetime)"),
    # ---- drift monitor (obs/drift)
    "drift.rows": ("gauge", "rows folded into the live drift counts"),
    "drift.columns_tracked": ("gauge", "columns with a training snapshot"),
    "drift.columns_flagged": ("gauge", "columns with PSI over threshold"),
    "drift.psi_max": ("gauge", "max per-column PSI vs training snapshot"),
    "drift.psi_mean": ("gauge", "mean per-column PSI vs training snapshot"),
    # ---- model-quality plane (obs/scorelog, obs/outcomes, obs/quality)
    "scorelog.records": ("counter",
                         "sampled prediction records appended to the "
                         "score log"),
    "scorelog.segments": ("counter",
                          "score-log segments committed by atomic "
                          "rotation"),
    "scorelog.pruned_segments": ("counter",
                                 "committed segments pruned by the "
                                 "disk budget"),
    "quality.outcomes": ("counter",
                         "outcome records ingested (POST /outcome + "
                         "drop directory)"),
    "quality.outcomes_late": ("counter",
                              "outcomes dropped: unknown/evicted "
                              "request id, watermark miss, or length "
                              "mismatch"),
    "quality.scored_rows": ("gauge",
                            "sampled scores folded into the live "
                            "score histograms"),
    "quality.joined_rows": ("gauge",
                            "outcome-joined (score,label) rows in the "
                            "rolling windows"),
    "quality.live_auc": ("gauge",
                         "rolling live AUC of the current serving "
                         "generation"),
    "quality.ece": ("gauge",
                    "reliability-bin expected calibration error "
                    "(current generation)"),
    "quality.score_psi": ("gauge",
                          "PSI of live scores vs the posttrain "
                          "snapshot (current generation)"),
    "quality.degraded": ("gauge",
                         "1 while the quality plane flags live-AUC or "
                         "score-PSI degradation"),
}

# dynamic families: f-string names must start with one of these
PREFIXES: Tuple[str, ...] = (
    "eval.",         # eval.<set>.auc / eval.<set>.pr_auc per eval set
)

# span-name literals (obs.span("...") / obs.record_span("...") call
# sites) — the timeline tracks, report sections and tests join on these
SPANS: Dict[str, str] = {
    "setup": "step scaffolding before process() (processor base)",
    # setup's children (BasicProcessor.setup): what a job pays before its
    # step body, by name; attrs are counts the code holds
    "setup.config": "ModelConfig.load and the PathFinder",
    "setup.probe": "the step's validation of ModelConfig (config.validator.probe)",
    "setup.columns": ("load_column_configs: ColumnConfig.json parsed into its "
                      "objects, bins included (columns, bytes of the file; "
                      "plans_built: jsonbean's conversion plans built by "
                      "this load, 3 the first time a process loads "
                      "columns, 0 after)"),
    "setup.journal": "ensure_dirs and the step's StepJournal read",
    "setup.precheck": ("_check_step_preconditions: the inputs of the step "
                       "exist; `train` stats every journaled norm shard "
                       "(shards verified)"),
    "process": "step body (processor base)",
    "varselect.sensitivity": "SE/ST sensitivity scoring phase",
    "ingest.window_prep": "background window materialization (prep thread)",
    "ingest.h2d_wait": "consumer blocked on window prep / H2D",
    "serve.request": ("sampled scoring request: queue-wait / deadline-"
                      "wait / pad / launch / device decomposition"),
    "serve.batch": ("sampled padded-bucket launch; links the member "
                    "requests' trace ids (fan-in causality)"),
    "dcn.step": ("elastic quorum step: contribute -> wait for quorum/"
                 "timeout/peer close -> adopt the committed aggregate"),
    "refresh.retrain": ("warm-start retraining of a refresh candidate "
                        "(checkpoint resume over the data-window "
                        "cursor)"),
    # ---- the in-RAM NN train job's path (attrs are counts the code
    # already holds; none costs a device sync)
    "data.load": ("Shards.load_all: one plane allocated at its final "
                  "size, every shard read into its row slice (bytes of "
                  "the returned plane; shards; direct = shards whose "
                  "every member went file -> destination in one copy, "
                  "the others were decoded by np.load or quarantined; "
                  "threads of the fill).  A key its consumer asked for "
                  "on the device goes file -> staging piece -> device "
                  "inside this span, upload and placement included, and "
                  "is there when the span ends (staged_bytes that went "
                  "that way: 0 says no key did; staging_bytes allocated "
                  "for it, two pieces a thread whatever the plane's "
                  "size; pieces sent)"),
    "data.alloc": ("every shard's sizes from its zip directory and npy "
                   "headers, then one np.empty a host key; a key bound "
                   "for the device gets its zero-filled device plane "
                   "and its staging pieces here"),
    "data.read": ("the fill: shards read into their slices, or into "
                  "staging pieces that are sent on, on the thread pool "
                  "and checked against their CRC-32s, from the first "
                  "submit to the last result (bytes read)"),
    "data.put": ("a fill thread waiting for the device before it writes "
                 "a staging piece again: until the placement that read "
                 "the piece is done (a child of data.read, opened on "
                 "the fill's thread)"),
    "train.split": ("member_masks and the weight products: the "
                    "train/validation row weights of every member (rows)"),
    "nn.init": ("mesh, params and optimizer state, their device_put; the "
                "three programs found in compile_cache.PROGRAMS or made anew "
                "(members, programs_built: 3 when made, 0 when the "
                "process's last job left them)"),
    "nn.h2d": ("the trainer's uploads: rows zero-padded on the host to "
               "their final multiple (the minibatch when MiniBatchs is "
               "set, else the mesh's data extent), then one device_put "
               "each of y/weights, and of x when it comes as a host "
               "array; an x the loader built on the device (data.load's "
               "staged_bytes) is taken as it is (bytes sent here: "
               "without such an x; pad_rows appended); ends at "
               "dispatch, the copy is waited for by whoever next needs "
               "it.  Nothing of the plane comes back to the host"),
    "nn.epoch": "one epoch of the in-RAM NN trainer (epoch)",
    "nn.epoch.dispatch": ("rng split and the step / epoch_steps and "
                          "eval_errors calls (epoch 0 builds them, unless "
                          "they were held and the shapes are the last "
                          "job's)"),
    "nn.epoch.fetch": "the packed train/validation error fetch that waits",
    "nn.epoch.best_copy": "device->host copy of improved members' params",
    "nn.epoch.progress": "the progress callback (progress file line)",
    "nn.epoch.checkpoint": "tmp-model and trainer-state checkpoints",
    # the tower trainer (train/tower_trainer.py): one TRAIN job
    "tower.tokenize": "bins -> token ids and the train/validation split (rows, ids)",
    "tower.pack": ("the packing of a microbatch's rows into sequences, in numbers (rows, "
                   "sequences, positions, pad_positions)"),
    "tower.init": ("the three programs found in compile_cache.PROGRAMS or made anew, "
                   "parameters and optimizer state made on the device or restored, the id "
                   "plane put up (params, bytes, programs_built: 3 when made, 0 when the "
                   "process's last job left them)"),
    "tower.epoch": "one epoch of the tower trainer (epoch)",
    "tower.epoch.dispatch": ("the epoch's order and its step and validation "
                             "programs launched (epoch 0 builds them, unless they were held)"),
    "tower.epoch.fetch": "the fetch of the epoch's loss and counters that waits",
    "tower.epoch.checkpoint": "the trainer-state checkpoint (bytes)",
    "tower.save": ("device->host copy of the parameters and the model file (bytes of the "
                   "file); its children tell the seconds apart"),
    "tower.save.clear": "the models directory's old model files unlinked (bytes removed)",
    "tower.save.fetch": ("jax.device_get of the parameters alone: ends when the last array "
                         "is on the host (bytes fetched)"),
    "tower.save.write": ("np.savez of the host arrays into the temp file, its close "
                         "included (bytes of the file; models/towers.save_model)"),
    "tower.save.commit": "the temp file renamed into place (models/towers.save_model)",
    "xla.build": ("jax traced / lowered / built (compiled or loaded from "
                  "the compile cache) one program (stage, program, secs); "
                  "recorded when it ends"),
}


def is_declared(name: str) -> bool:
    return name in MANIFEST or any(name.startswith(p) for p in PREFIXES)


def is_declared_span(name: str) -> bool:
    return name in SPANS


def declared_type(name: str) -> str:
    """Instrument type for an exact declared name ('' for prefix-only)."""
    if name in MANIFEST:
        return MANIFEST[name][0]
    return ""
