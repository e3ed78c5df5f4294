"""One-line descriptions for docs/gen_parameters.py."""

DESC = {
    "task": "train or predict",
    "objective": "regression | regression_l1 | huber | fair | poisson | "
                 "binary | multiclass | lambdarank | none (custom fobj)",
    "boosting_type": "gbdt | dart | goss",
    "data": "training data file path",
    "valid_data": "validation data file path(s), comma separated",
    "num_iterations": "number of boosting rounds",
    "learning_rate": "shrinkage rate",
    "shrinkage_decay": "default decay in (0, 1] applied to the merged "
                       "model's leaf outputs in Booster.merge (1 = "
                       "verbatim; the train->serve->retrain loop's "
                       "delta-forest damping)",
    "num_leaves": "max leaves per tree (leaf-wise growth)",
    "tree_learner": "serial | feature | data | voting — distributed learner "
                    "over the device mesh (`num_machines` devices of one "
                    "process, or every device of a multi-process runtime). "
                    "`data`: each device holds one equal row block, placed "
                    "there from the host, and grows it with the "
                    "leaf-ordered grower (the serial learner's program); "
                    "one all-reduce of int32 histogram sums a split (in "
                    "16-bit halves, `[F, 18, B]`, exact at any row count) "
                    "and the root's sums cross devices, so the tree is the "
                    "serial learner's (uint8 bins without EFB; wider bins "
                    "or bundled columns pass over all local rows a split "
                    "on `ops/grow.py` and all-reduce float histograms). "
                    "`voting`: rows sharded as for `data`, top-k feature "
                    "election. `feature`: rows replicated, split search "
                    "sharded by feature",
    "compile_cache_dir": "persistent XLA compilation cache directory so "
                         "repeated/resumed runs skip the warmup compile "
                         "tax ('' = one fixed directory inside the "
                         "checkout, 'off' disables; where "
                         "JAX_COMPILATION_CACHE_DIR is set it places the "
                         "cache and a directory given here is ignored; "
                         "docs/OBSERVABILITY.md §Warmup & compile caching)",
    "row_buckets": "pad training rows up a shared shape ladder "
                   "(utils/compile_cache.py bucket_rows; zero row_weight "
                   "pad rows, exact histogram sums) so "
                   "train_step/grow_tree programs are shared across "
                   "nearby dataset sizes instead of compiling per N",
    "serve_host": "task=serve: HTTP bind address (docs/SERVING.md)",
    "serve_port": "task=serve: HTTP port",
    "serve_max_batch": "task=serve: row cap per coalesced device batch "
                       "(micro-batcher, serve/batcher.py)",
    "serve_max_delay_ms": "task=serve: micro-batch coalescing deadline "
                          "measured from the oldest queued request",
    "predict_buckets": "batch bucket ladder for the compiled-forest "
                       "predict paths (comma-separated sizes; empty = "
                       "powers of two 16..65536; docs/SERVING.md)",
    "serve_replicas": "task=serve: device replicas in the fleet — one "
                      "CompiledForest + micro-batcher per local device "
                      "(0 = all of jax.local_devices(); serve/fleet.py, "
                      "docs/SERVING.md §Fleet)",
    "serve_queue_depth": "task=serve: pending-request cap per replica "
                         "queue; beyond it requests shed with 429 + "
                         "Retry-After (0 = unbounded)",
    "serve_max_inflight": "task=serve: fleet-wide cap on admitted "
                          "requests in flight; beyond it requests shed "
                          "with 429 + Retry-After (0 = unbounded)",
    "serve_canary_model": "task=serve: optional second model file served "
                          "at serve_canary_weight traffic share (A/B "
                          "routing; metrics labeled model=canary)",
    "serve_canary_weight": "task=serve: canary traffic share in [0, 1) — "
                           "deterministic rotation, exact split",
    "serve_retry_limit": "task=serve: hedged retries per request onto a "
                         "different replica after a replica-attributable "
                         "failure (0 = none; serve/health.py, "
                         "docs/FAULT_TOLERANCE.md §Serving)",
    "serve_error_threshold": "task=serve: consecutive request errors "
                             "before a replica is marked suspect (the "
                             "watchdog then ejects it)",
    "serve_watchdog_ms": "task=serve: replica health watchdog interval — "
                         "ejection, synthetic probes, re-admission "
                         "(0 disables the whole health machine)",
    "serve_stall_ms": "task=serve: how long a replica's worker may sit "
                      "inside one device batch before it counts as "
                      "wedged (stall detector; 0 = off)",
    "serve_latency_outlier": "task=serve: EWMA service-time multiple of "
                             "the fleet median beyond which a replica is "
                             "a straggler (suspect after 2 ticks)",
    "serve_state_file": "task=serve: JSON file recording the last-good "
                        "model per slot after each successful reload; a "
                        "restarted server boots it instead of "
                        "input_model (crash restore)",
    "serve_shadow": "task=serve: fraction of primary traffic mirrored "
                    "onto the canary OFF the response path (bounded "
                    "queue, dropped under load — never sheds or slows "
                    "real requests; serve/lifecycle.py, "
                    "docs/FAULT_TOLERANCE.md §Model lifecycle)",
    "lifecycle_window_s": "task=serve: guarded-promotion observation "
                          "window after a canary reload — the "
                          "PromotionController ends it in promote / "
                          "rollback / extend (0 disables the guarded "
                          "lifecycle)",
    "lifecycle_max_window_s": "task=serve: hard cap on the extended "
                              "observation window; a candidate still "
                              "unproven at the cap is rolled back, "
                              "never promoted by timeout (0 = 4x "
                              "lifecycle_window_s)",
    "lifecycle_min_samples": "task=serve: canary requests each guardrail "
                             "gate needs in the window before it may "
                             "vote (promote or rollback)",
    "lifecycle_latency_ratio": "task=serve: rollback when windowed "
                               "canary p99 latency exceeds this multiple "
                               "of the primary's (0 disables the "
                               "latency gate)",
    "lifecycle_error_rate": "task=serve: rollback when the canary's "
                            "windowed (errors + ejections) / requests "
                            "exceeds this rate",
    "lifecycle_cooldown_s": "task=serve: sticky cooldown after a "
                            "rollback — a re-reloaded candidate inside "
                            "it is rolled back immediately; doubles per "
                            "consecutive rollback (0 = none)",
    "drift": "task=serve: off | on — streaming drift collector over the "
             "served rows vs the model's training-data fingerprint "
             "(docs/OBSERVABILITY.md §Drift; off is one attribute read "
             "on the predict path)",
    "drift_window": "task=serve: collector window seconds — each window "
                    "computes per-feature PSI/KL/L-inf and score PSI on "
                    "a host thread; shorter windows detect faster but "
                    "sample fewer rows",
    "drift_top_k": "task=serve: offending features labeled per window "
                   "in drift_psi{feature=} gauges and named in drift "
                   "verdicts (the full set is always in /stats)",
    "lifecycle_drift_threshold": "task=serve: per-feature PSI above this "
                                 "for consecutive canary windows votes "
                                 "rollback with reason 'drift'; also the "
                                 "train_delta skew-warning bar "
                                 "(0 disables the gate; 0.25 = classic "
                                 "major-shift reading)",
    "serve_walk": "auto | fused | gather — forest-walk serving strategy "
                  "(docs/SERVING.md §Serving strategies): 'fused' runs "
                  "the single-pass Pallas walk kernel with the forest "
                  "pinned in VMEM, 'gather' keeps the classic per-depth "
                  "gather programs byte-identical, 'auto' picks fused "
                  "when the forest's VMEM footprint fits the "
                  "LIGHTGBM_TPU_WALK_VMEM_BYTES budget (gather "
                  "otherwise, and always off-TPU)",
    "serve_quantize_leaves": "task=serve: with serve_walk=fused, "
                             "accumulate leaf values in bfloat16 when "
                             "the per-class worst-case rounding bound "
                             "stays within QUANTIZE_LEAF_ATOL — "
                             "otherwise falls back to float32 and "
                             "increments forest_quantize_fallback "
                             "(docs/SERVING.md §Bin quantization)",
    "serve_max_body_bytes": "task=serve: request body size cap — larger "
                            "payloads are shed with 413 before any "
                            "parsing or device time (0 = no cap)",
    "serve_nonfinite_policy": "reject | propagate — NaN/Inf feature "
                              "values in /predict payloads either 400 "
                              "naming the offending row, or pass "
                              "through to the forest",
    "events_file": "per-iteration JSONL telemetry stream path "
                   "(docs/OBSERVABILITY.md; --events-file on the CLI)",
    "trace_dir": "device trace output dir; LIGHTGBM_TPU_TRACE_DIR env "
                 "overrides (docs/OBSERVABILITY.md)",
    "trace_start_iter": "first traced iteration (default 5, skips "
                        "compile/warmup)",
    "trace_num_iters": "trace window length in iterations (default 2)",
    "metrics_port": "port of the training /metrics listener serving the "
                    "obs registry in Prometheus text exposition 0.0.4 "
                    "(0 = off; LIGHTGBM_TPU_METRICS_PORT env wins; "
                    "docs/OBSERVABILITY.md)",
    "metrics_host": "bind address of the training /metrics listener "
                    "(default 127.0.0.1)",
    "compile_ledger_file": "append-only JSONL of every XLA compilation "
                           "(program, abstract shapes, seconds); "
                           "LIGHTGBM_TPU_COMPILE_LEDGER env wins "
                           "(docs/OBSERVABILITY.md)",
    "memwatch": "sample HBM watermark gauges (live/peak device bytes, "
                "per phase) at span boundaries; off by default, "
                "LIGHTGBM_TPU_MEMWATCH env wins",
    "devprof": "device-time attribution: off | full | sample:N forces a "
               "sync on every Nth dispatch per XLA program and records "
               "per-program device seconds, roofline gauges, and the "
               "per-round host/device split; off by default (zero "
               "overhead), LIGHTGBM_TPU_DEVPROF env wins "
               "(docs/OBSERVABILITY.md)",
    "trace_events_file": "Chrome trace-event JSON export of the causal "
                         "span tree (one trace per serve request / "
                         "boosting round; load in Perfetto); "
                         "LIGHTGBM_TPU_TRACE_EVENTS env wins",
    "use_two_round_loading": "stream the data file in two rounds instead of "
                             "materializing the full float matrix "
                             "(io/streaming.py)",
    "num_machines": "mesh device count for distributed learners",
    "max_bin": "max feature histogram bins",
    "min_data_in_leaf": "minimum rows per leaf",
    "min_sum_hessian_in_leaf": "minimum hessian sum per leaf",
    "feature_fraction": "per-tree feature subsample ratio",
    "bagging_fraction": "row subsample ratio",
    "bagging_freq": "re-bag every k iterations (0 = off)",
    "lambda_l1": "L1 regularization",
    "lambda_l2": "L2 regularization",
    "min_gain_to_split": "minimum gain to accept a split",
    "linear_tree": "fit an affine model in each leaf over its split-path "
                   "features (batched on-device ridge solve after growth; "
                   "needs raw feature values — docs/LINEAR_TREES.md)",
    "linear_lambda": "ridge strength on the affine leaves' slope terms "
                     "(linear_tree; lambda_l2 regularizes the intercept)",
    "linear_max_leaf_features": "K: path features per affine leaf, a "
                                "static pad width so every leaf fit "
                                "shares one compiled program (0 "
                                "degenerates linear_tree to constant "
                                "leaves, bit-identical to linear_tree="
                                "false; docs/LINEAR_TREES.md)",
    "max_depth": "depth limit (-1 = none)",
    "early_stopping_round": "stop when no metric improves in this many "
                            "rounds",
    "metric": "evaluation metric list",
    "num_class": "number of classes (multiclass)",
    "is_unbalance": "reweight unbalanced binary labels",
    "scale_pos_weight": "positive class weight (binary)",
    "sigmoid": "sigmoid sharpness (binary/lambdarank)",
    "huber_delta": "delta for huber loss",
    "fair_c": "c for fair loss",
    "gaussian_eta": "hessian smoothing width for L1/huber",
    "poisson_max_delta_step": "poisson optimization safeguard",
    "max_position": "NDCG truncation for lambdarank",
    "label_gain": "per-label gains for lambdarank",
    "ndcg_eval_at": "NDCG/MAP evaluation positions",
    "drop_rate": "dart: tree drop probability",
    "skip_drop": "dart: probability of skipping dropout",
    "max_drop": "dart: max dropped trees per iteration",
    "uniform_drop": "dart: uniform dropping",
    "xgboost_dart_mode": "dart: xgboost normalization mode",
    "top_rate": ("goss: large-gradient keep ratio: exactly "
                 "int(top_rate x N) rows kept by abs(g x h) (summed over "
                 "classes), equal scores by the lower row index; no round "
                 "samples before int(1 / learning_rate). The draw is a stage "
                 "of the fused round: one train_step for every round, the "
                 "exact top set by the goss_sample kernel (no sort), the "
                 "kept rows moved to the front by the partition kernel and "
                 "the others routed through the tree (ops/goss.py, "
                 "ops/ordered_grow.py); the per-stage path is left for a "
                 "custom fobj, LGBT_NO_FUSED_STEP and a multi-process "
                 "learner"),
    "other_rate": ("goss: small-gradient sample ratio: every other real "
                   "row kept with probability other_rate / (1 - top_rate), "
                   "its gradient and hessian amplified by (1 - top_rate) / "
                   "other_rate. The draw is stateless: row r of iteration i "
                   "under bagging_seed s is kept where fmix32(r * "
                   "0x9E3779B9 + fmix32(s ^ fmix32(i + 0x9E3779B9))) >> 8 < "
                   "round(other_rate / (1 - top_rate) * 2^24), in 32-bit "
                   "unsigned arithmetic (fmix32: MurmurHash3's finaliser), "
                   "so the same rows come out under any row padding or "
                   "sharding and a resumed run needs no generator state. "
                   "Earlier versions drew from a jax.random key split every "
                   "sampled round: the rows differ from theirs, and their "
                   "snapshots' goss key is read and ignored"),
    "top_k": "voting-parallel: candidates per shard",
    "output_model": "model save path (train)",
    "input_model": "model load path (predict / continued training)",
    "output_result": "prediction output path",
    "is_training_metric": "also print metrics on training data",
    "output_freq": "metric print frequency",
    "bin_construct_sample_cnt": "rows sampled for bin boundary construction",
    "min_data_in_bin": "minimum rows per histogram bin",
    "data_random_seed": "binning/partition seed",
    "bagging_seed": "bagging seed; also keys GOSS's draw (other_rate)",
    "feature_fraction_seed": "feature subsample seed",
    "drop_seed": "dart dropout seed",
    "has_header": "data files have a header line",
    "label_column": "label column index",
    "categorical_column": "categorical feature indices",
    "ignore_column": "feature indices to drop",
    "is_predict_raw_score": "predict: output raw scores",
    "is_predict_leaf_index": "predict: output leaf indices",
    "verbose": "log level (alias verbosity)",
    "seed": "master seed; derived seeds cover bagging/feature/dart draws "
            "unless set explicitly",
    "num_threads": "host thread hint (accepted for conf compatibility; "
                   "device parallelism comes from the mesh)",
    "num_iteration_predict": "predict with only the first K iterations "
                             "(-1 = all)",
    "is_pre_partition": "distributed: data files are already partitioned "
                        "per machine (accepted for conf compatibility)",
    "is_enable_sparse": "enable sparse-aware optimizations: false also "
                        "disables EFB bundling candidate selection (the "
                        "TPU bin matrix itself stays dense either way)",
    "is_save_binary_file": "save the parsed dataset as a binary sidecar "
                           "for faster reloads",
    "enable_load_from_binary_file": "load the binary sidecar when present "
                                    "instead of re-parsing text",
    "max_conflict_rate": "EFB: max share of conflicting rows (both "
                         "features non-default) a bundle may absorb, in "
                         "[0, 1); 0 bundles only perfectly exclusive "
                         "features (docs/SPARSE.md)",
    "enable_bundle": "bundle mutually-exclusive sparse features into "
                     "shared columns (EFB, io/bundling.py): the device "
                     "bin matrix and histogram pass shrink from F to "
                     "F_bundled while trees/models stay in original "
                     "feature space (docs/SPARSE.md)",
    "feature_screen_ratio": "EMA-FS gain screening: share of the feature "
                            "space masked out of screened rounds by the "
                            "split-gain EWMA (0 = off; screened rounds "
                            "also compact the histogram pass to the "
                            "active columns; docs/SPARSE.md)",
    "feature_screen_refresh": "screening: every K-th post-warmup round "
                              "scans the FULL feature set so dormant "
                              "features can re-enter; the active set is "
                              "re-drawn once per period",
    "feature_screen_warmup": "screening: unscreened warm-up rounds that "
                             "seed the per-feature gain EWMA before any "
                             "mask applies",
    "feature_screen_decay": "screening: per-round EWMA decay of realized "
                            "split gains (closer to 1 = longer memory)",
    "weight_column": "per-row weight column index/name in the data file",
    "group_column": "query/group column index/name (lambdarank)",
    "histogram_pool_size": "reference histogram cache budget in MB "
                           "(-1 = unbounded; accepted for conf "
                           "compatibility — the TPU learner keeps leaf "
                           "histograms on device)",
    "local_listen_port": "distributed: first TCP port from the reference "
                         "machine-list protocol; the coordinator binds "
                         "entry 0's port, the heartbeat mesh datagrams "
                         "each rank's own (parallel/multihost.py)",
    "time_out": "distributed: socket/connect timeout in minutes from the "
                "reference conf surface (coordinator connects use "
                "distributed_init_retries/backoff)",
    "machine_list_file": "distributed: one 'host port' line per rank — "
                         "numbers the processes, locates the "
                         "coordinator, and seeds the watchdog heartbeat "
                         "mesh (docs/FAULT_TOLERANCE.md §Distributed)",
    # fault tolerance (docs/FAULT_TOLERANCE.md)
    "snapshot_dir": "crash-safe snapshot directory; also enables "
                    "auto-resume (multihost: rank 0 writes, resume runs "
                    "the cross-rank consensus)",
    "snapshot_freq": "checkpoint every K iterations (0 = off; alias "
                     "save_period)",
    "snapshot_keep": "newest snapshot files retained (0 = keep all)",
    "nan_policy": "none | fail_fast | skip_tree — non-finite "
                  "gradient/score containment",
    "memory_policy": "fail_fast | degrade — HBM admission control: an "
                     "over-budget config either refuses up front with "
                     "the per-component estimate table, or walks the "
                     "footprint-reduction ladder (score donation → drop "
                     "the leaf-histogram cache → cap the row-bucket "
                     "pad) before refusing "
                     "(docs/FAULT_TOLERANCE.md §Resource exhaustion)",
    "sink_error_policy": "disable | fatal — what a guarded telemetry/"
                         "state sink does on a classified write error "
                         "(ENOSPC/EROFS/EDQUOT/EMFILE): disable itself "
                         "with one warning + sink_write_errors_total, "
                         "or raise a named SinkWriteError "
                         "(docs/FAULT_TOLERANCE.md §Resource exhaustion)",
    "events_flush_every": "events JSONL flush cadence in committed "
                          "records — a crash loses at most this many "
                          "trailing records (default 1: every record "
                          "is on disk when note() returns)",
    "bad_data_policy": "fail_fast | quarantine — malformed input rows at "
                       "file load either raise a LightGBMError naming "
                       "file:line + token, or are skipped into "
                       "<data>.quarantine under the error budget "
                       "(docs/FAULT_TOLERANCE.md §Data boundary)",
    "max_bad_rows": "absolute quarantine budget: abort the load after "
                    "this many bad rows (0 = no absolute cap)",
    "max_bad_row_fraction": "relative quarantine budget: abort when bad "
                            "rows exceed this fraction of rows seen "
                            "(0 = no fractional cap)",
    "distributed_init_retries": "coordinator-connect retries with "
                                "exponential backoff",
    "distributed_init_backoff": "first coordinator-connect retry delay, "
                                "seconds (doubles each retry)",
    "distributed_heartbeat_ms": "out-of-band UDP rank-heartbeat interval "
                                "for the collective watchdog (0 = off; "
                                "docs/FAULT_TOLERANCE.md §Distributed)",
    "collective_timeout_s": "per-round collective deadline / peer "
                            "staleness bound; 0 = auto, derived from "
                            "the comm_seconds EWMA with a 60 s floor",
    "distributed_consistency_check": "allgather a replicated-state digest "
                                     "every K iterations to catch rank "
                                     "desync (0 = off; zero overhead "
                                     "single-process)",
    "desync_policy": "fail_fast | resync — stop the pod with a named "
                     "diagnostic, or broadcast rank 0's state to the "
                     "diverged ranks and continue",
}
