"""Parameter surface: defaults, aliases, type coercion, conflict checks.

Mirrors the reference's single string-map config pipeline used identically by
CLI, config file, and Python params dict (reference: include/LightGBM/config.h
ConfigBase::Set + ParameterAlias::KeyAliasTransform config.h:322-416, conflict
derivation src/io/config.cpp:138-176).  The TPU build keeps the same parameter
names, aliases, and defaults so reference conf files run unmodified.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Mapping, Optional

from .utils import coerce_bool as _coerce_bool

# ---------------------------------------------------------------------------
# Alias table (reference config.h:322-416).  alias -> canonical name.
# ---------------------------------------------------------------------------
PARAM_ALIASES: Dict[str, str] = {
    "config": "config_file",
    "nthread": "num_threads",
    "num_thread": "num_threads",
    "random_seed": "seed",
    "boosting": "boosting_type",
    "boost": "boosting_type",
    "application": "objective",
    "app": "objective",
    "train_data": "data",
    "train": "data",
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "valid": "valid_data",
    "test_data": "valid_data",
    "test": "valid_data",
    "is_sparse": "is_enable_sparse",
    "enable_sparse": "is_enable_sparse",
    "pre_partition": "is_pre_partition",
    "tranining_metric": "is_training_metric",
    "train_metric": "is_training_metric",
    "ndcg_at": "ndcg_eval_at",
    "eval_at": "ndcg_eval_at",
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "num_leaf": "num_leaves",
    "sub_feature": "feature_fraction",
    "colsample_bytree": "feature_fraction",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_trees": "num_iterations",
    "num_rounds": "num_iterations",
    "sub_row": "bagging_fraction",
    "subsample": "bagging_fraction",
    "subsample_freq": "bagging_freq",
    "shrinkage_rate": "learning_rate",
    "tree": "tree_learner",
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "two_round_loading": "use_two_round_loading",
    "two_round": "use_two_round_loading",
    "mlist": "machine_list_file",
    "is_save_binary": "is_save_binary_file",
    "save_binary": "is_save_binary_file",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "verbosity": "verbose",
    "header": "has_header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "query": "group_column",
    "query_column": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "categorical_feature": "categorical_column",
    "cat_column": "categorical_column",
    "cat_feature": "categorical_column",
    "save_period": "snapshot_freq",
    "predict_raw_score": "is_predict_raw_score",
    "predict_leaf_index": "is_predict_leaf_index",
    "raw_score": "is_predict_raw_score",
    "leaf_index": "is_predict_leaf_index",
    "min_split_gain": "min_gain_to_split",
    "topk": "top_k",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2",
    "num_classes": "num_class",
    "unbalanced_sets": "is_unbalance",
}

# ---------------------------------------------------------------------------
# Defaults (reference config.h:86-264).
# ---------------------------------------------------------------------------
_DEFAULTS: Dict[str, Any] = {
    # task / top-level
    "task": "train",
    "objective": "regression",
    "boosting_type": "gbdt",
    "tree_learner": "serial",
    "seed": 0,
    "num_threads": 0,
    "metric": [],
    # IO
    "max_bin": 255,
    "num_class": 1,
    "data_random_seed": 1,
    "data": "",
    "valid_data": [],
    "output_model": "LightGBM_model.txt",
    "output_result": "LightGBM_predict_result.txt",
    "input_model": "",
    "verbose": 1,
    "num_iteration_predict": -1,
    "is_pre_partition": False,
    "is_enable_sparse": True,
    "use_two_round_loading": False,
    "is_save_binary_file": False,
    "enable_load_from_binary_file": True,
    "bin_construct_sample_cnt": 200000,
    "is_predict_leaf_index": False,
    "is_predict_raw_score": False,
    "min_data_in_bin": 5,
    "max_conflict_rate": 0.0,
    "enable_bundle": True,
    # gain-informed feature screening (EMA-FS; models/screening.py,
    # docs/SPARSE.md) — off unless feature_screen_ratio > 0
    "feature_screen_ratio": 0.0,    # share of feature space masked out of
                                    # screened rounds (0 = off)
    "feature_screen_refresh": 10,   # full-feature refresh round period
    "feature_screen_warmup": 20,    # unscreened warm-up rounds seeding
                                    # the gain EWMA
    "feature_screen_decay": 0.9,    # per-round EWMA decay of realized
                                    # split gains
    "has_header": False,
    "label_column": "",
    "weight_column": "",
    "group_column": "",
    "ignore_column": "",
    "categorical_column": "",
    # objective
    "sigmoid": 1.0,
    "huber_delta": 1.0,
    "fair_c": 1.0,
    "gaussian_eta": 1.0,
    "poisson_max_delta_step": 0.7,
    "label_gain": [],
    "max_position": 20,
    "is_unbalance": False,
    "scale_pos_weight": 1.0,
    # metric
    "ndcg_eval_at": [1, 2, 3, 4, 5],
    # tree
    "min_data_in_leaf": 100,
    "min_sum_hessian_in_leaf": 10.0,
    "lambda_l1": 0.0,
    "lambda_l2": 0.0,
    "min_gain_to_split": 0.0,
    "num_leaves": 127,
    # piece-wise linear trees (models/linear.py, docs/LINEAR_TREES.md):
    # affine leaf models fitted by a batched ridge solve after growth
    "linear_tree": False,
    "linear_lambda": 0.0,            # ridge strength on the slope terms
    "linear_max_leaf_features": 5,   # K: path features per leaf (static
                                     # pad width; 0 = constant leaves)
    "feature_fraction_seed": 2,
    "feature_fraction": 1.0,
    "histogram_pool_size": -1.0,
    "max_depth": -1,
    "top_k": 20,
    # boosting
    "output_freq": 1,
    "is_training_metric": False,
    "num_iterations": 10,
    "learning_rate": 0.1,
    "bagging_fraction": 1.0,
    "bagging_seed": 3,
    "bagging_freq": 0,
    "early_stopping_round": 0,
    "drop_rate": 0.1,
    "max_drop": 50,
    "skip_drop": 0.5,
    "xgboost_dart_mode": False,
    "uniform_drop": False,
    "drop_seed": 4,
    "top_rate": 0.2,
    "other_rate": 0.1,
    # network (TPU build: devices on the mesh replace machines)
    "num_machines": 1,
    "local_listen_port": 12400,
    "time_out": 120,
    "machine_list_file": "",
    # fault tolerance (lightgbm_tpu/snapshot.py, docs/FAULT_TOLERANCE.md)
    "snapshot_freq": 0,        # checkpoint every K iterations (0 = off)
    "snapshot_dir": "",        # where snapshots live; also enables resume
    "snapshot_keep": 3,        # newest files retained (0 = keep all)
    "nan_policy": "none",      # none | fail_fast | skip_tree
    # resource exhaustion (utils/resource.py + utils/diskguard.py,
    # docs/FAULT_TOLERANCE.md §Resource exhaustion)
    "memory_policy": "fail_fast",  # fail_fast | degrade: refuse an
                                   # over-budget config, or walk the
                                   # footprint-reduction ladder first
    "sink_error_policy": "disable",  # disable | fatal: what a guarded
                                     # telemetry/state sink does on a
                                     # classified write error (ENOSPC...)
    "events_flush_every": 1,   # events JSONL flush cadence in committed
                               # records (crash loses at most this many)
    # data boundary (io/guard.py; docs/FAULT_TOLERANCE.md §Data boundary)
    "bad_data_policy": "fail_fast",  # fail_fast | quarantine malformed
                                     # input rows at file load
    "max_bad_rows": 0,         # absolute quarantine budget (0 = no cap)
    "max_bad_row_fraction": 0.1,  # relative quarantine budget over rows
                                  # seen (0 = no cap)
    "distributed_init_retries": 3,    # coordinator-connect retries
    "distributed_init_backoff": 2.0,  # first retry delay, seconds (x2 each)
    # distributed fault tolerance (parallel/watchdog.py,
    # docs/FAULT_TOLERANCE.md §Distributed)
    "distributed_heartbeat_ms": 500.0,  # out-of-band rank heartbeat
                                        # interval (0 = watchdog off)
    "collective_timeout_s": 0.0,  # per-round collective deadline
                                  # (0 = auto from the comm_seconds EWMA)
    "distributed_consistency_check": 0,  # allgather a replicated-state
                                         # digest every K iters (0 = off)
    "desync_policy": "fail_fast",  # fail_fast | resync (broadcast rank
                                   # 0's state on divergence)
    # serving (lightgbm_tpu/serve/; docs/SERVING.md)
    "serve_host": "127.0.0.1",  # bind address for task=serve
    "serve_port": 8080,         # HTTP port for task=serve
    "serve_max_batch": 8192,    # micro-batcher row cap per device batch
    "serve_max_delay_ms": 5.0,  # micro-batch coalescing deadline
    "predict_buckets": [],      # batch bucket ladder ([] = powers of two)
    "serve_walk": "auto",       # forest walk strategy: auto | fused
                                # (Pallas VMEM kernel) | gather (XLA)
    "serve_quantize_leaves": False,  # bf16 fused leaf tables behind the
                                     # QUANTIZE_LEAF_ATOL pin
    # serving fleet (serve/fleet.py: replicas, admission, canary)
    "serve_replicas": 0,        # device replicas (0 = all local devices)
    "serve_queue_depth": 128,   # pending requests per replica (0 = no cap)
    "serve_max_inflight": 0,    # fleet-wide in-flight cap (0 = no cap)
    "serve_canary_model": "",   # optional second model file (A/B routing)
    "serve_canary_weight": 0.0,  # canary traffic share in [0, 1)
    # serving fault tolerance (serve/health.py; docs/FAULT_TOLERANCE.md)
    "serve_retry_limit": 2,     # hedged retries per request (0 = none)
    "serve_error_threshold": 3,  # consecutive errors -> replica suspect
    "serve_watchdog_ms": 250.0,  # health watchdog interval (0 = off)
    "serve_stall_ms": 5000.0,   # device-batch stall age -> replica wedged
    "serve_latency_outlier": 8.0,  # EWMA multiple of fleet median -> suspect
    "serve_state_file": "",     # last-good model state JSON (crash restore)
    # guarded model lifecycle (serve/lifecycle.py; docs/FAULT_TOLERANCE.md
    # §Model lifecycle): canary observation window + guardrails
    "serve_shadow": 0.0,        # fraction of primary traffic mirrored onto
                                # the canary off the response path [0, 1]
    "lifecycle_window_s": 0.0,  # canary observation window before a
                                # promote/rollback verdict (0 = manual
                                # promotion, controller off)
    "lifecycle_max_window_s": 0.0,  # hard cap on extended windows
                                    # (0 = 4x lifecycle_window_s)
    "lifecycle_min_samples": 50,  # canary requests a guardrail needs
                                  # before it may vote
    "lifecycle_latency_ratio": 3.0,  # canary p99 / primary p99 above this
                                     # -> rollback (0 = gate off)
    "lifecycle_error_rate": 0.05,  # canary error+ejection share above
                                   # this -> rollback
    "lifecycle_cooldown_s": 60.0,  # post-rollback cooldown base, doubling
                                   # per consecutive rollback
    "shrinkage_decay": 1.0,     # leaf-output decay Booster.merge applies
                                # to the donor's trees (1.0 = plain merge)
    # serve ingress hardening (serve/server.py; docs/FAULT_TOLERANCE.md)
    "serve_max_body_bytes": 33554432,  # request body cap -> 413 (0 = none)
    "serve_nonfinite_policy": "reject",  # reject | propagate NaN/Inf
                                         # feature values in requests
    # observability (lightgbm_tpu/obs/; docs/OBSERVABILITY.md)
    "events_file": "",         # per-iteration JSONL event stream path
    "trace_dir": "",           # device trace dir (LIGHTGBM_TPU_TRACE_DIR wins)
    "trace_start_iter": 5,     # first traced iteration (skip compile/warmup)
    "trace_num_iters": 2,      # trace window length in iterations
    "metrics_port": 0,         # training /metrics listener port (0 = off;
                               # LIGHTGBM_TPU_METRICS_PORT env wins)
    "metrics_host": "127.0.0.1",  # bind address for the metrics listener
    "compile_ledger_file": "",  # append-only JSONL of every XLA compile
                                # (LIGHTGBM_TPU_COMPILE_LEDGER env wins)
    "memwatch": False,          # HBM watermark gauges at span boundaries
                                # (LIGHTGBM_TPU_MEMWATCH env wins)
    "devprof": "off",           # device-time attribution: off | full |
                                # sample:N forces+times a device sync every
                                # Nth dispatch per program
                                # (LIGHTGBM_TPU_DEVPROF env wins)
    "trace_events_file": "",    # Chrome trace-event JSON export of the
                                # causal span tree (LIGHTGBM_TPU_TRACE_EVENTS
                                # env wins; load in Perfetto)
    # warmup tax (utils/compile_cache.py; docs/OBSERVABILITY.md)
    "compile_cache_dir": "",   # persistent XLA compile cache dir ("" = the
                               # fixed in-checkout default, "off"
                               # disables; JAX_COMPILATION_CACHE_DIR,
                               # where set, places the cache instead)
    "row_buckets": True,       # pad training rows up a shared shape ladder
                               # (zero row_weight, bit-identical trees) so
                               # train_step/grow_tree programs are shared
                               # across nearby dataset sizes
    # drift observatory (obs/drift.py; docs/OBSERVABILITY.md §Drift)
    "drift": "off",             # serve-side drift collector: off | on
                                # (needs a model with a data_fingerprint
                                # section)
    "drift_window": 30.0,       # collector window seconds (PSI/KL/L-inf
                                # vs the fingerprint, computed per window
                                # on a host thread)
    "drift_top_k": 5,           # offending features labeled per window
                                # in drift_psi{feature=} / /stats
    "lifecycle_drift_threshold": 0.25,  # sustained per-feature PSI above
                                        # this votes rollback (0 = gate
                                        # off; 0.25 = classic major-shift
                                        # reading)
}

_BOOL_KEYS = {k for k, v in _DEFAULTS.items() if isinstance(v, bool)}
_INT_KEYS = {k for k, v in _DEFAULTS.items() if isinstance(v, int) and not isinstance(v, bool)}
_FLOAT_KEYS = {k for k, v in _DEFAULTS.items() if isinstance(v, float)}
_LIST_KEYS = {"metric", "valid_data", "label_gain", "ndcg_eval_at",
              "predict_buckets"}

_OBJECTIVE_ALIASES = {
    "regression": "regression",
    "regression_l2": "regression",
    "mean_squared_error": "regression",
    "mse": "regression",
    "l2": "regression",
    "regression_l1": "regression_l1",
    "mean_absolute_error": "regression_l1",
    "mae": "regression_l1",
    "l1": "regression_l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "binary": "binary",
    "multiclass": "multiclass",
    "softmax": "multiclass",
    "lambdarank": "lambdarank",
    "rank": "lambdarank",
}

_METRIC_ALIASES = {
    "l2": "l2", "mse": "l2", "mean_squared_error": "l2", "regression": "l2",
    "l1": "l1", "mae": "l1", "mean_absolute_error": "l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "binary_logloss": "binary_logloss", "binary": "binary_logloss",
    "binary_error": "binary_error",
    "auc": "auc",
    "multi_logloss": "multi_logloss", "multiclass": "multi_logloss",
    "multi_error": "multi_error",
    "ndcg": "ndcg",
    "map": "map", "mean_average_precision": "map",
}


def apply_aliases(params: Mapping[str, Any]) -> Dict[str, Any]:
    """KeyAliasTransform: canonical keys win over aliases (config.h:405-415)."""
    out: Dict[str, Any] = {}
    aliased: Dict[str, Any] = {}
    for key, value in params.items():
        key = key.strip()
        if key in PARAM_ALIASES:
            aliased[PARAM_ALIASES[key]] = value
        else:
            out[key] = value
    for key, value in aliased.items():
        out.setdefault(key, value)
    return out




def _coerce_list(value: Any, elem=str) -> List[Any]:
    if isinstance(value, (list, tuple)):
        return [elem(v) for v in value]
    s = str(value).strip()
    if not s:
        return []
    return [elem(v) for v in s.replace(",", " ").split()]


class Config:
    """Typed view over a raw params dict, after alias resolution.

    Attribute access returns the canonical typed value, e.g. ``cfg.num_leaves``.
    Unknown parameters are kept in ``raw`` (the reference silently ignores
    unknown keys too).
    """

    def __init__(self, params: Optional[Mapping[str, Any]] = None):
        params = dict(params or {})
        params = apply_aliases(params)
        self.raw: Dict[str, Any] = params
        self._values: Dict[str, Any] = copy.deepcopy(_DEFAULTS)
        for key, value in params.items():
            if key not in self._values:
                continue
            self._values[key] = self._coerce(key, value)
        self._check_param_conflict()

    def raw_params(self) -> Dict[str, Any]:
        """The user-supplied (alias-resolved) parameter dict."""
        return dict(self.raw)

    @staticmethod
    def _coerce(key: str, value: Any) -> Any:
        if key in _LIST_KEYS:
            if key == "metric":
                names = _coerce_list(value, str)
                out = []
                for name in names:
                    if name in ("", "none", "null", "na"):
                        continue
                    out.append(_METRIC_ALIASES.get(name, name))
                return out
            if key in ("label_gain",):
                return _coerce_list(value, float)
            if key in ("ndcg_eval_at", "predict_buckets"):
                return _coerce_list(value, int)
            return _coerce_list(value, str)
        if key in _BOOL_KEYS:
            return _coerce_bool(value)
        if key in _INT_KEYS:
            return int(float(value))
        if key in _FLOAT_KEYS:
            return float(value)
        if key == "objective":
            name = str(value).strip()
            return _OBJECTIVE_ALIASES.get(name, name)
        return str(value).strip() if isinstance(value, str) else value

    def _check_param_conflict(self) -> None:
        """Reference CheckParamConflict (config.cpp:138-176) semantics."""
        v = self._values
        if v["tree_learner"] not in ("serial", "feature", "data", "voting"):
            raise ValueError(f"Unknown tree learner type {v['tree_learner']}")
        if v["nan_policy"] not in ("none", "fail_fast", "skip_tree"):
            raise ValueError(
                f"Unknown nan_policy {v['nan_policy']} "
                "(expected none, fail_fast, or skip_tree)")
        if v["snapshot_freq"] < 0:
            raise ValueError("snapshot_freq must be >= 0")
        if v["memory_policy"] not in ("fail_fast", "degrade"):
            raise ValueError(
                f"Unknown memory_policy {v['memory_policy']} "
                "(expected fail_fast or degrade)")
        if v["sink_error_policy"] not in ("disable", "fatal"):
            raise ValueError(
                f"Unknown sink_error_policy {v['sink_error_policy']} "
                "(expected disable or fatal)")
        if v["events_flush_every"] < 1:
            raise ValueError("events_flush_every must be >= 1 (flush "
                             "after every K committed event records)")
        if not (0.0 <= v["max_conflict_rate"] < 1.0):
            raise ValueError(
                "max_conflict_rate must be in [0, 1): it bounds the share "
                "of conflicting rows an EFB bundle may absorb (0 = only "
                "perfectly exclusive features bundle)")
        if not (0.0 <= v["feature_screen_ratio"] < 1.0):
            raise ValueError(
                "feature_screen_ratio must be in [0, 1) (0 disables "
                "gain-informed feature screening; 1 would mask every "
                "feature)")
        if v["feature_screen_refresh"] < 1:
            raise ValueError("feature_screen_refresh must be >= 1 (every "
                             "K-th round re-scans the full feature set)")
        if v["feature_screen_warmup"] < 0:
            raise ValueError("feature_screen_warmup must be >= 0")
        if not (0.0 < v["feature_screen_decay"] <= 1.0):
            raise ValueError("feature_screen_decay must be in (0, 1]")
        if v["linear_lambda"] < 0.0:
            raise ValueError("linear_lambda must be >= 0 (ridge strength "
                             "on the per-leaf affine slope terms)")
        if v["linear_max_leaf_features"] < 0:
            raise ValueError("linear_max_leaf_features must be >= 0 "
                             "(0 degenerates linear_tree to constant "
                             "leaves)")
        if v["bad_data_policy"] not in ("fail_fast", "quarantine"):
            raise ValueError(
                f"Unknown bad_data_policy {v['bad_data_policy']} "
                "(expected fail_fast or quarantine)")
        if v["max_bad_rows"] < 0:
            raise ValueError("max_bad_rows must be >= 0 (0 = no absolute "
                             "quarantine budget)")
        if not (0.0 <= v["max_bad_row_fraction"] <= 1.0):
            raise ValueError("max_bad_row_fraction must be in [0, 1] "
                             "(0 = no fractional quarantine budget)")
        if v["serve_max_body_bytes"] < 0:
            raise ValueError("serve_max_body_bytes must be >= 0 "
                             "(0 = no request body cap)")
        if v["serve_nonfinite_policy"] not in ("reject", "propagate"):
            raise ValueError(
                f"Unknown serve_nonfinite_policy "
                f"{v['serve_nonfinite_policy']} "
                "(expected reject or propagate)")
        if v["distributed_heartbeat_ms"] < 0:
            raise ValueError("distributed_heartbeat_ms must be >= 0 "
                             "(0 disables the collective watchdog)")
        if v["collective_timeout_s"] < 0:
            raise ValueError("collective_timeout_s must be >= 0 (0 = "
                             "auto, derived from the comm_seconds EWMA)")
        if v["distributed_consistency_check"] < 0:
            raise ValueError("distributed_consistency_check must be >= 0 "
                             "(0 disables the desync detector)")
        if v["desync_policy"] not in ("fail_fast", "resync"):
            raise ValueError(
                f"Unknown desync_policy {v['desync_policy']} "
                "(expected fail_fast or resync)")
        if v["serve_max_batch"] <= 0:
            raise ValueError("serve_max_batch must be > 0")
        if not (0 <= v["metrics_port"] < 65536):
            raise ValueError("metrics_port must be in [0, 65536) "
                             "(0 disables the metrics listener)")
        if v["serve_max_delay_ms"] < 0:
            raise ValueError("serve_max_delay_ms must be >= 0")
        if v["serve_walk"] not in ("auto", "fused", "gather"):
            raise ValueError(
                f"Unknown serve_walk {v['serve_walk']} "
                "(expected auto, fused or gather)")
        if any(b <= 0 for b in v["predict_buckets"]):
            raise ValueError("predict_buckets must be positive sizes")
        if v["serve_replicas"] < 0:
            raise ValueError("serve_replicas must be >= 0 "
                             "(0 = one replica per local device)")
        if v["serve_queue_depth"] < 0:
            raise ValueError("serve_queue_depth must be >= 0 (0 = no cap)")
        if v["serve_max_inflight"] < 0:
            raise ValueError("serve_max_inflight must be >= 0 (0 = no cap)")
        if not (0.0 <= v["serve_canary_weight"] < 1.0):
            raise ValueError("serve_canary_weight must be in [0, 1) — the "
                             "canary is a minority share, not the primary")
        # serve_canary_weight > 0 with no serve_canary_model is valid:
        # it reserves an EMPTY canary slot that a later
        # ``POST /reload {"target": "canary"}`` fills (the guarded
        # promotion flow, serve/lifecycle.py) — routing only splits
        # traffic once a canary is actually live
        if v["serve_retry_limit"] < 0:
            raise ValueError("serve_retry_limit must be >= 0 "
                             "(0 disables hedged retries)")
        if v["serve_error_threshold"] < 1:
            raise ValueError("serve_error_threshold must be >= 1")
        if v["serve_watchdog_ms"] < 0:
            raise ValueError("serve_watchdog_ms must be >= 0 "
                             "(0 disables the health watchdog)")
        if v["serve_stall_ms"] < 0:
            raise ValueError("serve_stall_ms must be >= 0 "
                             "(0 disables the wedge detector)")
        if v["serve_latency_outlier"] <= 1.0:
            raise ValueError("serve_latency_outlier must be > 1 — it "
                             "multiplies the fleet-median service time")
        if not (0.0 <= v["serve_shadow"] <= 1.0):
            raise ValueError("serve_shadow must be in [0, 1] — the "
                             "fraction of primary traffic mirrored onto "
                             "the canary")
        if v["lifecycle_window_s"] < 0:
            raise ValueError("lifecycle_window_s must be >= 0 "
                             "(0 = manual promotion, controller off)")
        if v["lifecycle_max_window_s"] < 0:
            raise ValueError("lifecycle_max_window_s must be >= 0 "
                             "(0 = 4x lifecycle_window_s)")
        if v["lifecycle_max_window_s"] > 0 \
                and v["lifecycle_max_window_s"] < v["lifecycle_window_s"]:
            raise ValueError("lifecycle_max_window_s must be >= "
                             "lifecycle_window_s (or 0 for the 4x default)")
        if v["lifecycle_min_samples"] < 1:
            raise ValueError("lifecycle_min_samples must be >= 1 — a "
                             "guardrail must never vote on zero evidence")
        if v["lifecycle_latency_ratio"] != 0 \
                and v["lifecycle_latency_ratio"] <= 1.0:
            raise ValueError("lifecycle_latency_ratio must be > 1 (it "
                             "multiplies the primary's p99) or 0 to "
                             "disable the latency gate")
        if not (0.0 <= v["lifecycle_error_rate"] <= 1.0):
            raise ValueError("lifecycle_error_rate must be in [0, 1]")
        if v["lifecycle_cooldown_s"] < 0:
            raise ValueError("lifecycle_cooldown_s must be >= 0")
        if not (0.0 < v["shrinkage_decay"] <= 1.0):
            raise ValueError("shrinkage_decay must be in (0, 1] — 0 would "
                             "merge dead trees, > 1 would amplify them")
        if v["drift"] not in ("off", "on"):
            raise ValueError(f"drift must be 'off' or 'on', "
                             f"got {v['drift']!r}")
        if v["drift_window"] <= 0:
            raise ValueError("drift_window must be > 0 seconds (disable "
                             "the collector with drift=off instead)")
        if v["drift_top_k"] < 1:
            raise ValueError("drift_top_k must be >= 1")
        if v["lifecycle_drift_threshold"] < 0:
            raise ValueError("lifecycle_drift_threshold must be >= 0 "
                             "(0 disables the drift gate)")
        # devprof mode grammar is owned by obs/devprof.parse_mode — a
        # typo'd value must die here, not silently disable profiling
        from .obs.devprof import parse_mode as _devprof_parse
        _devprof_parse(v["devprof"])
        # num_machines here means mesh devices; 1 device => normalize back to
        # serial like the reference (config.cpp:161-172).
        if v["num_machines"] <= 1:
            v["is_parallel"] = False
            v["tree_learner"] = "serial"
        else:
            v["is_parallel"] = v["tree_learner"] != "serial"
            if not v["is_parallel"]:
                v["num_machines"] = 1
        v["is_parallel_find_bin"] = v["is_parallel"] and v["tree_learner"] in ("data", "voting")
        obj = v["objective"]
        if obj == "multiclass":
            # Reference: "greater than 2 for multiclass training"
            # (config.cpp:143-146).
            if v["num_class"] <= 2:
                raise ValueError(
                    "Number of classes should be specified and greater than 2 "
                    "for multiclass training")
        elif obj == "none":
            pass  # custom objective (python fobj): any num_class allowed
        else:
            if v["num_class"] != 1 and v["task"] == "train":
                raise ValueError("Number of classes must be 1 for non-multiclass training")
        # Objective/metric compatibility (config.cpp:152-160).
        if obj != "none":
            for metric in v["metric"]:
                metric_multiclass = metric in ("multi_logloss", "multi_error")
                if (obj == "multiclass") != metric_multiclass:
                    raise ValueError("Objective and metrics don't match")
        if v["boosting_type"] == "goss" and (
            v["bagging_fraction"] < 1.0 and v["bagging_freq"] > 0
        ):
            raise ValueError("cannot use bagging in GOSS")
        if not v["metric"]:
            v["metric"] = default_metric_for_objective(obj)
        if v["num_leaves"] <= 1:
            raise ValueError("num_leaves must be > 1")
        if v["max_depth"] > 0:
            v["num_leaves"] = min(v["num_leaves"], 2 ** v["max_depth"])

    def __getattr__(self, name: str) -> Any:
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        raise AttributeError(name)

    def __getitem__(self, name: str) -> Any:
        return self._values[name]

    def get(self, name: str, default: Any = None) -> Any:
        return self._values.get(name, default)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._values)

    def updated(self, **kwargs: Any) -> "Config":
        merged = dict(self.raw)
        merged.update(kwargs)
        return Config(merged)


def default_metric_for_objective(objective: str) -> List[str]:
    """GetMetricType default: metric matching the objective (config.cpp)."""
    table = {
        "regression": ["l2"],
        "regression_l1": ["l1"],
        "huber": ["huber"],
        "fair": ["fair"],
        "poisson": ["poisson"],
        "binary": ["binary_logloss"],
        "multiclass": ["multi_logloss"],
        "lambdarank": ["ndcg"],
    }
    return list(table.get(objective, []))


def parse_config_file(path: str) -> Dict[str, str]:
    """Parse a reference-style ``key = value`` conf file with # comments
    (reference application.cpp:46-104)."""
    params: Dict[str, str] = {}
    with open(path, "r") as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            key, value = line.split("=", 1)
            params[key.strip()] = value.strip()
    return params


def parse_cli_args(argv: List[str]) -> Dict[str, str]:
    """Parse ``k=v`` CLI tokens; a config file (if given) is loaded first and
    command-line keys override it (reference application.cpp:46-76)."""
    params: Dict[str, str] = {}
    for token in argv:
        if "=" not in token:
            if token.startswith("--"):
                # the two-token GNU form (--events-file out.jsonl) is NOT
                # supported — only --key=value; dropping it silently would
                # disable the feature with no diagnostic
                from .utils import log
                log.warning("ignoring CLI flag %r: flags must use the "
                            "--key=value form", token)
            continue
        key, value = token.split("=", 1)
        key = key.strip()
        if key.startswith("--"):
            # GNU-style flags (--events-file=out.jsonl) normalize onto the
            # reference key=value namespace (events_file=out.jsonl)
            key = key[2:].replace("-", "_")
        params[key] = value.strip()
    params = apply_aliases(params)
    config_path = params.pop("config_file", None)
    if config_path:
        file_params = apply_aliases(parse_config_file(config_path))
        file_params.update(params)
        params = file_params
    return params
