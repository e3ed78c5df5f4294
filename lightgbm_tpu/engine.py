"""Training and cross-validation entry points (reference engine.py).

train() (engine.py:17-203): callback-driven boosting loop with valid sets,
custom fobj/feval, continued training from init_model, per-iteration
learning rates, early stopping, evals_result capture.

cv() (engine.py:204-416): n-fold (optionally stratified) cross validation
aggregating mean/std per metric through a CVBooster.
"""

from __future__ import annotations

import collections
import copy
from typing import Dict, List, Optional

import numpy as np

from . import callback, obs
from .basic import Booster, Dataset
from .utils import log
from .utils.log import LightGBMError


def train(params, train_set, num_boost_round=100,
          valid_sets=None, valid_names=None,
          fobj=None, feval=None, init_model=None,
          feature_name="auto", categorical_feature="auto",
          early_stopping_rounds=None, evals_result=None,
          verbose_eval=True, learning_rates=None, callbacks=None,
          events_file=None):
    """Train with given parameters; returns a Booster.

    ``events_file`` (or the ``events_file`` params key / CLI
    ``--events-file``) streams one JSONL telemetry record per boosting
    iteration — phase timings, eval values, tree shape, cumulative
    collective bytes (lightgbm_tpu/obs/, docs/OBSERVABILITY.md).

    ``metrics_port`` (or ``LIGHTGBM_TPU_METRICS_PORT``) starts a
    daemon-thread ``GET /metrics`` listener for the duration of the run,
    serving the obs registry in Prometheus text exposition so standard
    monitoring can scrape a multi-hour boosting run mid-flight
    (``obs/metrics_server.py``; stopped cleanly when training exits).

    ``snapshot_dir`` + ``snapshot_freq`` params make the run crash-safe
    (docs/FAULT_TOLERANCE.md): every K iterations the full booster state
    is checkpointed atomically, and a later call with the same
    ``snapshot_dir`` auto-resumes from the newest valid snapshot,
    bit-exactly — corrupt/partial snapshot files are detected by
    checksum and fall back to the previous one."""
    params = dict(params or {})
    events_file = events_file or params.get("events_file") or None
    # the job's set-up account (obs/setup.py): the one opened at package
    # import while that is still open, so a Dataset.construct that ran
    # before this call is inside it; a later job's opens here
    obs.setup.ensure_open()
    # -- persistent XLA compile cache (utils/compile_cache.py): applied
    # BEFORE any device work so the training programs themselves are
    # covered — repeated/resumed runs load executables from disk instead
    # of paying the warmup tax again.  On by default; where
    # JAX_COMPILATION_CACHE_DIR is set it places the cache;
    # compile_cache_dir=off (or LIGHTGBM_TPU_COMPILE_CACHE=off) disables.
    from .utils import compile_cache as _compile_cache
    _compile_cache.setup(params.get("compile_cache_dir") or None)
    # -- deep observability (lightgbm_tpu/obs/, docs/OBSERVABILITY.md):
    # compile ledger / HBM watermarks / causal trace export.  All off
    # unless configured; the matching env vars win inside configure().
    from .obs import compile_ledger as _compile_ledger
    from .obs import devprof as _devprof
    from .obs import memwatch as _memwatch
    from .obs import tracing as _tracing
    _compile_ledger.configure(params.get("compile_ledger_file") or None)
    _devprof.configure(params.get("devprof"))
    _memwatch.configure(params.get("memwatch"))
    _tracing.TRACER.configure(params.get("trace_events_file") or None)
    # -- disk-full-safe sinks (utils/diskguard.py): each run's policy is
    # authoritative, and sinks a previous run's full disk disabled are
    # re-armed — this run may write to a different, healthy volume.
    from .utils import diskguard as _diskguard
    _diskguard.set_default_policy(params.get("sink_error_policy") or None)
    _diskguard.reset_disabled()
    # -- crash-safe snapshot/resume (lightgbm_tpu/snapshot.py) ----------
    snapshot_dir = str(params.get("snapshot_dir") or "") or None
    try:
        snapshot_freq = int(params.get("snapshot_freq", 0) or 0)
    except (TypeError, ValueError):
        raise ValueError(f"snapshot_freq={params['snapshot_freq']!r} "
                         "is not an integer")
    try:
        snapshot_keep = int(params.get("snapshot_keep", 3) or 0)
    except (TypeError, ValueError):
        snapshot_keep = 3
    if snapshot_freq > 0 and not snapshot_dir:
        log.warning("snapshot_freq=%d but no snapshot_dir given; "
                    "snapshots are DISABLED", snapshot_freq)
    resume_state = None
    if snapshot_dir:
        # multihost resume goes through the cross-rank consensus
        # (docs/FAULT_TOLERANCE.md §Distributed): all ranks agree on the
        # minimum common valid iteration and verify byte-identical files
        # before any round trains; single-process keeps the plain path.
        from .parallel.multihost import process_rank_world
        from .snapshot import coordinated_resume, load_latest_snapshot
        found = (coordinated_resume(snapshot_dir)
                 if process_rank_world()[1] > 1
                 else load_latest_snapshot(snapshot_dir))
        if found is not None:
            resume_path, resume_state = found
            if init_model is not None:
                log.warning("snapshot %s takes precedence over "
                            "init_model for resume", resume_path)
                init_model = None
            log.info("Resuming from snapshot %s (%d rounds done)",
                     resume_path, int(resume_state.get("rounds_done", 0)))
    if fobj is not None:
        params["objective"] = "none"
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "num_tree", "num_trees", "num_round", "num_rounds"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
            break

    # continued training setup (engine.py:94-112)
    predictor = None
    if isinstance(init_model, str):
        predictor = Booster(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor = init_model._to_predictor()
    init_iteration = 0
    if predictor is not None:
        # total prior rounds, including any the predictor itself continued
        # from (chained continued training)
        init_iteration = len(predictor._booster.models) // max(
            predictor._booster.num_class, 1)

    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    train_set._update_params(params) \
             ._set_predictor(predictor) \
             .set_feature_name(feature_name) \
             .set_categorical_feature(categorical_feature)

    booster = Booster(params=params, train_set=train_set)
    if predictor is not None:
        # bring forward the previous model's trees (GBDT::MergeFrom role)
        booster._booster.models = list(predictor._booster.models) + \
            booster._booster.models
        booster._booster.num_init_iteration = init_iteration
        booster._booster.iter_ = init_iteration

    is_valid_contain_train = False
    train_data_name = "training"
    reduced_valid_sets = []
    name_valid_sets = []
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if isinstance(valid_names, str):
            valid_names = [valid_names]
        for i, valid_data in enumerate(valid_sets):
            if valid_data is train_set:
                is_valid_contain_train = True
                if valid_names is not None:
                    train_data_name = valid_names[i]
                continue
            if not isinstance(valid_data, Dataset):
                raise TypeError("Validation data should be Dataset instance")
            valid_data._update_params(params)
            reduced_valid_sets.append(valid_data)
            name_valid_sets.append(valid_names[i] if valid_names is not None
                                   else f"valid_{i}")
    booster.set_train_data_name(train_data_name)
    for vs, name in zip(reduced_valid_sets, name_valid_sets):
        booster.add_valid(vs, name)

    # Apply the resume state AFTER valid sets are attached so their
    # saved score caches land on the right _DeviceData buffers (the
    # replays above ran against an empty model and were no-ops).
    resume_done = 0
    if resume_state is not None:
        from .snapshot import restore_booster_state
        resume_done = restore_booster_state(booster, resume_state)
        init_iteration = booster._booster.num_init_iteration
        if early_stopping_rounds is not None:
            # the callback's best-score baseline is closure state the
            # snapshot cannot reach: it re-arms from the resume point, so
            # a run that would have early-stopped may run longer
            log.warning("resuming with early_stopping_rounds=%d: the "
                        "early-stopping counter restarts at iteration %d "
                        "(its pre-crash best-score baseline is not part "
                        "of the snapshot)", early_stopping_rounds,
                        init_iteration + resume_done)
        if resume_done >= num_boost_round:
            log.warning("snapshot already holds %d rounds >= "
                        "num_boost_round=%d; nothing left to train",
                        resume_done, num_boost_round)

    # telemetry event stream (lightgbm_tpu/obs/): the recorder is owned
    # here — attached to the booster for per-iteration notes, fed eval
    # values by log_telemetry, drained+closed after the loop.
    recorder = None
    if events_file:
        from .obs import EventRecorder
        try:
            flush_every = int(params.get("events_flush_every", 1) or 1)
        except (TypeError, ValueError):
            flush_every = 1
        recorder = EventRecorder(str(events_file),
                                 flush_every=flush_every)
        booster._booster.set_event_recorder(recorder)

    # callbacks (engine.py:113-142)
    cbs = set(callbacks or [])
    if recorder is not None:
        cbs.add(callback.log_telemetry())
    if verbose_eval is True:
        cbs.add(callback.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval is not False:
        cbs.add(callback.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None:
        cbs.add(callback.early_stopping(early_stopping_rounds,
                                        verbose=bool(verbose_eval)))
    if learning_rates is not None:
        cbs.add(callback.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        cbs.add(callback.record_evaluation(evals_result))
    callbacks_before = {cb for cb in cbs
                        if getattr(cb, "before_iteration", False)}
    callbacks_after = cbs - callbacks_before
    callbacks_before = sorted(callbacks_before,
                              key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(callbacks_after,
                             key=lambda cb: getattr(cb, "order", 0))

    # resumed eval history: restore AFTER record_evaluation's factory
    # cleared the dict, so the resumed run's evals_result continues the
    # interrupted one seamlessly
    if resume_state is not None and evals_result is not None \
            and resume_state.get("evals_result"):
        evals_result.update(copy.deepcopy(resume_state["evals_result"]))

    # -- scrapeable /metrics listener (obs/metrics_server.py): started
    # when metrics_port / LIGHTGBM_TPU_METRICS_PORT asks for one, so a
    # multi-hour run is visible to standard monitoring mid-flight.
    # Started HERE, after all setup that can raise, so a bad-params call
    # can never leak the bound port/thread; the finally below always
    # stops it.
    from .obs.metrics_server import maybe_start as _maybe_start_metrics
    metrics_server = _maybe_start_metrics(params)

    # a collective-watchdog hard abort (parallel/watchdog.py) bypasses
    # this function's finally block (os._exit while the loop is wedged
    # in a collective): hand the recorder to the watchdog so the event
    # stream is drained before the process dies
    from .parallel.watchdog import active_watchdog
    _watchdog = active_watchdog()
    if _watchdog is not None and recorder is not None:
        _watchdog.register_flush(recorder.close)

    # boosting loop (engine.py:143-203)
    try:
        for i in range(init_iteration + resume_done,
                       init_iteration + num_boost_round):
            for cb in callbacks_before:
                cb(callback.CallbackEnv(model=booster, params=params,
                                        iteration=i,
                                        begin_iteration=init_iteration,
                                        end_iteration=init_iteration
                                        + num_boost_round,
                                        evaluation_result_list=None))
            finished = booster.update(fobj=fobj)

            evaluation_result_list = []
            if is_valid_contain_train:
                evaluation_result_list.extend(booster.eval_train(feval))
            if reduced_valid_sets:
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after:
                    cb(callback.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=init_iteration,
                        end_iteration=init_iteration + num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except callback.EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                break
            if snapshot_dir and snapshot_freq > 0 \
                    and (i + 1 - init_iteration) % snapshot_freq == 0:
                from .snapshot import save_snapshot
                save_snapshot(snapshot_dir, booster,
                              rounds_done=i + 1 - init_iteration,
                              evals_result=evals_result,
                              keep=snapshot_keep)
            if finished:
                # No leaf met the split requirements: the model is saturated
                # and further rounds would re-do full histogram work for
                # nothing (the CLI loop breaks the same way,
                # application.cpp:231).
                break
    finally:
        # a trace window the run ended inside must stop now, not at exit
        booster._booster.close_trace()
        if recorder is not None:
            # drain the pipelined last iteration so its tree shape lands in
            # the final record; best-effort, because if the loop is already
            # unwinding an exception the pending device arrays may be
            # poisoned and the flush must not mask the root cause (or skip
            # the close that writes the drained records out)
            try:
                booster._booster._flush_pending()
            except Exception:
                pass
            recorder.close()
            booster._booster.set_event_recorder(None)
        if metrics_server is not None:
            metrics_server.stop()
        if _watchdog is not None and recorder is not None:
            _watchdog.unregister_flush(recorder.close)
        # flush the causal span tree (one trace per boosting round) to
        # the configured Chrome trace-event file
        _tracing.TRACER.maybe_export()
    return booster


def _base_fingerprint(base_model):
    """The base model's training-data fingerprint (obs/drift.py), from a
    live Booster/engine or parsed straight out of a model-file tail.
    None when the artifact predates fingerprints — the skew check then
    quietly abstains."""
    from .obs.drift import parse_model_fingerprint
    try:
        if isinstance(base_model, str):
            with open(base_model) as fh:
                return parse_model_fingerprint(fh.read())
        inner = getattr(base_model, "_booster", base_model)
        return getattr(inner, "data_fingerprint", None)
    except Exception:
        # a garbled section raises its NAMED error on the train() load
        # path; the advisory check never preempts that diagnosis
        return None


def train_delta(base_model, fresh_data, num_trees=100, params=None,
                **kwargs):
    """Warm-start retrain for the serve→retrain loop (docs/SERVING.md
    §Promotion): boost ``num_trees`` new rounds on ``fresh_data`` on top
    of ``base_model`` (a Booster or model-file path) via the
    ``init_model`` path.  The base trees are carried over untouched —
    the returned booster's first ``base.num_trees()`` trees bit-match
    the base model — so the delta can be evaluated, merged
    (``Booster.merge``), or served as a canary candidate on its own.

    Train/serve skew check (docs/OBSERVABILITY.md §Drift): the fresh
    data's RAW rows are rebinned under the base artifact's fingerprint
    edges — the same comparison the serve collector makes (two
    fingerprints each bin their own data under their own quantile
    ladders, so shifted data re-binned by its own quantiles looks
    uniform again; data-vs-fingerprint is not fooled).  Drifted
    features become a named WARNING (plus the
    ``drift_skew_warnings_total`` counter), never a refusal: retraining
    on shifted data is the point of the delta loop, but it should say
    which columns moved."""
    base_fp = _base_fingerprint(base_model)  # before the data swap below
    raw = getattr(fresh_data, "data", None)  # before free_raw_data drops it
    raw = None if isinstance(raw, str) else raw
    booster = train(dict(params or {}), fresh_data,
                    num_boost_round=num_trees, init_model=base_model,
                    **kwargs)
    cmp = None
    threshold = float((params or {}).get("lifecycle_drift_threshold",
                                         0.25) or 0.25)
    top_k = int((params or {}).get("drift_top_k", 5) or 5)
    if base_fp is not None and raw is not None:
        from .obs.drift import compare_to_data
        try:
            cmp = compare_to_data(base_fp, raw, top_k=top_k)
        except Exception:
            cmp = None  # ragged/exotic raw payloads abstain, never fail
    if cmp is not None:
        offenders = [f for f in cmp["features"] if f["psi"] > threshold]
        if offenders:
            obs.inc("drift_skew_warnings_total")
            log.warning(
                "train_delta: fresh data drifted from the base model's "
                "training distribution (train/serve skew): %s "
                "(PSI threshold %g; rows %d -> %d)",
                ", ".join(f"{f['feature']} psi={f['psi']:g}"
                          for f in offenders),
                threshold, cmp["expected_rows"], cmp["actual_rows"])
    return booster


class CVBooster:
    """Auxiliary data struct holding all fold boosters (engine.py:204-240)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster):
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, data_splitter, nfold, params, seed,
                  fpreproc=None, stratified=False, shuffle=True):
    """Fold construction (engine.py:242-276)."""
    full_data.construct()
    num_data = full_data.num_data()
    if data_splitter is not None:
        if not hasattr(data_splitter, "split"):
            raise AttributeError("data_splitter has no method 'split'")
        folds = data_splitter.split(np.arange(num_data))
    elif stratified:
        label = np.asarray(full_data.get_label())
        classes, y = np.unique(label, return_inverse=True)
        rng = np.random.RandomState(seed)
        fold_id = np.zeros(num_data, np.int64)
        for c in range(len(classes)):
            idx = np.where(y == c)[0]
            if shuffle:
                rng.shuffle(idx)
            fold_id[idx] = np.arange(len(idx)) % nfold
        folds = [(np.where(fold_id != k)[0], np.where(fold_id == k)[0])
                 for k in range(nfold)]
    else:
        if shuffle:
            randidx = np.random.RandomState(seed).permutation(num_data)
        else:
            randidx = np.arange(num_data)
        test_id = [randidx[i::nfold] for i in range(nfold)]
        folds = [(np.setdiff1d(randidx, test_id[k], assume_unique=False),
                  test_id[k]) for k in range(nfold)]

    ret = CVBooster()
    for train_idx, test_idx in folds:
        train_subset = full_data.subset(np.sort(train_idx))
        valid_subset = full_data.subset(np.sort(test_idx))
        if fpreproc is not None:
            train_subset, valid_subset, tparam = fpreproc(
                train_subset, valid_subset, params.copy())
        else:
            tparam = params
        cvbooster = Booster(tparam, train_subset)
        cvbooster.add_valid(valid_subset, "valid")
        ret.append(cvbooster)
    return ret


def _agg_cv_result(raw_results):
    """Aggregate per-fold eval results (engine.py:278-290)."""
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = f"{one_line[0]} {one_line[1]}"
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k],
             float(np.std(v))) for k, v in cvmap.items()]


def cv(params, train_set, num_boost_round=10,
       data_splitter=None, nfold=5, stratified=False, shuffle=True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds=None, fpreproc=None,
       verbose_eval=None, show_stdv=True, seed=0, callbacks=None):
    """Cross-validation; returns {metric-name: [mean...], -stdv: [...]}."""
    if not isinstance(train_set, Dataset):
        raise TypeError("Training only accepts Dataset object")
    params = dict(params or {})
    if fobj is not None:
        params["objective"] = "none"
    for alias in ("num_boost_round", "num_iterations", "num_iteration",
                  "num_tree", "num_trees", "num_round", "num_rounds"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
            break
    if metrics is not None:
        params["metric"] = metrics
    train_set._update_params(params) \
             .set_feature_name(feature_name) \
             .set_categorical_feature(categorical_feature)

    results = collections.defaultdict(list)
    cvfolds = _make_n_folds(train_set, data_splitter, nfold, params, seed,
                            fpreproc=fpreproc, stratified=stratified,
                            shuffle=shuffle)

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None:
        cbs.add(callback.early_stopping(early_stopping_rounds,
                                        verbose=False))
    if verbose_eval is True:
        cbs.add(callback.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int):
        cbs.add(callback.print_evaluation(verbose_eval, show_stdv=show_stdv))
    callbacks_before = {cb for cb in cbs
                        if getattr(cb, "before_iteration", False)}
    callbacks_after = cbs - callbacks_before
    callbacks_before = sorted(callbacks_before,
                              key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(callbacks_after,
                             key=lambda cb: getattr(cb, "order", 0))

    for i in range(num_boost_round):
        for cb in callbacks_before:
            cb(callback.CallbackEnv(model=cvfolds, params=params,
                                    iteration=i, begin_iteration=0,
                                    end_iteration=num_boost_round,
                                    evaluation_result_list=None))
        for fold in cvfolds.boosters:
            fold.update(fobj=fobj)
        res = _agg_cv_result([fold.eval_valid(feval)
                              for fold in cvfolds.boosters])
        for _, key, mean, _, std in res:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in callbacks_after:
                cb(callback.CallbackEnv(model=cvfolds, params=params,
                                        iteration=i, begin_iteration=0,
                                        end_iteration=num_boost_round,
                                        evaluation_result_list=res))
        except callback.EarlyStopException as e:
            cvfolds.best_iteration = e.best_iteration + 1
            for k in results:
                results[k] = results[k][:cvfolds.best_iteration]
            break
    return dict(results)
