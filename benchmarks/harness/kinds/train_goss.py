"""Kind ``train_goss``: kind ``train_sparse`` for a job that sets
``boosting=goss``.

What is measured is kind ``train``'s: the same ``Window``, the same three
end-to-end metrics, the same readers, called with kind ``train``.  The
traffic's ``warmup_rounds`` holds the ``1 / learning_rate`` rounds GOSS
does not sample and ``check_trees`` sampled ones after them, so the
window times sampled rounds only.  It differs from ``train_sparse.py``
in this:

- a program whose GOSS draw is not the stateless one (ops/goss.py, which
  the plain reference reproduces) has nothing to compare, and the run
  exits 1 before it makes its data;
- in the last ``check_trees`` warm-up rounds the callback reads the
  program's round sample (the booster's row weight and factor) as a
  status a row: left out, of the top set, drawn and amplified;
- the comparison is ``correct_goss.check_train``: every warm-up tree,
  the first ``goss.warmup`` walked and the sampled ones followed over
  the kept rows, the program's losses and statuses of those;
- the readers are told the stored entries a row as ``features`` (as a
  sparse kind) and the sampled rounds' kept rows as ``rows``
  (``counts_goss.kept_rows``), and ``goss_sample_roofline``'s count
  function is entered under ``goss_sample_work``.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from .. import counts, counts_goss, counts_sparse, data_sparse, device
from .. import layers, result
from . import train, train_sparse


class SampleWindow(train.Window):
    """``train.Window``, and the program's sample of each of the last
    ``check`` warm-up rounds."""

    def __init__(self, *, check, num_data, **kw):
        super().__init__(**kw)
        self.check = int(check)
        self.num_data = int(num_data)
        self.statuses = []

    def __call__(self, env):
        k = env.iteration + 1
        if self.warmup - self.check < k <= self.warmup:
            gbdt = env.model._booster
            w = np.asarray(gbdt._row_weight)[:self.num_data]
            f = np.asarray(gbdt._goss_factor)[:self.num_data]
            self.statuses.append((w > 0).astype(np.int8)
                                 + (f > 1.0).astype(np.int8))
        return super().__call__(env)


def measure(cell, args, chip, t_process_start):
    """One run of the cell; the result as a dict, None where no window
    was opened."""
    cfg = cell["config_file"]
    traffic = cell["traffic_file"]
    rehearse = bool(args.rehearse)
    rows = int(cell["rehearse"]["num_data"] if rehearse else cfg["num_data"])
    say = lambda m: print(f"[{time.time() - t_process_start:7.1f}s] {m}",
                          file=sys.stderr, flush=True)

    # ---- set-up ---------------------------------------------------------
    X, y = data_sparse.make(cfg["data"], rows, args.seed)
    stored = counts_sparse.stored_per_row(X)
    say(f"data: {rows} x {X.shape[1]} sparse, {X.nnz} stored entries "
        f"({stored} a row), {int(y.sum())} positive, from seed {args.seed}")

    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback as lgb_callback
    from lightgbm_tpu.obs import compile_ledger

    params = dict(cfg["params"])
    dataset = lgb.Dataset(X, label=y, params=dict(params))
    dataset.construct()
    t_binned = time.time()
    binned = dataset._binned
    say(f"binned: {binned.bins.shape[0]} columns of "
        f"{len(binned.mappers)} used features; "
        f"{train_sparse.ingest_counters()}")
    bounds = [np.asarray(binned.mappers[i].bin_upper_bound, np.float64)
              if i >= 0 else np.asarray([np.inf])
              for i in binned.real_to_inner]

    own_trace_dir = None
    trace_dir = args.out
    if args.trace and trace_dir is None:
        own_trace_dir = trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    n_check = int(traffic["check_trees"])
    win = SampleWindow(check=n_check, num_data=rows,
                       warmup=traffic["warmup_rounds"], seconds=args.seconds,
                       trace=args.trace, trace_dir=trace_dir,
                       traffic=traffic, compile_events=compile_ledger.events,
                       stop_exc=lgb_callback.EarlyStopException)
    failed = 0
    booster = lgb.train(params, dataset,
                        num_boost_round=int(traffic["max_rounds"]),
                        verbose_eval=False, callbacks=[win])
    if win.t_open is None:
        print("benchmarks: no window was opened", file=sys.stderr)
        return None
    if win.t_close is None:
        say("training ended before the window closed (model saturated?)")
        failed = 1
        train._sync(booster)
        win.t_close, win.t_close_wall = time.perf_counter(), time.time()
        win.rounds = len(win.stamps) - 1
        win.stamps.append(win.t_close)
    window_s = win.t_close - win.t_open
    rounds = win.rounds
    setup_s = win.t_open_wall - t_process_start
    say(f"window: {rounds} rounds in {window_s:.3f} s; set-up {setup_s:.1f} s "
        f"(data and binning {t_binned - t_process_start:.1f} s)")

    events = compile_ledger.events()
    in_window = [e for e in events[win.compiles_at_open:]
                 if win.t_open_wall < float(e["t"]) <= win.t_close_wall]
    setup_compiles = events[:win.compiles_at_open]
    for e in events:
        say(f"compiled {e['program']} in {e['seconds']:.1f} s"
            + (" INSIDE THE WINDOW" if e in in_window else ""))

    peak_bytes = device.memory_peak_bytes(chip["devices"])

    # what the comparison and the counts need from the model, then free it
    warm = int(traffic["warmup_rounds"])
    first, last = win.traced or (1, 0)
    info = booster.dump_model(num_iteration=max(warm, last))["tree_info"]
    check_trees = info[:warm]
    traced_trees = info[first - 1:last] or None
    program_losses = list(win.warm_losses)[warm - n_check:]
    del booster, dataset, binned
    gc.collect()

    # ---- per-layer metrics (traced run) ---------------------------------
    breakdown = None
    device_block = {"platform": chip["platform"], "kind": chip["kind"],
                    "count": chip["count"], "memory_peak_bytes": peak_bytes}
    metrics = {}
    if args.trace:
        counts.COUNT_FUNCTIONS["goss_sample_work"] = counts_goss.bind(rows)
        ctx = layers.Context(
            trace_dir=trace_dir, traced=win.traced, traced_trees=traced_trees,
            rows=(counts_goss.kept_rows(traced_trees) if traced_trees
                  else rows),
            features=stored, peaks=chip["peaks"],
            compiles_in_window=len(in_window), peak_bytes=peak_bytes,
            setup_compile_s=sum(float(e["seconds"]) for e in setup_compiles),
            chips=chip["count"])
        # the readers go by kind, and this is a training cell
        if not rehearse:
            metrics, breakdown, busy = layers.read_all(cell["name"], ctx,
                                                       "train")
            device_block.update(busy)
        else:
            layers.rehearse_all(cell["name"], ctx, say, "train")
    elif not rehearse:
        intervals_ms = [1e3 * (b - a)
                        for a, b in zip(win.stamps, win.stamps[1:])]
        metrics = {
            "train_rounds_per_s": {"value": rounds / window_s,
                                   "unit": "rounds/s"},
            "train_round_p95_ms": {"value": result.percentile(
                intervals_ms, 95), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    if own_trace_dir is not None:
        shutil.rmtree(own_trace_dir, ignore_errors=True)

    # ---- correct: the sampled trees against the plain GOSS reference ----
    from .. import correct_goss
    t_ref = time.time()
    compared, notes = correct_goss.check_train(
        X, y, bounds, check_trees, program_losses, win.statuses, cfg,
        cell.get("limits", {}))
    say(f"reference: {time.time() - t_ref:.1f} s; {notes}")
    ok = result.verdict(compared) and failed == 0
    return {"correct": ok, "attempted": rounds, "failed": failed,
            "metrics": metrics, "device": device_block, "compared": compared,
            "breakdown": breakdown,
            "extra": {"window_s": window_s, "rounds": rounds,
                      "reference_s": time.time() - t_ref}}


def run(cell, args, chip, t_process_start) -> int:
    try:
        from lightgbm_tpu.ops import goss  # noqa: F401
    except ImportError:
        print("benchmarks: this program's GOSS draw is not the stateless one "
              "(lightgbm_tpu/ops/goss.py) that the plain reference "
              "reproduces; nothing to compare", file=sys.stderr)
        return 1
    # train.run looks ``measure`` up when it is called (train_sparse.py)
    train.measure = measure
    return train.run(cell, args, chip, t_process_start)
