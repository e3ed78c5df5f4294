"""Kind ``train_rank``: kind ``train`` for a query-grouped table and a
ranking objective.

What is measured is kind ``train``'s: the same ``Window`` (warm-up, open,
stamps, the traced rounds, close), the same three end-to-end metrics, the
same readers of the per-layer metrics, called with kind ``train`` as
``train_rowblocks.py`` does.  Three things differ, and ``train.py`` has no
seam for them (PERF.md section 7, row m), so ``measure`` is written out
here:

- the data comes from ``harness/data_rank.py`` with its query sizes, and
  ``lightgbm_tpu.Dataset`` is handed ``group=``;
- after each warm-up round the window reads the program's own
  ``eval_train`` NDCG at the configuration's cut-offs, where kind
  ``train`` reads ``binary_logloss``;
- the comparison is ``correct_rank.check_train`` (the plain ranking
  reference, ``ndcg_gap`` and ``bin_table_gap``), and the count function
  of the pair kernel's roofline, ``counts_rank.rank_pair_work``, is bound
  to this run's query sizes and labels and entered in
  ``counts.COUNT_FUNCTIONS`` before the readers run.

A further ranking cell needs no new kind: a configuration whose ``data``
names a generator of ``data_rank.GENERATORS``, whose ``params`` carry the
objective and ``ndcg_eval_at``, and whose ``stated`` tells the reference
sigma, the label gains, ``max_position`` and the cut-offs.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from .. import counts, counts_rank, data_rank, device, layers, result
from . import train


class RankWindow(train.Window):
    """``train.Window`` whose warm-up rounds read NDCG."""

    def __init__(self, *, cutoffs, **kw):
        super().__init__(**kw)
        self.names = [f"ndcg@{k}" for k in cutoffs]
        self.warm_ndcg = []             # a list of NDCG values a round

    def __call__(self, env):
        k = env.iteration + 1
        if k > self.warmup:
            return super().__call__(env)
        train._sync(env.model)
        got = {name: float(v) for _, name, v, _ in env.model.eval_train()}
        self.warm_ndcg.append([got[n] for n in self.names])
        if k == self.warmup:
            train._sync(env.model)
            self.compiles_at_open = len(self.compile_events())
            self.t_open_wall = time.time()
            self.t_open = time.perf_counter()
            self.stamps = [self.t_open]


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
    X, y, group = data_rank.make(cfg["data"], rows, args.seed)
    say(f"data: {rows} x {X.shape[1]} in {len(group)} queries from seed "
        f"{args.seed}")

    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback as lgb_callback
    from lightgbm_tpu.obs import compile_ledger

    params = dict(cfg["params"])
    dataset = lgb.Dataset(X, label=y, group=group, params=dict(params))
    dataset.construct()
    t_binned = time.time()
    say("binned")
    bounds = [np.asarray(m.bin_upper_bound, np.float64)
              for m in dataset._binned.mappers]

    own_trace_dir = None
    trace_dir = args.out
    if args.trace and trace_dir is None:
        own_trace_dir = trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    win = RankWindow(cutoffs=cfg["stated"]["eval_at"],
                     warmup=traffic["warmup_rounds"], seconds=args.seconds,
                     trace=args.trace, trace_dir=trace_dir, traffic=traffic,
                     compile_events=compile_ledger.events,
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
    n_check = int(traffic["check_trees"])
    first, last = win.traced or (1, 0)
    info = booster.dump_model(num_iteration=max(n_check, last))["tree_info"]
    check_trees = info[:n_check]
    traced_trees = info[first - 1:last] or None
    program_ndcg = list(win.warm_ndcg)
    del booster, dataset
    gc.collect()

    # ---- per-layer metrics (traced run) ---------------------------------
    breakdown = None
    device_block = {"platform": chip["platform"], "kind": chip["kind"],
                    "count": chip["count"], "memory_peak_bytes": peak_bytes}
    metrics = {}
    if args.trace:
        counts.COUNT_FUNCTIONS["rank_pair_work"] = counts_rank.bind(group, y)
        ctx = layers.Context(
            trace_dir=trace_dir, traced=win.traced, traced_trees=traced_trees,
            rows=rows, features=X.shape[1], peaks=chip["peaks"],
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

    # ---- correct: the first trees against the plain ranking reference ---
    from .. import correct_rank
    t_ref = time.time()
    compared, notes = correct_rank.check_train(
        X, y, group, bounds, check_trees, program_ndcg, cfg,
        cell.get("limits", {}))
    say(f"reference: {time.time() - t_ref:.1f} s; {notes}")
    ok = result.verdict(compared) and failed == 0
    return {"correct": ok, "attempted": rounds, "failed": failed,
            "metrics": metrics, "device": device_block, "compared": compared,
            "breakdown": breakdown,
            "extra": {"window_s": window_s, "rounds": rounds,
                      "reference_s": time.time() - t_ref}}


def run(cell, args, chip, t_process_start) -> int:
    # train.run looks ``measure`` up when it is called: the seam through
    # which this kind's measure runs behind kind train's last line
    train.measure = measure
    return train.run(cell, args, chip, t_process_start)
