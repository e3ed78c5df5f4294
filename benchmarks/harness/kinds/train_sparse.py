"""Kind ``train_sparse``: kind ``train`` for a table handed over as a
``scipy.sparse`` matrix.

What is measured is kind ``train``'s: the same ``Window`` (warm-up, open,
stamps, the traced rounds, close), the same three end-to-end metrics, the
same readers of the per-layer metrics, called with kind ``train`` as
``train_rank.py`` calls them.  ``train.py`` has no seam for another data
set or another comparison (PERF.md section 7, row m), so ``measure`` is
written out here.  It differs in this:

- the data comes from ``harness/data_sparse.py`` as a CSC matrix that
  refuses to be made dense (``RefusesDense``), and that matrix is what
  ``lightgbm_tpu.Dataset`` is handed: a program that densifies fails at
  once with ``MemoryError`` and the run exits non-zero in seconds;
- the bound tables go to the comparison a REAL column (the program drops a
  column whose sample holds one value; ``dump_model`` names real columns);
- the readers are told the STORED entries a row as ``features``
  (``counts_sparse.py`` says why: 30, not 4,228 and not the program's
  bundled columns), through a ``Context`` of this kind's own whose
  ``counters`` add the two per-layer metrics of the sparse ingest:
  ``bin_sparse_s`` (the registry's ``phase_seconds_bin_*`` sums) and
  ``efb_columns`` (the gauge);
- the comparison is ``correct_sparse.check_train`` (the plain reference
  from stored entries, ``bin_table_gap`` for ``bin_cdf_gap``).

A further sparse cell needs no new kind: a configuration whose ``data``
names a generator of ``data_sparse.GENERATORS``.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from .. import counts_sparse, data_sparse, device, layers, result
from . import train


def ingest_counters() -> dict:
    """What the program's registry holds of the sparse ingest; a key is
    left out where the program has no such span or gauge."""
    out = {}
    try:
        from lightgbm_tpu import obs
        snap = obs.snapshot()
    except Exception:                   # a program without the registry
        return out
    spans = [h["sum"] for name, h in snap.get("histograms", {}).items()
             if name.startswith("phase_seconds_bin_")]
    if spans:
        out["bin_sparse_s"] = float(sum(spans))
    columns = snap.get("gauges", {}).get("efb_columns")
    if columns is not None:
        out["efb_columns"] = float(columns)
    return out


@dataclasses.dataclass
class SparseContext(layers.Context):
    ingest: dict = dataclasses.field(default_factory=dict)

    def counters(self) -> dict:
        return {**super().counters(), **self.ingest}


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
    ingest = ingest_counters()
    say(f"binned: {binned.bins.shape[0]} columns of "
        f"{len(binned.mappers)} used features; {ingest}")
    bounds = [np.asarray(binned.mappers[i].bin_upper_bound, np.float64)
              if i >= 0 else np.asarray([np.inf])
              for i in binned.real_to_inner]

    own_trace_dir = None
    trace_dir = args.out
    if args.trace and trace_dir is None:
        own_trace_dir = trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    win = train.Window(warmup=traffic["warmup_rounds"], seconds=args.seconds,
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
    program_losses = list(win.warm_losses)
    del booster, dataset, binned
    gc.collect()

    # ---- per-layer metrics (traced run) ---------------------------------
    breakdown = None
    device_block = {"platform": chip["platform"], "kind": chip["kind"],
                    "count": chip["count"], "memory_peak_bytes": peak_bytes}
    metrics = {}
    if args.trace:
        ctx = SparseContext(
            trace_dir=trace_dir, traced=win.traced, traced_trees=traced_trees,
            rows=rows, features=stored, peaks=chip["peaks"],
            compiles_in_window=len(in_window), peak_bytes=peak_bytes,
            setup_compile_s=sum(float(e["seconds"]) for e in setup_compiles),
            chips=chip["count"], ingest=ingest)
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

    # ---- correct: the first trees against the plain sparse reference ----
    from .. import correct_sparse
    t_ref = time.time()
    compared, notes = correct_sparse.check_train(
        X, y, bounds, check_trees, program_losses, cfg,
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
