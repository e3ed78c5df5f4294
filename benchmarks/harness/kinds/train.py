"""Kind ``train``: one training job, closed loop.

The entry driven is ``lightgbm_tpu.train`` with the configuration's
parameters and one callback, as users call it.  Set-up (inside
``setup_s``): data from the seed, ``Dataset.construct``, the first compile
or cache load, the warm-up rounds.  The window opens after a
``block_until_ready`` on the score buffer at the end of warm-up; the
callback stamps the clock at the end of every round; the first round that
ends past ``--seconds`` ends training, and the window closes after a
``block_until_ready`` on the scores.  The program hands a round's packed
vectors to the host one round late, so a stamp may run a round behind the
device: the rate is taken over the whole window, never from one stamp,
and the intervals are those between all the stamps with the window's open
and close at the ends.  That is one interval more than rounds: under the
late hand-off the first stamp comes at once and the last round's end is
seen only by the closing wait, so one interval is near zero.  It sits in
the lower tail and does not move a 95th percentile; a stall still shows.

What the warm-up rounds produced (the first trees of the same call, the
same compiled step and the same state that the window then drives) is
compared with the plain reference once the window has closed.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from .. import data as datagen
from .. import device, layers, result


def _sync(booster):
    """Wait until the device has finished the rounds enqueued so far."""
    import jax
    jax.block_until_ready(booster._booster.train_data.score)


class Window:
    """The callback: warm-up, open, stamps, optional trace, close."""

    def __init__(self, *, warmup, seconds, trace, trace_dir, traffic,
                 compile_events, stop_exc):
        self.warmup = int(warmup)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.trace_dir = trace_dir
        self.trace_start_s = float(traffic["trace_start_share"]) * seconds
        self.trace_rounds = int(traffic["trace_rounds"])
        self.compile_events = compile_events
        self.stop_exc = stop_exc
        self.warm_losses = []          # the program's own loss, steps 1..
        self.t_open = self.t_close = None
        self.t_open_wall = self.t_close_wall = None
        self.stamps = []
        self.rounds = 0
        self.traced = None             # (first_round, last_round)
        self._tracing_since = None
        self._span = None
        self.compiles_at_open = 0

    # -- the harness's own span around every round of a traced window ----
    def _span_close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def _span_open(self, round_no):
        import jax
        self._span = jax.profiler.TraceAnnotation(f"bench_round_{round_no}")
        self._span.__enter__()

    def __call__(self, env):
        import jax
        k = env.iteration + 1                    # rounds done in this call
        if k <= self.warmup:
            _sync(env.model)
            for _, mname, v, _ in env.model.eval_train():
                if mname == "binary_logloss":
                    self.warm_losses.append(float(v))
            if k == self.warmup:
                _sync(env.model)
                self.compiles_at_open = len(self.compile_events())
                self.t_open_wall = time.time()
                self.t_open = time.perf_counter()
                self.stamps = [self.t_open]
            return
        now = time.perf_counter()
        self.stamps.append(now)
        elapsed = now - self.t_open
        if self.trace:
            if self._tracing_since is None and self.traced is None \
                    and elapsed >= self.trace_start_s:
                _sync(env.model)
                jax.profiler.start_trace(self.trace_dir)
                self._tracing_since = k
                self._span_open(k + 1)
            elif self._tracing_since is not None:
                self._span_close()
                k0 = self._tracing_since
                if k - k0 >= self.trace_rounds:
                    _sync(env.model)
                    jax.profiler.stop_trace()
                    self.traced = (k0 + 1, k)
                    self._tracing_since = None
                else:
                    self._span_open(k + 1)
        if elapsed >= self.seconds and self._tracing_since is None:
            _sync(env.model)
            self.t_close = time.perf_counter()
            self.t_close_wall = time.time()
            self.rounds = len(self.stamps) - 1
            self.stamps.append(self.t_close)     # device finished
            raise self.stop_exc(env.iteration)


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
    X, y = datagen.make(cfg["data"], rows, args.seed)
    say(f"data: {rows} x {X.shape[1]} from seed {args.seed}")

    import lightgbm_tpu as lgb
    from lightgbm_tpu import callback as lgb_callback
    from lightgbm_tpu.obs import compile_ledger

    params = dict(cfg["params"])
    dataset = lgb.Dataset(X, label=y, params=dict(params))
    dataset.construct()
    t_binned = time.time()
    say("binned")
    bounds = [np.asarray(m.bin_upper_bound, np.float64)
              for m in dataset._binned.mappers]

    own_trace_dir = None
    trace_dir = args.out
    if args.trace and trace_dir is None:
        own_trace_dir = trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    win = Window(warmup=traffic["warmup_rounds"], seconds=args.seconds,
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
        _sync(booster)
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
    del booster, dataset
    gc.collect()

    # ---- per-layer metrics (traced run) ---------------------------------
    breakdown = None
    device_block = {"platform": chip["platform"], "kind": chip["kind"],
                    "count": chip["count"], "memory_peak_bytes": peak_bytes}
    metrics = {}
    if args.trace:
        ctx = layers.Context(
            trace_dir=trace_dir, traced=win.traced, traced_trees=traced_trees,
            rows=rows, features=X.shape[1], peaks=chip["peaks"],
            compiles_in_window=len(in_window), peak_bytes=peak_bytes,
            setup_compile_s=sum(float(e["seconds"]) for e in setup_compiles),
            chips=chip["count"])
        if not rehearse:
            metrics, breakdown, busy = layers.read_all(
                cell["name"], ctx, cell["kind"])
            device_block.update(busy)
        else:
            layers.rehearse_all(cell["name"], ctx, say, cell["kind"])
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

    # ---- correct: the first trees against the plain reference -----------
    from .. import correct
    t_ref = time.time()
    compared, notes = correct.check_train(
        X, y, bounds, check_trees, program_losses, cfg, cell.get("limits", {}))
    say(f"reference: {time.time() - t_ref:.1f} s; {notes}")
    ok = result.verdict(compared) and failed == 0
    return {"correct": ok, "attempted": rounds, "failed": failed,
            "metrics": metrics, "device": device_block, "compared": compared,
            "breakdown": breakdown,
            "extra": {"window_s": window_s, "rounds": rounds,
                      "reference_s": time.time() - t_ref}}


def run(cell, args, chip, t_process_start) -> int:
    out = measure(cell, args, chip, t_process_start)
    if out is None:
        return 3
    if args.rehearse:
        print(f"rehearsal on {chip['platform']}: {out['attempted']} rounds, "
              f"correct {out['correct']}, compared "
              f"{ {k: v['value'] for k, v in out['compared'].items()} }")
    else:
        print(result.last_line(**out))
    sys.stdout.flush()
    result.print_compared(out["compared"], out["correct"])
    return 0
