"""Telemetry layer (lightgbm_tpu/obs/): registry semantics, the JSONL
per-iteration event stream, static collective-traffic accounting checked
against hand-computed histogram payload sizes on the 8-virtual-device
mesh, trace capture, and the log warn_once / stdlib-bridge satellites."""

import logging
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.utils import log as lgb_log
from lightgbm_tpu.utils import timetag


def _data(n=400, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_merge_reset():
    r = obs.Registry()
    r.inc("x")
    r.inc("x", 4)
    r.set_gauge("g", 7.5)
    snap = r.snapshot()
    assert snap["counters"]["x"] == 5
    assert snap["gauges"]["g"] == 7.5
    # merge: counters add, gauges last-write-wins
    r.merge({"counters": {"x": 2, "y": 1}, "gauges": {"g": 1.0}})
    snap = r.snapshot()
    assert snap["counters"] == {"x": 7, "y": 1}
    assert snap["gauges"]["g"] == 1.0
    r.reset()
    assert r.snapshot()["counters"] == {}
    assert r.snapshot()["gauges"] == {}


def test_process_registry_survives_reset_config():
    """reset_config rebuilds learner state; the run's telemetry account
    must persist across it (counters are process-scoped, not booster-
    scoped)."""
    X, y = _data(300, 4, seed=1)
    ds = lgb.Dataset(X, label=y)
    booster = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbose": -1}, ds, num_boost_round=2)
    before = booster.telemetry()["counters"]["iterations"]
    assert before >= 2
    booster.reset_parameter({"learning_rate": 0.05})
    booster.update()
    after = booster.telemetry()["counters"]["iterations"]
    assert after >= before + 1
    # HBM gauges from estimate_train_memory were recorded at setup
    gauges = booster.telemetry()["gauges"]
    assert gauges["hbm_train_estimate_bytes"] > 0
    assert gauges["hbm_histogram_cache_bytes"] > 0


# ---------------------------------------------------------------------------
# JSONL event stream
# ---------------------------------------------------------------------------

def test_events_jsonl_roundtrip(tmp_path):
    """3-iteration CPU train -> one record per iteration with phase
    timings, eval values, tree shape, cumulative collective bytes."""
    X, y = _data()
    path = str(tmp_path / "events.jsonl")
    ds = lgb.Dataset(X, label=y)
    vs = ds.create_valid(X[:100], y[:100])
    timetag.enable(True)
    timetag.reset()
    try:
        booster = lgb.train(
            {"objective": "binary", "num_leaves": 7, "verbose": -1,
             "metric": "auc"},
            ds, num_boost_round=3, valid_sets=[vs], events_file=path)
    finally:
        timetag.enable(False)
        timetag.reset()
    events = obs.read_events(path)
    assert [e["iter"] for e in events] == [0, 1, 2]
    for e in events:
        assert e["schema"] == obs.SCHEMA_VERSION
        assert e["wall_s"] > 0
        # TIMETAG was on: the per-phase breakdown folds in
        assert "GBDT::tree" in e["phases"]
        assert e["bag_cnt"] == 400          # bagging off -> full data
        assert e["comm_bytes_cum"] == 0     # serial learner, no collectives
        assert e["comm_calls_cum"] == 0
        assert len(e["trees"]) == 1         # binary: one tree per iter
        assert e["trees"][0]["num_leaves"] >= 2
        assert e["trees"][0]["max_depth"] >= 1
        assert 0.0 <= e["eval"]["valid_0"]["auc"] <= 1.0
    assert booster.num_trees() == 3


def test_events_bag_cnt_tracks_bagging(tmp_path):
    X, y = _data(500, 4, seed=3)
    path = str(tmp_path / "events.jsonl")
    ds = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
               "bagging_fraction": 0.5, "bagging_freq": 1},
              ds, num_boost_round=2, events_file=path)
    events = obs.read_events(path)
    assert [e["bag_cnt"] for e in events] == [250, 250]


def test_event_recorder_commit_on_advance(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    rec = obs.EventRecorder(path)
    rec.note(0, wall_s=0.1)
    rec.note(0, eval={"valid_0": {"auc": 0.9}})
    assert rec.events_written == 0          # nothing later noted yet
    rec.note(1, wall_s=0.2)
    assert rec.events_written == 1          # iter 0 committed on advance
    rec.close()                             # drains the rest
    events = obs.read_events(path)
    assert events[0]["eval"] == {"valid_0": {"auc": 0.9}}
    assert events[0]["wall_s"] == 0.1
    assert events[1]["iter"] == 1 and events[1]["wall_s"] == 0.2


# ---------------------------------------------------------------------------
# collective-traffic accounting (static shape math)
# ---------------------------------------------------------------------------

def test_comm_traffic_hand_computed():
    from lightgbm_tpu.parallel.comm import (DataParallelComm,
                                            FeatureParallelComm,
                                            HistExchange,
                                            VotingParallelComm,
                                            traffic_totals)
    F, B, L, k = 6, 16, 8, 8
    steps = L - 1
    F_pad = 8
    # data-parallel over leaf-ordered shards: one all-reduce of int32
    # digit sums in 16-bit halves, [F, 18, B], for the root and one a
    # split step (L in all),
    # the root's integer row count in one more call (its sums are the
    # histogram's own), its three scales in a pmax
    t = HistExchange("d", k).traffic_per_tree(F, B, L)
    assert t["psum"] == {"calls": 1 + L, "bytes": 4 + F * 18 * B * 4 * L}
    assert t["pmax"] == {"calls": 1, "bytes": 12}
    assert set(t) == {"psum", "pmax"}

    # data-parallel on ops/grow.py (uint16 bins, EFB): the FULL float
    # histogram all-reduced, the root's and both children's at every
    # split; each bin entry is <sum_g, sum_h, count> f32 = 12 bytes
    t2 = DataParallelComm("d", k).traffic_per_tree(F, B, L)
    assert t2["psum"]["calls"] == 3 + 1 + steps
    assert t2["psum"]["bytes"] == 12 + F * B * 12 * (1 + 2 * steps)
    assert set(t2) == {"psum"}

    # feature-parallel ships ONLY SplitInfos — zero histogram bytes
    t3 = FeatureParallelComm("f", k, 1).traffic_per_tree(F_pad, B, L)
    assert set(t3) == {"all_gather"}
    assert t3["all_gather"]["bytes"] == 6 * 4 * (1 + 2 * steps)

    # voting: O(top_k) election lists + elected-features-only psum
    K = min(20, F)
    t4 = VotingParallelComm("d", k, 20).traffic_per_tree(F, B, L)
    assert t4["psum"]["bytes"] == 12 + K * B * 12 * (1 + 2 * steps)
    assert t4["all_gather"]["calls"] == 2 * (1 + steps)
    assert t4["all_gather"]["bytes"] == 2 * K * 4 * (1 + 2 * steps)

    calls, total = traffic_totals(t)
    assert calls == sum(v["calls"] for v in t.values())
    assert total == sum(v["bytes"] for v in t.values())
    assert traffic_totals({}) == (0, 0)


def test_comm_traffic_through_parallel_grow():
    import jax
    from jax.sharding import Mesh
    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.parallel import make_parallel_grow
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must force 8 CPU devices"
    mesh = Mesh(np.array(devs[:8]), ("data",))
    params = GrowParams(num_leaves=8, max_bin=16, min_data_in_leaf=1,
                        min_sum_hessian_in_leaf=0.0)
    fn = make_parallel_grow(mesh, "data", params)
    # uint8 unbundled bins grow leaf-ordered shards: 8 digit-sum
    # exchanges a tree; uint16 bins or EFB columns keep the float
    # histograms of ops/grow.py
    t = fn.traffic_per_tree(6)
    assert t["psum"] == {"calls": 1 + 8, "bytes": 4 + 6 * 18 * 16 * 4 * 8}
    for kw in ({"bins_dtype": np.uint16}, {"bundled": True}):
        t = fn.traffic_per_tree(6, **kw)
        assert t["psum"]["bytes"] == 12 + 6 * 16 * 3 * 4 * (1 + 2 * 7)


def test_gbdt_accumulates_comm_bytes(tmp_path):
    """End-to-end: a 2-round data-parallel train on the 8-virtual-device
    mesh reports exactly 2x the static per-tree account, in both the
    Booster accessor and the event stream."""
    X, y = _data(600, 6, seed=2)
    path = str(tmp_path / "events.jsonl")
    ds = lgb.Dataset(X, label=y)
    booster = lgb.train(
        {"objective": "binary", "num_leaves": 4, "verbose": -1,
         "tree_learner": "data", "num_machines": 8, "max_bin": 16,
         "min_data_in_leaf": 5},
        ds, num_boost_round=2, events_file=path)
    tele = booster.telemetry()
    per_tree = sum(v["bytes"] for v in tele["comm"]["per_tree"].values())
    assert per_tree > 0
    assert tele["comm"]["bytes_cum"] == 2 * per_tree
    events = obs.read_events(path)
    assert events[-1]["comm_bytes_cum"] == tele["comm"]["bytes_cum"]
    assert events[0]["comm_bytes_cum"] == per_tree


# ---------------------------------------------------------------------------
# device trace capture
# ---------------------------------------------------------------------------

def test_trace_capture_window(tmp_path):
    trace_dir = str(tmp_path / "trace")
    X, y = _data(200, 3, seed=5)
    ds = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
               "trace_dir": trace_dir, "trace_start_iter": 0,
               "trace_num_iters": 1}, ds, num_boost_round=2)
    files = [os.path.join(r, f)
             for r, _, fs in os.walk(trace_dir) for f in fs]
    assert files, "trace window produced no profiler output"


def test_trace_window_counts_from_actual_start(tmp_path, monkeypatch):
    """Continued training resumes past start_iter; the window must span
    num_iters from where the trace actually started, not be truncated by
    the configured start_iter arithmetic."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    tc = obs.TraceCapture(str(tmp_path), start_iter=5, num_iters=2)
    tc.iter_begin(20)                   # resume point far past start_iter
    assert calls == ["start"]
    tc.iter_end(20)                     # only 1 iteration inside: stay open
    assert calls == ["start"]
    tc.iter_end(21)                     # 2 iterations inside: close
    assert calls == ["start", "stop"]
    tc.close()                          # idempotent
    assert calls == ["start", "stop"]


def test_trace_env_var_wins(tmp_path, monkeypatch):
    env_dir = str(tmp_path / "envtrace")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE_DIR", env_dir)
    tc = obs.TraceCapture.from_config(
        lgb.Config({"trace_dir": "/ignored", "trace_start_iter": 1,
                    "trace_num_iters": 3}))
    assert tc.trace_dir == env_dir
    assert tc.start_iter == 1 and tc.num_iters == 3
    monkeypatch.delenv("LIGHTGBM_TPU_TRACE_DIR")
    assert obs.TraceCapture.from_config(lgb.Config({})) is None


# ---------------------------------------------------------------------------
# log satellites: warn_once + stdlib bridge
# ---------------------------------------------------------------------------

def test_warn_once_dedupes(capsys):
    lgb_log.reset_warn_once()
    lgb_log.warn_once("k1", "warn-once payload %d", 1)
    lgb_log.warn_once("k1", "warn-once payload %d", 2)
    lgb_log.warn_once("k2", "other key")
    err = capsys.readouterr().err
    assert err.count("warn-once payload") == 1
    assert "other key" in err
    lgb_log.reset_warn_once()


def test_stdlib_bridge_mirrors_records():
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = lgb_log.enable_stdlib_bridge("lightgbm_tpu_test_bridge")
    handler = _Capture()
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        lgb_log.set_verbosity(-1)   # console fully suppressed...
        lgb_log.info("bridged %s", "yes")
        lgb_log.warning("bridged warning")
        with pytest.raises(lgb.LightGBMError):
            lgb_log.fatal("bridged fatal")
    finally:
        lgb_log.set_verbosity(1)
        lgb_log.disable_stdlib_bridge()
        logger.removeHandler(handler)
    msgs = [r.getMessage() for r in records]
    assert "bridged yes" in msgs           # ...but the bridge still sees all
    assert "bridged warning" in msgs
    assert "bridged fatal" in msgs
    levels = {r.getMessage(): r.levelno for r in records}
    assert levels["bridged warning"] == logging.WARNING
    assert levels["bridged fatal"] == logging.CRITICAL
