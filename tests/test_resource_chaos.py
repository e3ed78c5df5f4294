"""Resource-exhaustion chaos suite (marker ``resource_chaos``):
the one classic failure class PRs 2/9/11/13 skipped — running out of a
resource (docs/FAULT_TOLERANCE.md §Resource exhaustion).

What is pinned here:

1. **ENOSPC mid-run is contained**: a full training run with the disk
   failing under every telemetry/state sink at round k finishes all
   rounds BIT-IDENTICAL to an uninjected run, the last-good snapshot
   stays readable, every disabled sink is named in a warning, no
   orphaned ``.tmp`` survives, and ``sink_write_errors_total`` matches
   the injection count exactly.
2. **Device OOM is a diagnosis, not a backtrace**: an injected
   ``RESOURCE_EXHAUSTED`` at the jit dispatch boundary surfaces as a
   named ``DeviceOOM`` (a ``LightGBMError``) carrying the program name,
   the abstract call shapes, a memwatch snapshot and the admission
   gate's per-component memory table.
3. **The admission gate + degrade ladder** refuse/degrade as
   documented, and — with the guarded-writer layer — record ZERO new
   XLA programs (resource handling is host-side by construction).
4. **Estimate accuracy**: ``estimate_train_memory`` agrees with the
   memwatch-measured live-array peak within a bounded factor, so the
   gate cannot silently rot as new device buffers are added.
"""

import errno
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.models.gbdt import GBDT, estimate_train_memory
from lightgbm_tpu.obs import compile_ledger
from lightgbm_tpu.testing import faults
from lightgbm_tpu.utils import diskguard, log
from lightgbm_tpu.utils.log import LightGBMError
from lightgbm_tpu.utils.resource import (DEGRADE_STEPS, DeviceOOM,
                                         MemoryBudgetExceeded)

pytestmark = pytest.mark.resource_chaos


def _data(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float32)
    return X, y


def _params(tmp_path, **over):
    p = {"objective": "binary", "num_leaves": 7, "max_bin": 32,
         "min_data_in_leaf": 5, "verbose": -1}
    p.update(over)
    return p


@pytest.fixture(autouse=True)
def _fresh_sinks():
    """Each test starts with every sink armed and one-shot warnings
    re-armed (the chaos assertions read both)."""
    diskguard.reset_disabled()
    log.reset_warn_once()
    yield
    diskguard.reset_disabled()


# ---------------------------------------------------------------------------
# 1. ENOSPC injected mid-run: contained, bit-identical, last-good intact
# ---------------------------------------------------------------------------

def _train_full(tmp_path, X, y, subdir, inject_at=None):
    """One instrumented training run (events + compile ledger +
    snapshots), optionally with every guarded write under ``subdir``
    failing ENOSPC from iteration ``inject_at`` on.  Returns
    (model_text, injector stats or None)."""
    d = tmp_path / subdir
    d.mkdir()
    params = _params(tmp_path,
                     events_file=str(d / "events.jsonl"),
                     compile_ledger_file=str(d / "ledger.jsonl"),
                     snapshot_dir=str(d / "snaps"), snapshot_freq=2)
    train = lgb.Dataset(X, y)
    if inject_at is None:
        booster = lgb.train(params, train, num_boost_round=8)
        return booster.model_to_string(), None
    with faults.fail_writes(errno.ENOSPC, str(d / "*"),
                            armed=False) as stats:
        def arm(env):
            if env.iteration >= inject_at:
                stats["armed"] = True
        arm.before_iteration = True
        arm.order = -99
        booster = lgb.train(params, train, num_boost_round=8,
                            callbacks=[arm])
    return booster.model_to_string(), stats


def test_enospc_mid_run_is_contained_and_bit_identical(tmp_path, capsys):
    X, y = _data()
    clean_model, _ = _train_full(tmp_path, X, y, "clean")
    c0 = obs.get_counter("sink_write_errors_total")
    programs0 = {e["program"] for e in compile_ledger.events()}
    injected_model, stats = _train_full(tmp_path, X, y, "injected",
                                        inject_at=5)
    # -- the chaos acceptance, clause by clause -----------------------
    # all rounds finished, bit-identical to the uninjected run
    assert injected_model == clean_model
    # the injection actually struck (events sink + >=1 snapshot write)
    assert stats["fired"] >= 2
    # sink_write_errors_total matches the injection count exactly
    assert obs.get_counter("sink_write_errors_total") - c0 \
        == stats["fired"]
    # every disabled sink is named in a warning
    err = capsys.readouterr().err
    assert "sink 'events'" in err
    assert "sink 'snapshot'" in err
    assert "disk_full" in err
    # the last-good snapshot (written before the injection) is readable
    from lightgbm_tpu.snapshot import load_latest_snapshot
    found = load_latest_snapshot(str(tmp_path / "injected" / "snaps"))
    assert found is not None
    assert found[1]["rounds_done"] == 4
    # no orphaned .tmp survives the failed writes
    snaps = os.listdir(tmp_path / "injected" / "snaps")
    assert not [f for f in snaps if f.endswith(".tmp")]
    # the events records committed BEFORE the strike are on disk intact
    recs = [json.loads(ln) for ln in
            open(tmp_path / "injected" / "events.jsonl") if ln.strip()]
    # (the set-up account's one record comes first and has no iteration:
    # obs/setup.py, schema 2)
    assert "setup" in recs[0]
    recs = recs[1:]
    assert len(recs) >= 3
    assert [r["iter"] for r in recs] == list(range(len(recs)))
    # compile-ledger pin: the injected run introduced no new XLA
    # programs over the clean run (resource handling is host-side)
    assert {e["program"] for e in compile_ledger.events()} == programs0


def test_disk_full_after_budget_strikes_the_events_sink(tmp_path):
    X, y = _data(n=300)
    ev = tmp_path / "events.jsonl"
    params = _params(tmp_path, events_file=str(ev))
    c0 = obs.get_counter("sink_write_errors_events")
    with faults.disk_full_after(600, str(ev)) as stats:
        booster = lgb.train(params, lgb.Dataset(X, y), num_boost_round=6)
    assert booster.num_trees() == 6          # the run survived
    assert stats["fired"] >= 1
    assert obs.get_counter("sink_write_errors_events") - c0 >= 1
    # the bytes that fit are valid JSONL (no torn half-line commits at
    # the guarded layer: a failed write drops the whole record)
    got = [json.loads(ln) for ln in open(ev) if ln.strip()]
    assert all("iter" in r for r in got)


def test_crash_without_close_keeps_committed_events(tmp_path):
    """Satellite pin (torn_snapshot_write-style kill): the recorder is
    line-buffered + flushed per committed record, so a run that dies
    without ever calling close() keeps every record committed before
    the crash — the tail you need to debug the crash."""
    from lightgbm_tpu.obs import EventRecorder
    path = tmp_path / "ev.jsonl"
    rec = EventRecorder(str(path))
    for it in range(6):
        rec.note(it, wall_s=0.1 * it)
    # records 0..4 committed (5 still pending); simulate a hard crash:
    # no close(), no flush — read the file as another process would
    got = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert [r["iter"] for r in got] == [0, 1, 2, 3, 4]
    rec.close()


def test_events_flush_every_batches_flushes(tmp_path):
    from lightgbm_tpu.obs import EventRecorder
    path = tmp_path / "ev.jsonl"
    rec = EventRecorder(str(path), flush_every=3)
    for it in range(8):
        rec.note(it, wall_s=1.0)
    rec.close()
    got = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(got) == 8                     # close() drains everything


def test_quarantine_sink_enospc_keeps_accounting(tmp_path):
    """The quarantine SINK dying must not break the error-budget
    accounting (the in-memory verdicts are the contract; the file is
    telemetry)."""
    from lightgbm_tpu.io.guard import IngestGuard
    g = IngestGuard(str(tmp_path / "data.tsv"), policy="quarantine",
                    max_bad_rows=10)
    with faults.fail_writes(errno.ENOSPC, str(tmp_path / "*")) as stats:
        assert g.bad_row(3, "x\ty", "ragged_row", "5 != 6") is True
        assert g.bad_row(7, "a\tb", "unparseable_token", "'zz'") is True
    assert stats["fired"] >= 1
    assert g.bad_total == 2
    assert g.by_reason == {"ragged_row": 1, "unparseable_token": 1}
    g.finish()


def test_serve_state_write_failure_keeps_last_good(tmp_path):
    from lightgbm_tpu.serve.fleet import ModelManager
    state = tmp_path / "serve_state.json"
    mgr = ModelManager.__new__(ModelManager)
    mgr.state_file = str(state)
    mgr.note_good("/models/a.txt", target="primary", generation=3)
    assert ModelManager.restore_path(str(state)) is None  # file missing
    # write a real model path so restore_path can see it exists
    model = tmp_path / "m.txt"
    model.write_text("x")
    mgr.note_good(str(model), target="primary", generation=4)
    assert ModelManager.restore_path(str(state)) == str(model)
    c0 = obs.get_counter("sink_write_errors_serve_state")
    with faults.fail_writes(errno.EDQUOT, str(tmp_path / "*")):
        mgr.note_good("/models/never.txt", target="primary", generation=5)
    assert obs.get_counter("sink_write_errors_serve_state") - c0 == 1
    # the last-good file survived the failed write, no .tmp orphan
    assert ModelManager.restore_path(str(state)) == str(model)
    assert not (tmp_path / "serve_state.json.tmp").exists()


def test_compile_ledger_sink_disables_not_crashes(tmp_path):
    path = tmp_path / "ledger.jsonl"
    compile_ledger.configure(str(path))
    try:
        c0 = obs.get_counter("sink_write_errors_compile_ledger")
        with faults.fail_writes(errno.EROFS, str(tmp_path / "*")):
            compile_ledger.record("prog_a", "f32[8]", 0.1)
            compile_ledger.record("prog_b", "f32[8]", 0.1)
        # first failure disabled the sink; the second never attempted
        assert obs.get_counter(
            "sink_write_errors_compile_ledger") - c0 == 1
        # the in-memory account kept both events
        assert {"prog_a", "prog_b"} <= {e["program"]
                                        for e in compile_ledger.events()}
        assert not path.exists()
    finally:
        compile_ledger.configure(None)


def test_tracing_export_failure_disables_tracer(tmp_path):
    from lightgbm_tpu.obs.tracing import Tracer
    t = Tracer()
    t.path = str(tmp_path / "trace.json")
    t.enabled = True
    with t.span("GBDT::iteration"):
        pass
    with faults.fail_writes(errno.ENOSPC, str(tmp_path / "*")):
        assert t.maybe_export() is None
    assert t.enabled is False                # re-collecting is pointless
    assert not (tmp_path / "trace.json").exists()


def test_predict_output_enospc_is_a_named_fatal(tmp_path):
    """CLI task=predict: the output stream is an artifact — a full disk
    FAILS the task with a named diagnosis reporting rows written."""
    from lightgbm_tpu.cli import main as cli_main
    X, y = _data(n=300)
    booster = lgb.train(_params(tmp_path), lgb.Dataset(X, y),
                        num_boost_round=3)
    model = tmp_path / "model.txt"
    booster.save_model(str(model))
    data = tmp_path / "pred.tsv"
    with open(data, "w") as fh:
        for row in X:
            fh.write("0\t" + "\t".join(f"{v:g}" for v in row) + "\n")
    (tmp_path / "out").mkdir()
    out = tmp_path / "out" / "result.txt"
    with faults.fail_writes(errno.ENOSPC, str(tmp_path / "out" / "*")):
        with pytest.raises(LightGBMError) as ei:
            cli_main([f"task=predict", f"input_model={model}",
                      f"data={data}", f"output_result={out}"])
    msg = str(ei.value)
    assert "row(s) were written" in msg
    assert "disk_full" in msg


def test_sink_error_policy_fatal_flips_unpinned_sinks(tmp_path):
    """Post-review pin: ``sink_error_policy=fatal`` is not a no-op —
    the policy-unpinned sinks (events here) raise the classified
    ``SinkWriteError`` instead of disabling themselves, for runs where
    lost telemetry is unacceptable."""
    from lightgbm_tpu.obs import EventRecorder
    old = diskguard.default_policy()
    try:
        diskguard.set_default_policy("fatal")
        rec = EventRecorder(str(tmp_path / "ev.jsonl"))
        with faults.fail_writes(errno.ENOSPC, str(tmp_path / "*")):
            with pytest.raises(diskguard.SinkWriteError) as ei:
                rec.note(0, wall_s=1.0)
                rec.note(1, wall_s=1.0)   # commits record 0 -> raises
        assert ei.value.sink == "events"
        assert ei.value.classification == "disk_full"
    finally:
        diskguard.set_default_policy(old)


def test_model_file_save_failure_keeps_last_good(tmp_path):
    """Post-review pin: ``save_model`` used to truncate the destination
    in place, so an ENOSPC halfway through the save destroyed the
    previous good model.  The atomic artifact write keeps last-good and
    the failure is a named, classified ``SinkWriteError``."""
    X, y = _data(n=300)
    booster = lgb.train(_params(tmp_path), lgb.Dataset(X, y),
                        num_boost_round=3)
    model = tmp_path / "model.txt"
    booster.save_model(str(model))
    good = model.read_bytes()
    c0 = obs.get_counter("sink_write_errors_model_file")
    with faults.fail_writes(errno.ENOSPC, str(tmp_path / "*")):
        with pytest.raises(diskguard.SinkWriteError) as ei:
            booster.save_model(str(model))
    assert ei.value.sink == "model_file"
    assert ei.value.classification == "disk_full"
    # artifact failures are COUNTED like every other guarded failure
    assert obs.get_counter("sink_write_errors_model_file") - c0 == 1
    assert model.read_bytes() == good            # last-good survived
    assert not (tmp_path / "model.txt.tmp").exists()


def test_binary_dataset_save_failure_keeps_last_good(tmp_path):
    X, y = _data(n=200)
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    path = tmp_path / "train.bin"
    ds.save_binary(str(path))
    good = path.read_bytes()
    with faults.fail_writes(errno.EDQUOT, str(tmp_path / "*")):
        with pytest.raises(diskguard.SinkWriteError) as ei:
            ds.save_binary(str(path))
    assert ei.value.sink == "binary_dataset"
    assert ei.value.classification == "quota_exceeded"
    assert path.read_bytes() == good             # last-good survived
    assert not (tmp_path / "train.bin.tmp").exists()


def test_reset_training_data_reruns_admission_gate(tmp_path, monkeypatch):
    """Post-review pin: ``ResetTrainingData`` re-runs the HBM admission
    gate — a swapped dataset cannot sneak past the pre-flight check the
    constructor ran (it would die hours later in an opaque XLA
    RESOURCE_EXHAUSTED), and a degrade ladder applied at construction
    is re-walked instead of silently undone by the recomputed pad."""
    X, y = _data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    gb = GBDT(Config(_params(tmp_path, num_leaves=31)), ds)
    gb.train(2)
    monkeypatch.setenv("LGBT_DEVICE_MEMORY_BYTES", "1000")
    with pytest.raises(MemoryBudgetExceeded):
        gb.reset_training_data(ds)
    monkeypatch.delenv("LGBT_DEVICE_MEMORY_BYTES")
    # under memory_policy=degrade the reset walks the ladder again
    # (already-applied steps are skipped, not re-counted) and trains
    log.reset_warn_once()
    floor = estimate_train_memory(ds.num_data, ds.num_columns, 31, 32, 1,
                                  bin_itemsize=ds.bins.dtype.itemsize,
                                  leaf_cache=False)
    monkeypatch.setenv("LGBT_DEVICE_MEMORY_BYTES",
                       str(int(floor["total"] * 1.05)))
    gb2 = GBDT(Config(_params(tmp_path, num_leaves=31,
                              memory_policy="degrade")), ds)
    assert "hist_cache" in gb2._degrade_steps
    gb2.reset_training_data(ds)
    assert gb2._degrade_leaf_cache_off   # the degrade survived the reset
    gb2.train(2)
    assert len(gb2.models) == 2


def test_snapshot_tmp_sweep(tmp_path):
    """Satellite: stale .tmp files (a hard crash before os.replace) are
    swept by prune_snapshots instead of accumulating per retry."""
    from lightgbm_tpu import snapshot as snapmod
    d = tmp_path / "snaps"
    d.mkdir()
    snapmod.write_snapshot(str(d / "snapshot_0000000002.bin"),
                           {"booster": {}, "rounds_done": 2})
    (d / "snapshot_0000000004.bin.tmp").write_bytes(b"torn")
    (d / "snapshot_0000000006.bin.tmp").write_bytes(b"torn too")
    snapmod.prune_snapshots(str(d), keep=0)   # keep=0: sweep only
    left = sorted(os.listdir(d))
    assert left == ["snapshot_0000000002.bin"]


# ---------------------------------------------------------------------------
# 2. device OOM: a named diagnosis at the jit dispatch boundary
# ---------------------------------------------------------------------------

def test_injected_oom_is_a_named_diagnosis(tmp_path):
    X, y = _data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    cfg = Config(_params(tmp_path))
    gb = GBDT(cfg, ds)
    gb.train_one_iter()                      # warm: programs compiled
    c0 = obs.get_counter("device_oom_total")
    with faults.oom_on_program("train_step") as stats:
        with pytest.raises(DeviceOOM) as ei:
            gb.train_one_iter()
    assert stats["fired"] == 1
    err = ei.value
    assert isinstance(err, LightGBMError)    # one catchable family
    # the diagnosis names the program and its abstract shapes
    assert err.program == "train_step"
    assert "train_step" in str(err)
    assert "f32[" in err.shapes or "u8[" in err.shapes
    # ...the admission gate's per-component memory table...
    assert "admission estimate" in str(err)
    assert "histogram_cache" in str(err)
    assert "bins_device" in str(err)
    # ...and a memwatch snapshot of what the host/device held
    assert "memwatch" in str(err)
    assert obs.get_counter("device_oom_total") - c0 == 1
    # containment, not corruption: the booster state survived (the
    # poisoned dispatch never committed) and training can continue
    n0 = len(gb.models)
    gb.train_one_iter()
    assert len(gb.models) >= n0


def test_oom_classifier_ignores_ordinary_errors():
    from lightgbm_tpu.utils.resource import is_resource_exhausted
    assert is_resource_exhausted(
        faults.make_resource_exhausted("p"))
    assert is_resource_exhausted(MemoryError())
    assert not is_resource_exhausted(ValueError("shape mismatch"))
    assert not is_resource_exhausted(OSError(28, "No space left"))


def test_non_oom_dispatch_errors_pass_through():
    """The containment wrapper must re-raise everything else untouched
    — masking a real bug as an OOM would be worse than the backtrace."""
    from lightgbm_tpu.obs.compile_ledger import InstrumentedJit

    def boom():
        raise ValueError("a real bug")

    j = InstrumentedJit.__new__(InstrumentedJit)
    j._fn = boom
    j.program = "boom"
    j._seen_keys = set()
    with pytest.raises(ValueError, match="a real bug"):
        j._call_guarded()


# ---------------------------------------------------------------------------
# 3. admission gate + degrade ladder
# ---------------------------------------------------------------------------

def test_degrade_ladder_applies_in_order_and_counts(tmp_path, monkeypatch):
    X, y = _data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    # a budget the full config misses but the degraded one fits: compute
    # the no-cache, no-pad footprint and allow a little headroom
    floor = estimate_train_memory(ds.num_data, ds.num_columns, 31, 32, 1,
                                  bin_itemsize=ds.bins.dtype.itemsize,
                                  leaf_cache=False)
    monkeypatch.setenv("LGBT_DEVICE_MEMORY_BYTES",
                       str(int(floor["total"] * 1.05)))
    log.reset_warn_once()
    c0 = obs.get_counter("resource_degrade_total")
    cfg = Config(_params(tmp_path, num_leaves=31,
                         memory_policy="degrade"))
    gb = GBDT(cfg, ds)
    # the ladder fired (hist_cache at least; score_donation is
    # unavailable on CPU — aliasing is unsafe there — and row_pad only
    # if still needed), in documented order
    assert "hist_cache" in gb._degrade_steps
    assert list(gb._degrade_steps) == sorted(
        gb._degrade_steps, key=DEGRADE_STEPS.index)
    took = obs.get_counter("resource_degrade_total") - c0
    assert took == len(gb._degrade_steps) >= 1
    assert obs.get_counter("resource_degrade_hist_cache") >= 1
    # the degraded booster actually trains, and the cacheless learner
    # picks the SAME splits as the cached one (the cache is a reuse
    # strategy, not a model change; leaf aggregates re-associate in
    # f32, so values agree to float tolerance rather than bit-exactly)
    gb.train(3)
    assert len(gb.models) == 3
    monkeypatch.delenv("LGBT_DEVICE_MEMORY_BYTES")
    cfg2 = Config(_params(tmp_path, num_leaves=31))
    gb2 = GBDT(cfg2, ds)
    gb2.train(3)
    assert len(gb2.models) == 3
    for ta, tb in zip(gb.models, gb2.models):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_allclose(ta.threshold, tb.threshold, rtol=0,
                                   atol=0)
    np.testing.assert_allclose(gb.predict_raw(X), gb2.predict_raw(X),
                               rtol=1e-4, atol=1e-5)


def test_degrade_exhausted_refuses_with_table(tmp_path, monkeypatch):
    X, y = _data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    monkeypatch.setenv("LGBT_DEVICE_MEMORY_BYTES", "1024")  # 1 KB: hopeless
    cfg = Config(_params(tmp_path, memory_policy="degrade"))
    with pytest.raises(MemoryBudgetExceeded) as ei:
        GBDT(cfg, ds)
    err = ei.value
    assert "exceeds the device budget" in str(err)
    assert "Degrade ladder already applied" in str(err)
    assert err.limit == 1024
    assert set(err.estimate) >= {"bins_device", "histogram_cache",
                                 "total"}
    assert err.steps_taken                   # at least one step tried


def test_histogram_pool_size_is_a_real_bound_under_degrade(tmp_path,
                                                           monkeypatch):
    X, y = _data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    monkeypatch.delenv("LGBT_DEVICE_MEMORY_BYTES", raising=False)
    log.reset_warn_once()
    cfg = Config(_params(tmp_path, num_leaves=255,
                         histogram_pool_size=0.001,
                         memory_policy="degrade"))
    gb = GBDT(cfg, ds)
    assert "hist_cache" in gb._degrade_steps
    gb.train(2)
    assert len(gb.models) == 2


def test_score_donation_step_fires_where_aliasing_is_safe(tmp_path,
                                                          monkeypatch):
    """On an accelerator backend (simulated) with donation env'd off,
    the first ladder step re-enables it and drops the double buffer."""
    import lightgbm_tpu.models.gbdt as gbdt_mod
    X, y = _data()
    ds = BinnedDataset.from_matrix(X, y, max_bin=32, min_data_in_leaf=5)
    monkeypatch.setenv("LIGHTGBM_TPU_DONATION", "0")
    monkeypatch.setattr(gbdt_mod, "_donation_safe", lambda: True)
    full = estimate_train_memory(ds.num_data, ds.num_columns, 7, 32, 1,
                                 bin_itemsize=ds.bins.dtype.itemsize)
    # budget that fits once ONLY the double buffer goes away
    monkeypatch.setenv(
        "LGBT_DEVICE_MEMORY_BYTES",
        str(int(full["total"] - full["score_double_buffer"] // 2)))
    log.reset_warn_once()
    cfg = Config(_params(tmp_path, memory_policy="degrade"))
    gb = GBDT(cfg, ds)
    assert gb._degrade_steps == ("score_donation",)
    assert gb._donation_on() is True


def test_admission_and_diskguard_record_zero_xla_programs(tmp_path):
    """Compile-ledger pin: estimates, the gate, the degrade accounting
    and the guarded-writer layer are host-side — zero compile events."""
    n0 = len(compile_ledger.events())
    estimate_train_memory(100_000, 64, 255, 255, 2)
    estimate_train_memory(100_000, 64, 255, 255, 2, donate_score=True,
                          leaf_cache=False)
    from lightgbm_tpu.utils import resource
    resource.set_budget_table({"total": 1, "bins_device": 1}, "pin")
    resource.format_table({"total": 1, "bins_device": 1})
    w = diskguard.GuardedWriter(str(tmp_path / "s.jsonl"), sink="pin_sink")
    w.write('{"a": 1}\n')
    w.close()
    diskguard.append_line(str(tmp_path / "l.jsonl"), "{}", sink="pin_l")
    diskguard.write_file_atomic(str(tmp_path / "f.bin"), b"x", sink="pin_f")
    assert len(compile_ledger.events()) == n0


# ---------------------------------------------------------------------------
# 4. estimate accuracy vs memwatch (the gate cannot silently rot)
# ---------------------------------------------------------------------------

def test_estimate_tracks_memwatch_measured_peak(tmp_path):
    """``estimate_train_memory`` vs the memwatch-measured live-array
    peak over a real CPU training run: the estimate must be an UPPER
    bound on what Python holds live (it also budgets XLA working set
    the live-array walk cannot see), yet within a bounded factor — if a
    future PR adds a device buffer the estimate misses, the measured
    peak creeps toward/over the estimate and this pin fails before the
    admission gate rots."""
    import jax
    from lightgbm_tpu.obs import memwatch
    X, y = _data(n=4000, f=16, seed=3)
    base = memwatch.sample().get("live_bytes", 0)
    ds = BinnedDataset.from_matrix(X, y, max_bin=64, min_data_in_leaf=5)
    cfg = Config(_params(tmp_path, num_leaves=15, max_bin=64))
    gb = GBDT(cfg, ds)
    est = gb._train_mem_est
    peak = 0
    for _ in range(4):
        gb.train_one_iter()
        jax.block_until_ready(gb.train_data.score)
        peak = max(peak, memwatch.sample().get("live_bytes", 0) - base)
    assert peak > 0
    # upper bound: everything Python holds live fits the estimate
    assert est >= peak, (
        f"estimate {est}B < measured live peak {peak}B — a device "
        f"buffer is missing from estimate_train_memory")
    # bounded factor: the estimate may not balloon into meaninglessness
    assert est <= 64 * peak, (
        f"estimate {est}B is >64x the measured live peak {peak}B — "
        f"the admission gate would refuse configs that fit easily")


# ---------------------------------------------------------------------------
# bench_regress passthrough (informational `resource` BENCH block)
# ---------------------------------------------------------------------------

def test_bench_regress_passes_resource_block_through(tmp_path, capsys):
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "bench_regress", pathlib.Path(__file__).resolve().parent.parent
        / "tools" / "bench_regress.py")
    bench_regress = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_regress)

    base = {"metric": "m", "value": 10.0, "unit": "iters/sec"}
    cand = {"metric": "m", "value": 10.2, "unit": "iters/sec",
            "resource": {"estimated_peak_bytes": 123456,
                         "measured_peak_bytes": 65536,
                         "degrade_steps": ["hist_cache"],
                         "sink_write_errors": 0}}
    b = tmp_path / "b.json"
    c = tmp_path / "c.json"
    b.write_text(json.dumps(base))
    c.write_text(json.dumps(cand))
    rc = bench_regress.main(["--baseline", str(b), "--candidate", str(c),
                             "--threshold", "5"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    verdict = json.loads(out)
    assert rc == 0 and verdict["ok"]
    # informational: rides along on the side that carries it, never
    # gated, never required (old baselines keep comparing)
    assert verdict["resource_candidate"]["degrade_steps"] == ["hist_cache"]
    assert "resource_baseline" not in verdict
