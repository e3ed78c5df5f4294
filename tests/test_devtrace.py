"""obs/devtrace.py: the program's own reduction of a profiler window.

Pure functions over compiled text and plain event lists, so nothing here
needs a profiler or a chip: a hand-written HLO module for the phase map
(fusion takes its root's op_name, compiler-inserted instructions follow
their first operand, a loop's boundary copies go to the loop, a copy of
an argument to its user, hoisted literals are nameless), and a recorded
list of events for the reduction (self time under nesting, the join by
program, ``inserted``, ``unattributed``, idle gaps named by ``lgbt:``
spans)."""

import json
import re

import numpy as np
import pytest

from lightgbm_tpu.obs import devtrace, phases

HLO = """HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%region_cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0), metadata={op_name="sort"}
  %b = f32[] parameter(1), metadata={op_name="sort"}
  ROOT %lt.1 = pred[] compare(%a, %b), direction=LT, metadata={op_name="sort"}
}

%fused_key (p0: f32[8]) -> u8[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.7 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step_fn)/gradients/mul"}
  ROOT %convert.9 = u8[8]{0} convert(%mul.7), metadata={op_name="jit(step_fn)/grow_loop/while/body/split/key/convert_element_type"}
}

%fused_lost (q0: f32[8]) -> f32[8] {
  %q0 = f32[8]{0} parameter(0)
  ROOT %slice.3 = f32[8]{0} slice(%q0), slice={[0:8]}
}

%body (carry: (s32[], f32[8])) -> (s32[], f32[8]) {
  %carry = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%carry), index=1
  %copy.5 = f32[8]{0} copy(%gte.1)
  %dynamic_slice.2 = f32[8]{0} dynamic-slice(%copy.5), dynamic_slice_sizes={8}, metadata={op_name="jit(step_fn)/grow_loop/while/body/split/window_read/dynamic_slice"}
  %copy.6 = f32[8]{0:T(1024)S(1)} copy(%dynamic_slice.2)
  %fusion.4 = u8[8]{0} fusion(%copy.6), kind=kLoop, calls=%fused_key, metadata={op_name="jit(step_fn)/gradients/mul"}
  %sort.8 = (u8[8]{0}, f32[8]{0}) sort(%fusion.4, %copy.6), dimensions={0}, is_stable=true, to_apply=%region_cmp, metadata={op_name="jit(step_fn)/grow_loop/while/body/split/sort/sort" stack_frame_id=3}
  %gte.2 = f32[8]{0} get-tuple-element(%sort.8), index=1
  %slice_reduce_fusion.1 = f32[8]{0} fusion(%gte.2), kind=kLoop, calls=%fused_lost
  %digit_histogram.1 = s32[4,9,128]{2,1,0} custom-call(%slice_reduce_fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/grow_loop/while/body/hist/window/hist/kernel/pallas_call"}
  %gte.0 = s32[] get-tuple-element(%carry), index=0
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %slice_reduce_fusion.1)
}

%cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.9 = pred[] constant(true)
}

ENTRY %main (score: f32[8]) -> f32[8] {
  %score = f32[8]{0} parameter(0), metadata={op_name="score"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%score)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %constant.1 = s32[] constant(0), metadata={op_name="jit(step_fn)/jit(grow_tree_ordered)"}
  %broadcast.2 = f32[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(step_fn)/jit(grow_tree_ordered)"}
  %add.3 = f32[8]{0} add(%copy-done.1, %broadcast.2), metadata={op_name="jit(step_fn)/gradients/add"}
  %tuple.2 = (s32[], f32[8]{0}) tuple(%constant.1, %add.3)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/grow_loop/while"}
  %gte.9 = f32[8]{0} get-tuple-element(%while.1), index=1
  %rogue.1 = f32[8]{0} negate(%gte.9), metadata={op_name="jit(step_fn)/neg"}
  ROOT %add.4 = f32[8]{0} add(%rogue.1, %gte.9), metadata={op_name="jit(step_fn)/score_update/scatter-add"}
}
"""


def test_leaf_phase_is_the_innermost_declared_scope():
    lp = phases.leaf_phase
    assert lp("jit(f)/grow_loop/while/body/closed_call/cond/branch_0_fun/"
              "split/sort/sort") == "split/sort"
    # ops/grow.py's ``hist`` around the kernel's own scope: longer wins
    assert lp("jit(f)/hist/hist/kernel/pallas_call") == "hist/kernel"
    assert lp("jit(f)/hist/root/hist/window/slice") == "hist/window"
    # the last component is the primitive, never a scope
    assert lp("jit(f)/split/sort") == "split"
    assert lp("jit(f)/jit(take_along_axis)/gather") is None
    assert lp("gather") is None
    # the data-parallel shards' exchange holds a ``hist`` of its own in
    # its name: the phase that ends last is the innermost
    assert lp("jit(f)/grow_loop/while/body/exchange/hist/psum") \
        == "exchange/hist"
    assert lp("jit(f)/exchange/root/psum") == "exchange/root"
    assert lp("jit(f)/exchange/hist/hist/kernel/x") == "hist/kernel"
    assert set(phases.ROUND_PHASES) <= phases.DEVICE_PHASES
    assert len(set(phases.ROUND_PHASES)) == 18


def test_phase_map_parses_the_compiled_text():
    pm = devtrace.phase_map(HLO)
    ph = pm["phases"]
    assert pm["module"] == "jit_step_fn"
    # named instructions take their own leaf phase
    assert ph["sort.8"] == "split/sort"
    assert ph["dynamic_slice.2"] == "split/window_read"
    assert ph["digit_histogram.1"] == "hist/kernel"
    assert ph["while.1"] == "grow_loop"
    # a fusion takes its ROOT's op_name, not its own
    assert ph["fusion.4"] == "split/key"
    # a comparator's instructions are no device events of their own
    assert "lt.1" not in ph and "mul.7" not in ph
    # has an op_name, under no declared phase
    assert ph["rogue.1"] == devtrace.UNSCOPED
    assert pm["ops_unscoped"] == 1
    assert pm["unscoped_op_names"] == ["jit(step_fn)/neg"]
    assert pm["ops_by_phase"]["gradients"] == 2       # mul.7 (fused), add.3
    # ... and says which other phases the fusion holds: they blur
    assert pm["mixed"] == {"fusion.4": ["gradients"]}
    assert pm["ops_scoped"] == sum(pm["ops_by_phase"].values())


def test_inserted_follows_the_first_operand_until_one_is_named():
    pm = devtrace.phase_map(HLO)
    ph, ins = pm["phases"], set(pm["inserted"])
    # the compiler's copy of a named slice: the slice's phase
    assert ph["copy.6"] == "split/window_read" and "copy.6" in ins
    # the fusion that lost its metadata: through the tuple element to the
    # sort that produced it
    assert ph["slice_reduce_fusion.1"] == "split/sort"
    assert "slice_reduce_fusion.1" in ins
    # a copy of the loop's carried state: parameter has no producer, so
    # it belongs to the loop that holds the body
    assert ph["copy.5"] == "grow_loop" and "copy.5" in ins
    # a copy of the program's own argument has no producer at all: its
    # first user's phase
    assert ph["copy-start.1"] == "gradients"
    assert ph["copy-done.1"] == "gradients"
    assert {"copy-start.1", "copy-done.1"} <= ins
    # a literal jax hoisted to the top of the jitted call carries the
    # call's path and no primitive: nameless, goes to its user
    assert ph["broadcast.2"] == "gradients" and "broadcast.2" in ins
    # named instructions are never flagged
    assert not ins & {"sort.8", "fusion.4", "rogue.1", "while.1"}


SHARDED_HLO = """HloModule jit_step_fn, is_scheduled=true

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %constant.1 = f32[] constant(0), metadata={op_name="jit(step_fn)/jit(grow)/shard_map"}
  %broadcast.1 = f32[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(step_fn)/jit(grow)/shard_map"}
  %mul.1 = f32[8]{0} multiply(%x, %broadcast.1), metadata={op_name="jit(step_fn)/jit(grow)/shard_map/jit(grow_tree_ordered)/hist/window/mul" stack_frame_id=7}
  %shift-right-logical.3 = f32[8]{0} negate(%mul.1), metadata={op_name="jit(step_fn)/jit(grow)/shard_map/shift-right-logical.22"}
  %rogue.1 = f32[8]{0} negate(%shift-right-logical.3), metadata={op_name="jit(step_fn)/jit(grow)/shard_map/jit(grow_tree_ordered)/neg" stack_frame_id=8}
  ROOT %add.1 = f32[8]{0} add(%rogue.1, %mul.1), metadata={op_name="jit(step_fn)/jit(grow)/shard_map/jit(grow_tree_ordered)/exchange/hist/psum"}
}
"""


def test_what_the_compiler_names_by_the_shard_map_call_is_nameless():
    """The chip's compiler inlines a ``shard_map``'s body and puts the
    call's path before what was bare or nameless inside it: an
    instruction of its own making then reads
    ``.../shard_map/shift-right-logical.22`` where the serial program's
    twin has no name.  It resolves through its operand, as there; an
    operation the grower itself leaves under no scope still shows."""
    pm = devtrace.phase_map(SHARDED_HLO)
    ph, ins = pm["phases"], set(pm["inserted"])
    assert ph["shift-right-logical.3"] == "hist/window"
    assert "shift-right-logical.3" in ins
    assert ph["broadcast.1"] == "hist/window" and "broadcast.1" in ins
    assert ph["add.1"] == "exchange/hist"
    assert ph["rogue.1"] == devtrace.UNSCOPED
    assert pm["ops_unscoped"] == 1
    assert pm["unscoped_op_names"] == [
        "jit(step_fn)/jit(grow)/shard_map/jit(grow_tree_ordered)/neg"]


# -- the reduction ----------------------------------------------------------

def _ev(name, start_us, dur_us):
    return (name, start_us * 1000, dur_us * 1000)


MAPS = [
    {"module": "jit_step_fn", "program": "train_step",
     "phases": {"while.1": "grow_loop", "sort.8": "split/sort",
                "copy.6": "split/window_read",
                "digit_histogram.1": "hist/kernel",
                "add.4": "score_update"},
     "inserted": ["copy.6"], "mixed": {"sort.8": ["split/key"]}},
    # another program reuses an instruction name for another phase
    {"module": "jit_other", "program": "other",
     "phases": {"sort.8": "leaf_delta"}, "inserted": []},
]

OPS = [
    # round: a while holding a copy, a sort and the kernel
    _ev("%while.1 = (s32[], f32[8]{0}) while(%tuple.2), body=%body", 0, 1000),
    _ev("%copy.6 = f32[8]{0:T(1024)S(1)} copy(%dynamic_slice.2)", 100, 100),
    _ev("%sort.8 = (u8[8]{0}, f32[8]{0}) sort(%fusion.4, %copy.6)", 200, 400),
    _ev("%digit_histogram.1 = s32[4,9,128]{2,1,0} custom-call(%x)", 600, 300),
    _ev("%add.4 = f32[8]{0} add(%rogue.1, %gte.9)", 1000, 50),
    _ev("%mystery.3 = f32[8]{0} fusion(%x), kind=kLoop", 1050, 50),
    # gap of 400 us, then the other program
    _ev("%sort.8 = (s32[8]{0}, s32[8]{0}) sort(%a, %b)", 1500, 100),
]
MODULES = [_ev("jit_step_fn(123)", 0, 1100), _ev("jit_other(7)", 1500, 100)]
HOST = [_ev("lgbt:GBDT::iteration", 900, 1000),
        _ev("lgbt:GBDT::host_tree", 1050, 300)]


def test_self_time_under_nesting():
    st = {n.split(" ")[0]: s for n, _, s in devtrace.self_times(OPS[:4])}
    # the while holds copy 100 + sort 400 + kernel 300 of its 1000 us
    assert st["%while.1"] == 200_000
    assert st["%sort.8"] == 400_000
    # a child that overruns its parent only takes what the parent covers
    over = devtrace.self_times([("p", 0, 100), ("c", 50, 100)])
    assert over == [("p", 0, 50), ("c", 50, 100)]


def test_reduce_events_joins_by_name_within_the_running_program():
    rep = devtrace.reduce_events(OPS, MODULES, MAPS, HOST, rounds=2)
    ph = rep["phases"]
    per = 2 * 1000.0                       # us -> ms a round, two rounds
    assert ph["grow_loop"]["ms_per_round"] == pytest.approx(200 / per)
    assert ph["split/sort"]["ms_per_round"] == pytest.approx(400 / per)
    assert ph["hist/kernel"]["ms_per_round"] == pytest.approx(300 / per)
    assert ph["score_update"]["ms_per_round"] == pytest.approx(50 / per)
    # the same instruction name in the other program is the other phase
    assert ph["leaf_delta"]["ms_per_round"] == pytest.approx(100 / per)
    # inserted time shows under the phase that causes it
    assert ph["split/window_read"]["ms_per_round"] == pytest.approx(100 / per)
    assert ph["split/window_read"]["inserted_ms_per_round"] == \
        pytest.approx(100 / per)
    assert ph["split/sort"]["inserted_ms_per_round"] == 0
    # in no map
    assert rep["unattributed"]["ms_per_round"] == pytest.approx(50 / per)
    assert rep["mixed"] == [["split/sort", "split/key",
                             pytest.approx(400 / per)]]
    assert rep["unattributed"]["top"][0][0] == "mystery.3"
    # the phases and the unattributed rest sum to the busy time
    total = sum(v["ms_per_round"] for v in ph.values()) \
        + rep["unattributed"]["ms_per_round"]
    assert total * 2 / 1e3 == pytest.approx(rep["busy_s"])
    assert rep["busy_s"] == pytest.approx(1200e-6)
    assert rep["window_s"] == pytest.approx(1600e-6)
    # program order of the taxonomy, not of the dict
    order = [p for p in phases.ROUND_PHASES if p in ph]
    assert list(ph) == order


def test_idle_gaps_are_named_by_the_innermost_lgbt_span():
    rep = devtrace.reduce_events(OPS, MODULES, MAPS, HOST, rounds=1)
    # one gap: 1100 -> 1500 us, inside iteration AND host_tree: innermost
    assert rep["idle_gaps"] == [["lgbt:GBDT::host_tree",
                                 pytest.approx(400e-6)]]
    bare = devtrace.reduce_events(OPS, MODULES, MAPS, (), rounds=1)
    assert bare["idle_gaps"][0][0] == "outside_spans"


def test_without_a_modules_line_every_map_is_tried():
    rep = devtrace.reduce_events(OPS[:4], [], MAPS, (), rounds=1)
    assert rep["phases"]["split/sort"]["ms_per_round"] == pytest.approx(0.4)
    assert devtrace.instruction_name("sort.8") == "sort.8"
    assert devtrace.instruction_name("%sort.8 = (u8[8]) sort(%a)") == "sort.8"


def test_render_is_a_table_of_the_report():
    rep = devtrace.reduce_events(OPS, MODULES, MAPS, HOST, rounds=1)
    text = devtrace.render(rep)
    assert "split/sort" in text and "unattributed" in text
    assert "lgbt:GBDT::host_tree" in text
    json.dumps(rep)                        # the events record carries it


# -- the fused round's taxonomy is closed ------------------------------------

@pytest.fixture
def no_persistent_cache():
    """``compile_cache_dir=off`` does not switch the cache off in a process
    that has used it: jax decides once whether the persistent cache is in
    use and keeps the directory it opened, and that is the checkout's
    ``.jax_compile_cache/``, which every worker and every earlier run
    writes to.  A worker that ran another file first was then served a
    ``train_step`` of an older run (``cache_hit`` true, older op_names).
    Switch it off where jax looks, and put it back after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was_on = jax.config.jax_enable_compilation_cache
    was_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    jax.config.update("jax_compilation_cache_dir", was_dir)
    cc.reset_cache()


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_train_step_has_no_unscoped_operation(no_persistent_cache, learner):
    """(a) ``train_step`` lowered and compiled at a toy shape: every
    operation with an ``op_name`` of its own sits under a declared phase
    and every phase of the round's top level occurs.  ``data``: the
    sharded round over four virtual devices, leaf-ordered shards with
    the two ``exchange/*`` phases, which the serial round lacks."""
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.normal(size=(3000, 6))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    # the persistent cache is off: its key leaves metadata out, so an
    # entry compiled under older scopes would be served with their names
    g = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                            "verbose": -1, "bagging_fraction": 0.5,
                            "bagging_freq": 1, "compile_cache_dir": "off",
                            "tree_learner": learner, "num_machines": 4},
                    train_set=lgb.Dataset(X, label=y))._booster
    step = g._make_train_step()
    text = step.lower(g.train_data.score, g._feature_masks_all(),
                      g._bagging_mask(0), jnp.float32(0.1),
                      g._select_view()).compile().as_text()
    pm = devtrace.phase_map(text)
    assert pm["ops_unscoped"] == 0, pm["unscoped_op_names"]
    exchange = {"exchange/root", "exchange/hist"}
    missing = {p for p in phases.ROUND_PHASES if p not in pm["ops_by_phase"]}
    assert missing == (exchange if learner == "serial" else set())
    assert pm["ops_scoped"] > 500
    if learner == "data":
        # the root's scales, sums, rows and histogram, and a split's
        # histogram (the compiler may combine some of the root's)
        reduces = [n for n, rec in devtrace.parse_hlo(text)[
            "instructions"].items() if rec["opcode"] == "all-reduce"]
        assert 2 <= len(reduces) <= 5
        assert {pm["phases"][n] for n in reduces} == exchange


# -- the window itself, on the CPU ------------------------------------------

def _data(n=400, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    return X, (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)


def test_span_is_on_the_profilers_clock(tmp_path):
    """(d) ``obs.span`` inside a profiler window leaves an
    ``lgbt:GBDT::iteration`` event on a host plane; its other sinks (the
    histogram series, no timetag account while that mode is off) are as
    before."""
    import jax
    from lightgbm_tpu import obs
    from lightgbm_tpu.utils import timetag
    timetag.reset()
    before = (obs.get_histogram("phase_seconds_gbdt_iteration")
              or {"count": 0})["count"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("GBDT::iteration") as handle:
            handle.sync(np.zeros(2))
            with obs.span("GBDT::host_tree"):
                pass
    finally:
        jax.profiler.stop_trace()
    host = devtrace.load_planes(devtrace.find_xplane(str(tmp_path)))["host"]
    names = [n for n, _, _ in host]
    assert "lgbt:GBDT::iteration" in names
    assert "lgbt:GBDT::host_tree" in names
    outer = next(e for e in host if e[0] == "lgbt:GBDT::iteration")
    inner = next(e for e in host if e[0] == "lgbt:GBDT::host_tree")
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    after = obs.get_histogram("phase_seconds_gbdt_iteration")["count"]
    assert after == before + 1
    assert timetag.get_timings() == {}
    assert handle.trace is None            # the causal tracer is not armed


def test_lambdarank_round_sits_under_the_rank_phases(no_persistent_cache,
                                                     monkeypatch):
    """The round of ``objective=lambdarank`` in the TPU's form (the slab
    frame and the ``rank_lambda`` kernel, interpreted here): every
    operation under a declared phase, ``gradients/rank_slab`` and
    ``gradients/rank_lambda`` among them, as PERF.md section 5 reads the
    new cell's window."""
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.objective import LambdarankNDCG
    init = LambdarankNDCG.__init__

    def with_kernel(self, config):
        init(self, config)
        self.use_kernel = True
    monkeypatch.setattr(LambdarankNDCG, "__init__", with_kernel)
    rng = np.random.RandomState(0)
    sizes = [1, 40, 130, 200, 29, 300]
    X = rng.normal(size=(sum(sizes), 6))
    y = rng.randint(0, 5, size=len(X)).astype(np.float64)
    g = lgb.Booster(params={"objective": "lambdarank", "num_leaves": 7,
                            "verbose": -1, "compile_cache_dir": "off"},
                    train_set=lgb.Dataset(X, label=y, group=sizes))._booster
    step = g._make_train_step()
    text = step.lower(g.train_data.score, g._feature_masks_all(),
                      g._bagging_mask(0), jnp.float32(0.1),
                      g._select_view()).compile().as_text()
    pm = devtrace.phase_map(text)
    assert pm["ops_unscoped"] == 0, pm["unscoped_op_names"]
    assert set(phases.RANK_PHASES) <= set(pm["ops_by_phase"])
    assert phases.leaf_phase(
        "jit(step_fn)/gradients/gradients/rank_lambda/pallas_call") \
        == "gradients/rank_lambda"


def test_trace_window_writes_phase_maps_and_device_phases(
        tmp_path, no_persistent_cache):
    """(e) a ``trace_dir`` window over a 6-round CPU train: the armed
    capture makes ``train_step``'s compile export its phase map, the
    window's close writes ``device_phases.json`` (no device plane on the
    CPU: values unchecked), and the events record of the window's last
    round carries the same dict."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import compile_ledger
    trace_dir = tmp_path / "trace"
    events = str(tmp_path / "events.jsonl")
    X, y = _data(500, 7, seed=3)        # a shape no other test compiles
    compile_ledger.reset()
    lgb.train({"objective": "binary", "num_leaves": 5, "verbose": -1,
               "trace_dir": str(trace_dir), "trace_start_iter": 1,
               "trace_num_iters": 4, "compile_cache_dir": "off"},
              lgb.Dataset(X, label=y),
              num_boost_round=6, events_file=events)
    with open(trace_dir / "phase_map.train_step.json") as fh:
        pm = json.load(fh)
    assert pm["program"] == "train_step" and pm["module"] == "jit_step_fn"
    assert pm["ops_unscoped"] == 0 and pm["ops_scoped"] > 0
    assert set(pm["phases"].values()) <= phases.DEVICE_PHASES
    entry = [e for e in compile_ledger.events()
             if e["program"] == "train_step"][-1]
    assert entry["ops_scoped"] == pm["ops_scoped"]
    assert entry["ops_unscoped"] == 0 and entry["phase_map_s"] >= 0
    assert entry["stale_phases"] == [] and entry["cache_hit"] is False
    with open(trace_dir / devtrace.REPORT_FILE) as fh:
        rep = json.load(fh)
    assert rep["rounds"] == 4 and rep["programs"] == ["train_step"]
    assert rep["host_spans"] >= 4          # lgbt:GBDT::iteration per round
    recs = obs.read_events(events)
    carried = [r for r in recs if "device_phases" in r]
    assert [r["iter"] for r in carried] == [4]
    assert carried[0]["device_phases"]["rounds"] == 4
    assert obs.trace.armed() is None       # the window closed: disarmed


def test_without_trace_dir_nothing_is_parsed(tmp_path, monkeypatch):
    """... and a run without ``trace_dir`` lowers nothing twice and parses
    nothing: no capture is armed, the ledger entry has no ``ops_scoped``,
    ``phase_map`` is never called."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import compile_ledger
    called = []
    monkeypatch.setattr(devtrace, "phase_map",
                        lambda text: called.append(1) or {})
    X, y = _data(500, 9, seed=4)        # a shape no other test compiles
    compile_ledger.reset()
    lgb.train({"objective": "binary", "num_leaves": 5, "verbose": -1},
              lgb.Dataset(X, label=y), num_boost_round=3)
    assert obs.trace.armed() is None
    entries = [e for e in compile_ledger.events()
               if e["program"] == "train_step"]
    assert entries and all("ops_scoped" not in e for e in entries)
    assert all("cache_hit" in e for e in entries)
    assert called == []


def test_stale_phases_names_what_a_cache_served_executable_lacks():
    """The persistent cache's key leaves metadata out: an executable
    compiled under older scopes is served with THEIR op_names.  The
    phases on one side only give it away."""
    lowered = ('#loc1 = loc("jit(step_fn)/gradients/mul"(#loc0))\n'
               '#loc2 = loc("split/sort/jit(sort)"(#loc0))\n')
    fresh = ('%a = f32[] multiply(%p, %p), metadata={op_name="jit(step_fn)'
             '/gradients/mul"}\n%s = f32[8] sort(%a), metadata={op_name='
             '"jit(step_fn)/while/body/split/sort/jit(sort)/sort"}\n')
    stale = fresh.replace("split/sort/jit(sort)", "split/jit(sort)")
    assert devtrace.stale_phases(fresh, lowered) == []
    assert devtrace.stale_phases(stale, lowered) == ["split", "split/sort"]
