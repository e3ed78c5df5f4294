"""Compile ledger (lightgbm_tpu/obs/compile_ledger.py): instrumented
jits count compiles exactly — cache hits record nothing, shape misses
record one event with program name, abstract shapes, and seconds — the
events feed the registry (compile_count / compile_seconds, rendered at
/metrics), the JSONL sink, and the obs-report --compile section.

Process-global state (registry + in-memory ledger) is asserted by DELTA
so this file composes with the rest of the tier-1 run.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import compile_ledger


@pytest.fixture
def fresh_train_programs(monkeypatch):
    """Order-independence for the end-to-end training test: round 7
    made ``train_step``/``pack_words`` PROCESS-WIDE shared programs
    (models/gbdt.py ``_SHARED_JITS`` + module-level jits), so any
    earlier test that trained over the same shapes leaves them warm and
    a later training run legitimately records ZERO new compiles —
    which is exactly what this file must not depend on.  Swap in an
    empty shared-jit registry and fresh module-level pack jits for the
    duration, so the test observes a cold process no matter what ran
    before it (the originals — and their warm executable caches — are
    restored afterwards)."""
    from lightgbm_tpu.models import gbdt

    monkeypatch.setattr(gbdt, "_SHARED_JITS", {})
    # re-jitting the SAME function object would hit jax's
    # function-identity executable cache and still record nothing; a
    # fresh closure breaks the identity so the compile really happens
    from lightgbm_tpu.ops import ordered_grow
    raw_pack_words = ordered_grow._pack_words_padded._fn.__wrapped__
    raw_pack_tree = gbdt._PACK_TREE._fn.__wrapped__

    def fresh_pack_words(rm):
        return raw_pack_words(rm)

    def fresh_pack_tree(*args, **kwargs):
        return raw_pack_tree(*args, **kwargs)

    monkeypatch.setattr(
        ordered_grow, "_pack_words_padded",
        obs.instrumented_jit(fresh_pack_words, program="pack_words"))
    monkeypatch.setattr(
        gbdt, "_PACK_TREE",
        obs.instrumented_jit(fresh_pack_tree, program="pack_tree"))


@pytest.fixture
def ledger_file(tmp_path, monkeypatch):
    """Route the JSONL sink to a temp file for the duration of a test
    via the env var (which wins inside ``configure`` — so an
    engine.train call mid-test cannot clear it; configure is otherwise
    authoritative per run)."""
    path = tmp_path / "compile_ledger.jsonl"
    monkeypatch.setenv(compile_ledger.ENV_PATH, str(path))
    compile_ledger.configure()
    yield path
    monkeypatch.delenv(compile_ledger.ENV_PATH)
    compile_ledger.configure()             # back to in-memory only


def _deltas():
    return (obs.get_counter("compile_count"),
            (obs.get_histogram("compile_seconds") or {}).get("count", 0),
            len(compile_ledger.events()))


def test_cache_hit_vs_shape_miss_counting(ledger_file):
    c0, h0, e0 = _deltas()
    fn = obs.instrumented_jit(lambda x: x * 2 + 1, program="t_double")
    fn(jnp.ones(4))                        # compile 1
    fn(jnp.ones(4) * 3)                    # cache hit: same shape
    fn(jnp.ones(4))                        # cache hit again
    fn(jnp.ones(8))                        # compile 2: shape miss
    c1, h1, e1 = _deltas()
    assert c1 - c0 == 2
    assert h1 - h0 == 2
    assert e1 - e0 == 2
    mine = compile_ledger.events()[e0:]
    assert [e["program"] for e in mine] == ["t_double", "t_double"]
    assert mine[0]["shapes"] == "f32[4]"
    assert mine[1]["shapes"] == "f32[8]"
    assert all(e["seconds"] > 0 for e in mine)
    # per-program counter landed too
    assert obs.get_counter("compile_count_t_double") >= 2


def test_ledger_jsonl_roundtrip(ledger_file):
    fn = obs.instrumented_jit(lambda x: x - 1, program="t_file")
    fn(jnp.ones(3))
    fn(jnp.ones(5))
    evs = compile_ledger.read_ledger(str(ledger_file))
    assert [e["program"] for e in evs] == ["t_file", "t_file"]
    assert {e["shapes"] for e in evs} == {"f32[3]", "f32[5]"}
    # every line is independently parseable (append-only, flushed)
    with open(ledger_file) as fh:
        for line in fh:
            json.loads(line)


def test_static_args_and_kwargs_in_shapes():
    fn = obs.instrumented_jit(lambda x, n: x[:n].sum(), program="t_static",
                              static_argnames=("n",))
    e0 = len(compile_ledger.events())
    fn(jnp.arange(6.0), n=3)
    ev = compile_ledger.events()[e0]
    assert "f32[6]" in ev["shapes"] and "3" in ev["shapes"]


def test_nested_jit_calls_not_double_counted():
    """An instrumented jit called while another jit traces it inlines —
    it must NOT record a compile of its own."""
    inner = obs.instrumented_jit(lambda x: x * 3, program="t_inner")
    outer = obs.instrumented_jit(lambda x: inner(x) + 1, program="t_outer")
    e0 = len(compile_ledger.events())
    outer(jnp.ones(7))
    progs = [e["program"] for e in compile_ledger.events()[e0:]]
    assert progs == ["t_outer"]


def test_training_populates_ledger(ledger_file, fresh_train_programs):
    """End to end: a warmed-then-rerun training session leaves a
    populated ledger (every event has name, shapes, seconds) and re-runs
    on identical shapes add nothing (acceptance criterion).  Runs
    against fresh shared training programs so it passes in ANY tier-1
    order (an earlier training test would otherwise have pre-compiled
    the process-wide train_step/pack_words jits)."""
    rng = np.random.RandomState(3)
    X = rng.normal(size=(500, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 20}
    e0 = len(compile_ledger.events())
    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    mine = compile_ledger.events()[e0:]
    assert mine, "training compiled nothing according to the ledger"
    assert {"train_step", "pack_words"} <= {e["program"] for e in mine}
    for e in mine:
        assert e["program"] and e["shapes"] and e["seconds"] > 0
    # identical second run: the jit caches are warm per-instance only
    # for the booster-owned jits, but module-level programs (bag_mask,
    # grow via train_step closure) re-trace per closure — so assert the
    # cheap invariant: the ledger file carries exactly the in-memory
    # events appended since this test's file was installed
    disk = compile_ledger.read_ledger(str(ledger_file))
    assert [e["program"] for e in disk] == \
        [e["program"] for e in compile_ledger.events()[e0:]]


def test_counting_jit_feeds_ledger():
    """serve/batcher.py CountingJit rides the shared detection: its
    per-bucket counters AND the ledger record the same compile."""
    import jax
    from lightgbm_tpu.serve.batcher import CountingJit
    cj = CountingJit(jax.jit(lambda x: x.sum(axis=0)), "t_bucketed")
    c0 = obs.get_counter("t_bucketed_compiles")
    e0 = len(compile_ledger.events())
    cj(16, jnp.ones((16, 2)))
    cj(16, jnp.ones((16, 2)))              # warm
    cj(32, jnp.ones((32, 2)))
    assert obs.get_counter("t_bucketed_compiles") - c0 == 2
    assert obs.get_counter("t_bucketed_compiles_bucket_16") >= 1
    assert obs.get_counter("t_bucketed_compiles_bucket_32") >= 1
    progs = [e["program"] for e in compile_ledger.events()[e0:]]
    assert progs == ["t_bucketed", "t_bucketed"]


def test_compile_series_rendered_at_metrics():
    """The ledger's registry series render in the Prometheus exposition
    (what a /metrics scrape of a training run serves)."""
    from lightgbm_tpu.obs import prom
    fn = obs.instrumented_jit(lambda x: -x, program="t_prom")
    fn(jnp.ones(2))
    text = prom.render()
    assert "lightgbm_tpu_compile_count " in text
    assert "lightgbm_tpu_compile_seconds_bucket" in text
    assert "lightgbm_tpu_compile_count_t_prom" in text
    parsed = prom.parse_text(text)
    hist = prom.histogram_series(parsed, "lightgbm_tpu_compile_seconds")
    assert hist["count"] >= 1


def test_obs_report_compile_section(tmp_path):
    """obs-report --compile: totals, per-program seconds, slowest with
    shapes."""
    from lightgbm_tpu.obs.report import summarize_compile
    path = tmp_path / "ledger.jsonl"
    with open(path, "w") as fh:
        for prog, shapes, sec in (("grow_tree", "u8[28,100]", 120.5),
                                  ("grow_tree", "u8[28,200]", 60.25),
                                  ("train_gradients", "f32[1,100]", 1.5)):
            fh.write(json.dumps({"program": prog, "shapes": shapes,
                                 "seconds": sec}) + "\n")
    rep = summarize_compile(str(path), top_k=2)
    assert rep["count"] == 3
    assert rep["seconds_total"] == pytest.approx(182.25)
    assert rep["programs"]["grow_tree"]["count"] == 2
    assert rep["programs"]["grow_tree"]["seconds"] == pytest.approx(180.75)
    assert rep["slowest"][0] == {"program": "grow_tree",
                                 "shapes": "u8[28,100]", "seconds": 120.5}


# -- compile stages and unledgered compilations (ISSUE 38) ------------------

STAGES = ("trace_s", "lower_s", "backend_s", "other_s")


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compilation cache in ``tmp_path``, taking every
    program however small and fast; the process's own settings back
    afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    for n, v in zip(names, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(n, v)
    yield
    cc.reset_cache()
    for n, v in old.items():
        jax.config.update(n, v)


def _toy(x):
    return jnp.sin(x) @ x.T + jnp.where(x > 0, x, -x).sum()


def test_compile_event_splits_its_seconds_by_stage(persistent_cache):
    """Cold: trace, lowering and backend compile heard from
    ``jax.monitoring`` on the calling thread, ``other_s`` the rest; the
    same program from the persistent cache: ``cache_hit`` and
    ``cache_read_s`` inside ``backend_s``."""
    import jax
    fn = obs.instrumented_jit(_toy, program="t_stages")
    e0 = len(compile_ledger.events())
    fn(jnp.ones((32, 32)))
    fn(jnp.ones((32, 32)))                 # warm: records nothing
    (cold,) = compile_ledger.events()[e0:]
    assert cold["program"] == "t_stages" and cold["cache_hit"] is False
    for f in STAGES:
        assert cold[f] >= 0.0, f
    assert cold["trace_s"] > 0 and cold["lower_s"] > 0 \
        and cold["backend_s"] > 0
    assert sum(cold[f] for f in STAGES) == pytest.approx(
        cold["seconds"], abs=0.05, rel=0.02)
    assert cold["modules"] == 1
    assert "cache_read_s" not in cold and "saved_s" not in cold

    jax.clear_caches()
    fn(jnp.ones((32, 32)))
    warm = compile_ledger.events()[-1]
    assert warm["program"] == "t_stages" and warm["cache_hit"] is True
    assert 0.0 < warm["cache_read_s"] <= warm["backend_s"]
    assert "saved_s" in warm
    assert sum(warm[f] for f in STAGES) == pytest.approx(
        warm["seconds"], abs=0.05, rel=0.02)
    # the old fields keep their names and meaning
    for ev in (cold, warm):
        assert {"program", "shapes", "seconds", "t", "cache_hit"} <= set(ev)


def test_unledgered_compile_is_counted_and_not_recorded():
    """An un-instrumented ``jax.jit`` compiled between two instrumented
    ones: in the table and the two registry series, not in
    ``events()``."""
    import jax
    a = obs.instrumented_jit(lambda x: x * 5 - 1, program="t_before")
    b = obs.instrumented_jit(lambda x: x * 7 - 2, program="t_after")

    def t_unseen(x):
        return (x * 11).sum() - 3
    a(jnp.ones(9))
    e0 = len(compile_ledger.events())
    n0 = obs.get_counter("compile_unledgered_count")
    h0 = (obs.get_histogram("compile_unledgered_seconds")
          or {"count": 0, "sum": 0.0})
    row0 = compile_ledger.unledgered().get("jit(t_unseen)", {"count": 0})
    jax.jit(t_unseen)(jnp.ones(9))
    assert len(compile_ledger.events()) == e0
    assert obs.get_counter("compile_unledgered_count") == n0 + 1
    h1 = obs.get_histogram("compile_unledgered_seconds")
    assert h1["count"] == h0["count"] + 1 and h1["sum"] > h0["sum"]
    row = compile_ledger.unledgered()["jit(t_unseen)"]
    assert row["count"] == row0["count"] + 1
    assert row["backend_s"] > 0 and row["trace_lower_s"] > 0
    b(jnp.ones(9))
    assert [e["program"] for e in compile_ledger.events()[e0:]] == \
        ["t_after"]
    assert obs.get_counter("compile_unledgered_count") == n0 + 1


@pytest.mark.parametrize("case", ["nested_trace", "eager_in_trace",
                                  "cache_read", "siblings",
                                  "traces_while_lowering"])
def test_stage_spans_stay_a_flat_partition(case):
    """jax reports a nested stage before the one that holds it; a stage
    that arrives absorbs what started inside it, so no second counts
    twice; a cache read belongs to the backend span that ends next."""
    st = compile_ledger._Stages()
    if case == "nested_trace":
        st.add("trace", 10.2, 10.3)         # an inner jit, traced inside
        st.add("trace", 10.5, 10.6)
        st.add("trace", 10.0, 11.0)         # the outer trace ends last
        got = st.fields(1.5)
        assert got["trace_s"] == 1.0 and got["other_s"] == 0.5
    elif case == "eager_in_trace":
        for stage, s, e in (("trace", 10.1, 10.2), ("lower", 10.2, 10.3),
                            ("backend", 10.3, 10.6), ("trace", 10.0, 11.0),
                            ("lower", 11.0, 11.5), ("backend", 11.5, 13.0)):
            st.add(stage, s, e)
        got = st.fields(3.25)
        assert (got["trace_s"], got["lower_s"], got["backend_s"]) == \
            (1.0, 0.5, 1.5)
        assert got["modules"] == 1 and got["other_s"] == 0.25
    elif case == "cache_read":
        st.hits, st.saved_s = 1, 40.0
        st.add("trace", 10.0, 10.5)
        st.read_s += 0.75                   # heard inside the backend span
        st.add("backend", 10.5, 12.0)
        got = st.fields(2.0)
        assert got["backend_s"] == 1.5 and got["cache_read_s"] == 0.75
        assert got["saved_s"] == 40.0
        assert st.spans[-1] == ("backend", 10.5, 12.0, 0.75)
    elif case == "traces_while_lowering":
        # lowering a large program reports thousands of nested traces
        # AFTER the outer trace has ended (the chip's ``train_step``):
        # none of them may push the trace itself out
        st.add("trace", 10.0, 11.0)
        for k in range(3 * compile_ledger._MAX_HELD):
            st.add("trace", 11.1 + k * 1e-4, 11.1 + (k + 0.5) * 1e-4)
        st.add("lower", 11.0, 13.0)
        st.add("backend", 13.0, 14.0)
        got = st.fields(4.0)
        assert (got["trace_s"], got["lower_s"], got["backend_s"]) == \
            (1.0, 2.0, 1.0)
        # outside a call the held stages are bounded
        amb = compile_ledger._Stages(cap=8)
        for k in range(100):
            amb.add("trace", float(k), k + 0.5)
        assert len(amb.spans) == 8
    else:
        st.add("backend", 10.0, 11.0)       # two modules, one after the
        st.add("backend", 11.0, 13.0)       # other: a kernel's, the step's
        got = st.fields(3.0)
        assert got["backend_s"] == 3.0 and got["modules"] == 2
