"""The set-up account (lightgbm_tpu/obs/setup.py): from package import to
the end of a job's first round, every ``obs.span`` with its start, end and
parent, every compilation's stages under the span that was open, reduced
once at close; frozen after round 1.

One small job is trained once for the module (the account a process opens at
import belongs to whatever test trained first, so the fixture opens its own,
as the package's import does, and constructs the Dataset before ``train``,
as the benchmark's harness does)."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import report, setup

PARAMS = {"objective": "binary", "num_leaves": 7, "verbose": -1,
          "min_data_in_leaf": 20}


def _data(rows=2000, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(rows, 5))
    return X, (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """A 2,000-row job of three rounds over fresh training programs (an
    earlier test of this process may have compiled ``train_step`` at
    these shapes; the account must see a compile)."""
    from lightgbm_tpu.models import gbdt
    mp = pytest.MonkeyPatch()
    mp.setattr(gbdt, "_SHARED_JITS", {})
    path = str(tmp_path_factory.mktemp("setup") / "events.jsonl")
    X, y = _data()
    lengths = []

    def watch(env):
        lengths.append(len(acct.spans))
    try:
        acct = setup.open_account()
        ds = lgb.Dataset(X, label=y)
        ds.construct()                      # before train: inside
        lgb.train(PARAMS, ds, num_boost_round=3, events_file=path,
                  callbacks=[watch])
    finally:
        mp.undo()
    return {"account": obs.setup_account(), "open": acct, "path": path,
            "lengths": lengths}


def _ancestors(spans, i):
    out = []
    while spans[i]["parent"] >= 0:
        i = spans[i]["parent"]
        out.append(spans[i]["name"])
    return out


def _find(spans, name, program=None):
    return [i for i, sp in enumerate(spans) if sp["name"] == name
            and (program is None or sp.get("program") == program)]


def test_spans_are_parent_linked(job):
    spans = job["account"]["spans"]
    (find_bin,) = _find(spans, "Bin::find_bin")
    assert spans[spans[find_bin]["parent"]]["name"] == "Dataset::construct"
    (to_device,) = _find(spans, "Dataset::to_device")
    assert _ancestors(spans, to_device) == ["GBDT::setup", "Booster::init"]
    # the round's own span stays a root (a round is a causal-trace root)
    (it,) = _find(spans, "GBDT::iteration")
    assert spans[it]["parent"] == -1
    (first,) = _find(spans, "GBDT::first_round")
    assert spans[first]["parent"] == it
    for sp in spans:
        assert sp["end"] >= sp["start"] >= 0.0
        if sp["parent"] >= 0:
            up = spans[sp["parent"]]
            assert up["start"] - 1e-3 <= sp["start"]
            assert sp["end"] <= up["end"] + 1e-3


def test_train_step_stages_lie_under_the_first_round(job):
    spans = job["account"]["spans"]
    backend = _find(spans, "Compile::backend", "train_step")
    assert len(backend) == 1
    up = _ancestors(spans, backend[0])
    assert up[0] == "GBDT::tree" and "GBDT::first_round" in up
    assert _find(spans, "Compile::trace", "train_step")
    assert _find(spans, "Compile::lower", "train_step")
    row = job["account"]["compile"]["by_program"]["train_step"]
    assert row["count"] == 1 and row["backend_s"] > 0
    stages = row["trace_s"] + row["lower_s"] + row["backend_s"] \
        + row["other_s"]
    assert stages == pytest.approx(row["seconds"], abs=0.05, rel=0.02)


def test_construct_before_train_is_inside(job):
    acct = job["account"]
    (construct,) = _find(acct["spans"], "Dataset::construct")
    (init,) = _find(acct["spans"], "Booster::init")
    assert acct["spans"][construct]["end"] <= acct["spans"][init]["start"]
    assert acct["total_s"]["Dataset::construct"] > 0


def test_self_seconds_and_uncovered_sum_to_seconds(job):
    acct = job["account"]
    total = sum(acct["self_s"].values()) + acct["uncovered_s"]
    assert total == pytest.approx(acct["seconds"], abs=5e-3)
    assert 0.0 <= acct["uncovered_s"] < 0.10 * acct["seconds"]
    assert 0.0 <= acct["overhead_s"] < 0.05
    assert acct["device_bytes_placed"] > 2000 * 5


def test_frozen_after_the_first_round(job):
    """Rounds 2 and 3 append nothing: the list the open account held
    when round 1 returned is the list it holds now."""
    assert setup.ACTIVE is None
    n = len(job["account"]["spans"])
    assert job["lengths"] == [n, n, n]
    assert len(job["open"].spans) == n
    assert job["account"]["dropped"] == 0


def test_setup_record_comes_first_and_is_passed_over(job):
    with open(job["path"]) as fh:
        lines = [json.loads(line) for line in fh if line.strip()]
    assert list(lines[0]) == ["schema", "setup"]
    assert lines[0]["schema"] == obs.SCHEMA_VERSION == 2
    assert lines[0]["setup"] == json.loads(json.dumps(job["account"]))
    assert [rec.get("iter") for rec in lines[1:]] == [0, 1, 2]
    # the per-iteration readers pass over it
    assert [e["iter"] for e in obs.read_events(job["path"])] == [0, 1, 2]
    assert obs.read_setup(job["path"]) == lines[0]["setup"]
    rep = report.summarize([job["path"]])
    assert rep["events"] == 3 and rep["iterations"] == 3


def test_gauges_are_published(job):
    acct = job["account"]
    assert obs.get_gauge("setup_seconds") == acct["seconds"]
    assert obs.get_gauge("setup_uncovered_seconds") == acct["uncovered_s"]
    comp = acct["compile"]
    for gauge, key in (("setup_compile_trace_seconds", "trace_s"),
                       ("setup_compile_lower_seconds", "lower_s"),
                       ("setup_compile_backend_seconds", "backend_s"),
                       ("setup_cache_read_seconds", "cache_read_s"),
                       ("setup_compile_other_seconds", "other_s")):
        assert obs.get_gauge(gauge) == comp[key]
    for gauge, name in (("setup_first_round_seconds", "GBDT::first_round"),
                        ("setup_dataset_construct_seconds",
                         "Dataset::construct"),
                        ("setup_booster_init_seconds", "Booster::init")):
        assert obs.get_gauge(gauge) == acct["total_s"][name] > 0


def test_obs_report_setup_prints_the_account(job, capsys):
    assert report.main(["--setup", job["path"]]) == 0
    out = capsys.readouterr().out
    assert "set-up account:" in out and "uncovered" in out
    tree = out.split("compilations in the ledger")[0].splitlines()
    # a child is indented under its parent
    assert any(line.endswith("  Dataset::construct") for line in tree)
    assert any(line.endswith("    Bin::find_bin") for line in tree)
    assert any("Compile::backend [train_step]" in line for line in tree)
    assert "train_step" in out.split("compilations in the ledger")[1]
    assert "compilations outside the ledger:" in out
    assert report.main(["--setup", "--format=json", job["path"]]) == 0
    assert json.loads(capsys.readouterr().out)["seconds"] == \
        job["account"]["seconds"]


def test_obs_report_setup_without_a_record(tmp_path, capsys):
    path = tmp_path / "old.jsonl"
    path.write_text(json.dumps({"schema": 1, "iter": 0, "wall_s": 0.1})
                    + "\n")
    assert report.main(["--setup", str(path)]) == 1
    assert "no set-up record" in capsys.readouterr().err


def test_second_train_replaces_the_account(job):
    first = job["account"]
    X, y = _data(seed=12)
    lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=2)
    second = obs.setup_account()
    assert second is not first
    assert second["origin_wall"] > first["origin_wall"]
    # opened at engine.train's entry: the Dataset is constructed inside
    # Booster::init, and nothing of the first job is carried over
    (construct,) = _find(second["spans"], "Dataset::construct")
    assert _ancestors(second["spans"], construct) == ["Booster::init"]
    assert len(_find(second["spans"], "GBDT::first_round")) == 1
    assert "Setup::import" not in second["self_s"]
    assert setup.ACTIVE is None


def test_span_cap_counts_dropped():
    acct = setup.Account()
    with_parent = acct.enter("Dataset::construct", 1.0)
    for k in range(setup.MAX_SPANS + 10):
        acct.exit(acct.enter("Bin::apply", 2.0 + k), 2.5 + k)
    assert len(acct.spans) == setup.MAX_SPANS
    assert acct.dropped == 11
    # a span past the cap is counted, and its end is not written anywhere
    idx = acct.enter("Bin::apply", 9.0)
    assert idx == -1
    acct.exit(idx, 9.5)
    acct.exit(with_parent, 1e6)
    out = acct.reduce(acct.t0_perf + 10.0)
    assert out["dropped"] == 12 and len(out["spans"]) == setup.MAX_SPANS
    assert {sp["parent"] for sp in out["spans"][1:]} == {0}


def test_span_without_an_account_only_lands_its_histogram():
    assert setup.ACTIVE is None
    before = (obs.get_histogram("phase_seconds_gbdt_metric") or {}).get(
        "count", 0)
    with obs.span("GBDT::metric"):
        pass
    after = obs.get_histogram("phase_seconds_gbdt_metric")["count"]
    assert after == before + 1 and setup.ACTIVE is None


def test_import_span_counts_from_the_given_start():
    import time
    acct = setup.open_account()
    try:
        with obs.span("Setup::import", start=acct.t0_perf - 2.0):
            pass
        time.sleep(0.01)
    finally:
        out = setup.close()
    (sp,) = out["spans"]
    assert sp["name"] == "Setup::import" and sp["start"] == -2.0
    assert 2.0 <= out["total_s"]["Setup::import"] < 2.5
    assert setup.close() is None            # nothing open: nothing to do
