"""Metrics pipeline (lightgbm_tpu/obs/): histogram metric semantics,
Prometheus text exposition + minimal parser, the standalone training
/metrics listener (scraped mid-flight), serve-server /metrics + full
/stats, span timers, snapshot/resume histogram round-trips, registry
concurrency under a live scraper, the obs-report CLI, and the
bench-regression gate tool."""

import json
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import prom
from lightgbm_tpu.obs.metrics_server import MetricsServer
from lightgbm_tpu.utils import timetag


def _data(n=400, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode("utf-8"), resp.headers.get("Content-Type")


def _assert_valid_histograms(text: str):
    """Parse an exposition; for every histogram family and every LABEL
    SET within it (the fleet's ``model=`` dimension renders labeled and
    unlabeled series in one family) assert cumulative buckets are
    monotone and the +Inf bucket equals _count.  Returns the parsed
    structure and the set of histogram family names."""
    parsed = prom.parse_text(text)
    families = {name for name, t in parsed["types"].items()
                if t == "histogram"}
    assert families, "exposition carries no histogram"
    for fam in families:
        # one label group = the exact non-le label set of a _count line
        groups = [labels for name, labels, _ in parsed["samples"]
                  if name == fam + "_count"]
        assert groups, f"{fam}: no _count sample"
        for want in groups:
            buckets, cnt, total = [], None, None
            for name, labels, value in parsed["samples"]:
                nle = {k: v for k, v in labels.items() if k != "le"}
                if nle != want:
                    continue
                if name == fam + "_bucket":
                    buckets.append((prom._parse_value(labels["le"]), value))
                elif name == fam + "_count":
                    cnt = value
                elif name == fam + "_sum":
                    total = value
            assert cnt is not None and total is not None, (fam, want)
            buckets.sort(key=lambda t: t[0])
            values = [v for _, v in buckets]
            assert values == sorted(values), \
                f"{fam}{want}: non-monotone buckets"
            assert buckets[-1][0] == float("inf"), (fam, want)
            assert buckets[-1][1] == cnt, \
                f"{fam}{want}: +Inf bucket != _count"
    return parsed, families


# ---------------------------------------------------------------------------
# histogram metric type
# ---------------------------------------------------------------------------

def test_histogram_observe_buckets_sum_count():
    r = obs.Registry()
    r.observe("lat", 0.5, buckets=[1.0, 2.0, 4.0])
    r.observe("lat", 1.0)            # == bound -> le-inclusive bucket
    r.observe("lat", 3.0)
    r.observe("lat", 99.0)           # overflow
    h = r.get_histogram("lat")
    assert h["buckets"] == [1.0, 2.0, 4.0]
    assert h["counts"] == [2, 0, 1, 1]
    assert h["count"] == 4
    assert h["sum"] == pytest.approx(103.5)
    # bucket layout is fixed by the first observe
    r.observe("lat", 0.1, buckets=[7.0])
    assert r.get_histogram("lat")["buckets"] == [1.0, 2.0, 4.0]
    assert r.get_histogram("missing") is None


def test_histogram_merge_identical_and_rebucket():
    a = obs.Registry()
    b = obs.Registry()
    for v in (0.5, 1.5, 9.0):
        a.observe("h", v, buckets=[1.0, 2.0])
        b.observe("h", v, buckets=[1.0, 2.0])
    # fold-worker style: identical layouts add element-wise
    a.merge(b.snapshot())
    h = a.get_histogram("h")
    assert h["counts"] == [2, 2, 2] and h["count"] == 6
    assert h["sum"] == pytest.approx(22.0)
    # differing layouts re-bucket at the incoming upper edge (never down)
    c = obs.Registry()
    c.observe("h", 0.2, buckets=[0.25, 1.0, 2.0, 50.0])
    c.merge(a.snapshot())
    hc = c.get_histogram("h")
    assert hc["count"] == 7
    # le-1.0 pair -> le-1.0, le-2.0 pair -> le-2.0; the incoming +Inf
    # overflow pair has no upper edge to re-bucket by, so it stays +Inf
    assert hc["counts"] == [1, 2, 2, 0, 2]
    assert hc["sum"] == pytest.approx(22.2)
    # a histogram absent locally is copied wholesale
    d = obs.Registry()
    d.merge(a.snapshot())
    assert d.get_histogram("h") == a.get_histogram("h")


def test_histogram_restore_overwrites_bit_exact():
    a = obs.Registry()
    for v in (0.001, 0.7, 1e-9, 123.456):
        a.observe("h", v)
    snap = a.snapshot()
    b = obs.Registry()
    b.observe("h", 5.0)              # pre-existing state is replaced
    b.restore(snap)
    assert b.get_histogram("h") == a.get_histogram("h")
    # float sum restores bit-exactly, not approximately
    assert b.get_histogram("h")["sum"] == a.get_histogram("h")["sum"]


def test_histogram_quantile_interpolation():
    r = obs.Registry()
    for v in [0.1] * 50 + [0.9] * 50:
        r.observe("q", v, buckets=[0.25, 1.0])
    h = r.get_histogram("q")
    # p25 inside the first bucket, p75 inside the second
    assert 0.0 < obs.histogram_quantile(h, 0.25) <= 0.25
    assert 0.25 < obs.histogram_quantile(h, 0.75) <= 1.0
    assert obs.histogram_quantile(None, 0.5) is None
    assert obs.histogram_quantile({"count": 0}, 0.5) is None


def test_snapshot_resume_preserves_histogram_state(tmp_path):
    """Crash-safe resume (lightgbm_tpu/snapshot.py) restores the FULL
    registry: counters, gauges, and histogram bucket state bit-exactly."""
    from lightgbm_tpu.snapshot import (load_latest_snapshot,
                                       restore_booster_state)
    X, y = _data(300, 4, seed=7)
    ds = lgb.Dataset(X, label=y)
    booster = lgb.train({"objective": "binary", "num_leaves": 4,
                         "verbose": -1}, ds, num_boost_round=2)
    obs.observe("custom_series", 0.125)
    obs.observe("custom_series", 7.25)
    before_hist = obs.get_histogram("custom_series")
    before_iters = obs.get_counter("iterations")
    assert before_hist["count"] == 2
    booster.save_snapshot(str(tmp_path))

    obs.reset()
    assert obs.get_histogram("custom_series") is None
    # fresh same-config booster, as a crash-restarted process would build
    booster2 = lgb.Booster(params={"objective": "binary", "num_leaves": 4,
                                   "verbose": -1}, train_set=ds)
    _, state = load_latest_snapshot(str(tmp_path))
    restore_booster_state(booster2, state)
    assert obs.get_histogram("custom_series") == before_hist
    assert obs.get_histogram("custom_series")["sum"] == before_hist["sum"]
    assert obs.get_counter("iterations") == before_iters


# ---------------------------------------------------------------------------
# Prometheus exposition + parser
# ---------------------------------------------------------------------------

def test_prom_render_and_parse_roundtrip():
    r = obs.Registry()
    r.inc("iterations", 3)
    r.set_gauge("hbm_budget_bytes", 1024)
    r.set_gauge("non_numeric", "skipped")
    for v in (0.01, 0.2, 500.0):
        r.observe("phase_seconds_gbdt_tree", v)
    text = prom.render(r.snapshot(), labels={"rank": "2"})
    parsed, fams = _assert_valid_histograms(text)
    assert "lightgbm_tpu_phase_seconds_gbdt_tree" in fams
    samples = {(n, tuple(sorted(lab.items()))): v
               for n, lab, v in parsed["samples"]}
    assert samples[("lightgbm_tpu_iterations", (("rank", "2"),))] == 3
    assert parsed["types"]["lightgbm_tpu_iterations"] == "counter"
    assert parsed["types"]["lightgbm_tpu_hbm_budget_bytes"] == "gauge"
    # every sample carries the rank label
    assert all(lab.get("rank") == "2" for _, lab, _ in parsed["samples"])
    # the non-numeric gauge was dropped, not rendered invalidly
    assert "non_numeric" not in text


def test_prom_metric_name_sanitization():
    assert prom.metric_name("GBDT::tree") == "lightgbm_tpu_gbdt_tree"
    assert prom.metric_name("serve-latency.p50") == \
        "lightgbm_tpu_serve_latency_p50"
    assert prom.metric_name("9lives").startswith("lightgbm_tpu__9")


def test_prom_parser_rejects_garbage():
    with pytest.raises(ValueError):
        prom.parse_text("this is not { valid\n")
    with pytest.raises(ValueError):
        prom.parse_text('m{le="0.1} 3\n')


def test_prom_label_escape_roundtrip():
    """render -> parse is an identity on label values, including a
    literal backslash before 'n' or a quote (single-pass unescape)."""
    for value in ('a\\nb', 'a\nb', 'back\\slash', 'quo"te', '\\\\'):
        r = obs.Registry()
        r.inc("c")
        text = prom.render(r.snapshot(), labels={"tag": value})
        parsed = prom.parse_text(text)
        got = [lab["tag"] for n, lab, _ in parsed["samples"]
               if n == "lightgbm_tpu_c"]
        assert got == [value], (value, got)


# ---------------------------------------------------------------------------
# standalone metrics listener
# ---------------------------------------------------------------------------

def test_metrics_server_scrape_and_shutdown():
    obs.observe("phase_seconds_gbdt_tree", 0.05)
    srv = MetricsServer(port=0).start()
    try:
        host, port = srv.address
        text, ctype = _get(f"http://{host}:{port}/metrics")
        assert "version=0.0.4" in ctype
        _assert_valid_histograms(text)
        health, _ = _get(f"http://{host}:{port}/healthz")
        assert json.loads(health)["status"] == "ok"
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://{host}:{port}/nope")
        assert err.value.code == 404
    finally:
        srv.stop()
    srv.stop()                                   # idempotent
    with pytest.raises(Exception):
        _get(f"http://{host}:{port}/healthz", timeout=1)


def test_training_scrapeable_midflight():
    """engine.train(metrics_port=...) serves live Prometheus exposition
    WHILE the boosting loop runs, and tears the listener down on exit."""
    X, y = _data(300, 4, seed=3)
    ds = lgb.Dataset(X, label=y)
    port = _free_port()
    seen = {}

    def scrape_midflight(env):
        if env.iteration >= 1 and "text" not in seen:
            seen["text"], seen["ctype"] = _get(
                f"http://127.0.0.1:{port}/metrics")
    scrape_midflight.order = 99

    lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
               "metrics_port": port}, ds, num_boost_round=4,
              callbacks=[scrape_midflight])
    assert "text" in seen, "mid-training scrape never ran"
    assert "version=0.0.4" in seen["ctype"]
    parsed, fams = _assert_valid_histograms(seen["text"])
    # the migrated iteration wall-time bookkeeping is a live histogram
    assert "lightgbm_tpu_phase_seconds_gbdt_iteration" in fams
    h = prom.histogram_series(parsed,
                              "lightgbm_tpu_phase_seconds_gbdt_iteration")
    assert h["count"] >= 1
    counters = {n: v for n, lab, v in parsed["samples"]
                if n == "lightgbm_tpu_iterations"}
    assert counters["lightgbm_tpu_iterations"] >= 1
    # listener is gone once train() returns
    with pytest.raises(Exception):
        _get(f"http://127.0.0.1:{port}/healthz", timeout=1)


def test_metrics_env_var_and_bind_failure(monkeypatch):
    from lightgbm_tpu.obs import metrics_server as ms
    monkeypatch.setenv(ms.ENV_PORT, "not-a-port")
    assert ms.resolve_port({"metrics_port": 0}) == 0
    monkeypatch.setenv(ms.ENV_PORT, "12345")
    assert ms.resolve_port({"metrics_port": 0}) == 12345
    # an EXPLICIT env 0 disables, beating a param that asks for a port
    monkeypatch.setenv(ms.ENV_PORT, "0")
    assert ms.resolve_port({"metrics_port": 7}) == 0
    monkeypatch.delenv(ms.ENV_PORT)
    assert ms.resolve_port({"metrics_port": "7"}) == 7
    # a taken port degrades to None + warning, never an exception
    srv = MetricsServer(port=0).start()
    try:
        assert ms.maybe_start({"metrics_port": srv.address[1]}) is None
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# serve server: /metrics + full /stats
# ---------------------------------------------------------------------------

@pytest.mark.serve
def test_serve_metrics_and_full_stats():
    from lightgbm_tpu.serve.server import PredictServer
    X, y = _data(300, 4, seed=5)
    booster = lgb.train({"objective": "binary", "num_leaves": 4,
                         "verbose": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=2)
    cf = booster.compile(buckets=[16, 64])
    cf.warmup(max_bucket=64)
    srv = PredictServer(cf, port=0, max_batch=64, max_delay_ms=1.0).start()
    try:
        host, port = srv.address
        base = f"http://{host}:{port}"
        body = json.dumps({"rows": X[:5].tolist()}).encode()
        for _ in range(3):
            req = urllib.request.Request(
                base + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=30).read()

        text, ctype = _get(base + "/metrics")
        assert "version=0.0.4" in ctype
        parsed, fams = _assert_valid_histograms(text)
        assert "lightgbm_tpu_serve_latency_seconds" in fams
        h = prom.histogram_series(parsed,
                                  "lightgbm_tpu_serve_latency_seconds")
        assert h["count"] >= 3

        # /stats is the FULL registry snapshot: counters + gauges +
        # histogram summaries — plus the fleet topology (round 8) — so
        # new metric names can never drift out
        stats = json.loads(_get(base + "/stats")[0])
        assert set(stats) == {"counters", "gauges", "histograms", "fleet",
                              "lifecycle", "drift"}
        assert stats["drift"] == {"enabled": False}  # off is the default
        assert stats["fleet"]["generation"] >= 1
        assert stats["fleet"]["replicas"], "fleet topology missing"
        assert stats["counters"]["serve_requests"] >= 3
        # non-serve counters appear too (full snapshot, not hand-picked)
        assert "iterations" in stats["counters"]
        lat = stats["histograms"]["serve_latency_seconds"]
        assert lat["count"] >= 3 and lat["sum"] > 0
        assert lat["p50"] is not None and lat["p99"] is not None
        # old gauge names survive as derived values
        assert stats["gauges"]["serve_latency_p50_ms"] > 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# concurrency: writers hammering one histogram under a live scraper
# ---------------------------------------------------------------------------

def test_histogram_concurrency_under_scraper():
    reg = obs.Registry()
    n_threads, per_thread = 8, 2000
    # seed the series so the scraper always sees >= 1 histogram, even if
    # it wins the race to the first render
    reg.observe("hammered_seconds", 0.5)
    stop = threading.Event()
    scrape_errors = []

    def writer(seed):
        rng = np.random.RandomState(seed)
        for _ in range(per_thread):
            reg.observe("hammered_seconds", float(rng.uniform(0, 2.0)))
            reg.inc("hammered_total")

    def scraper():
        while not stop.is_set():
            try:
                _assert_valid_histograms(prom.render(reg.snapshot()))
            except AssertionError as exc:      # pragma: no cover - failure
                scrape_errors.append(exc)
                return

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    scr = threading.Thread(target=scraper)
    scr.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    scr.join()
    assert not scrape_errors
    h = reg.get_histogram("hammered_seconds")
    assert h["count"] == n_threads * per_thread + 1
    assert sum(h["counts"]) == n_threads * per_thread + 1
    assert reg.get_counter("hammered_total") == n_threads * per_thread
    _assert_valid_histograms(prom.render(reg.snapshot()))


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_series_mapping():
    assert obs.span_series("GBDT::tree") == "phase_seconds_gbdt_tree"
    assert obs.span_series("Serve::batch") == "phase_seconds_serve_batch"
    assert obs.span_series("free form!") == "phase_seconds_free_form"
    # every declared phase resolves (the lint enforces this too)
    for name in obs.HOST_PHASES | obs.DEVICE_PHASES:
        assert obs.span_series(name).startswith("phase_seconds_")


def test_span_and_timed_feed_histograms():
    reg = obs.Registry()
    with obs.span("GBDT::metric", reg=reg):
        pass
    h = reg.get_histogram("phase_seconds_gbdt_metric")
    assert h["count"] == 1 and h["sum"] >= 0.0

    calls = []

    @obs.timed("Report::render")
    def work(x):
        calls.append(x)
        return x * 2

    before = (obs.get_histogram("phase_seconds_report_render")
              or {"count": 0})["count"]
    assert work(21) == 42
    h2 = obs.get_histogram("phase_seconds_report_render")
    assert h2["count"] == before + 1 and calls == [21]


def test_span_feeds_timetag_when_serializing():
    timetag.enable(True)
    timetag.reset()
    try:
        with obs.span("GBDT::metric"):
            pass
        assert "GBDT::metric" in timetag.get_timings()
        # one account, two sinks: the same span lands in the histogram
        # series whether or not the serializing mode is on
        before = obs.get_histogram("phase_seconds_gbdt_metric")["count"]
        with obs.span("GBDT::metric"):
            pass
        after = obs.get_histogram("phase_seconds_gbdt_metric")["count"]
        assert after == before + 1
    finally:
        timetag.enable(False)
        timetag.reset()


# ---------------------------------------------------------------------------
# obs-report
# ---------------------------------------------------------------------------

def test_obs_report_real_training_run(tmp_path, capsys):
    from lightgbm_tpu.obs import report
    X, y = _data(400, 5, seed=11)
    path = str(tmp_path / "events.jsonl")
    ds = lgb.Dataset(X, label=y)
    vs = ds.create_valid(X[:100], y[:100])
    timetag.enable(True)
    timetag.reset()
    try:
        lgb.train({"objective": "binary", "num_leaves": 6, "verbose": -1,
                   "metric": "auc"}, ds, num_boost_round=4,
                  valid_sets=[vs], events_file=path)
    finally:
        timetag.enable(False)
        timetag.reset()

    rep = report.summarize([path], top_k=2)
    events = obs.read_events(path)
    # reproduces the run's totals from the stream alone
    assert rep["iterations"] == 4 and rep["events"] == len(events)
    assert rep["wall_s_total"] == pytest.approx(
        sum(e["wall_s"] for e in events), rel=1e-6)
    want_tree = sum(e["phases"].get("GBDT::tree", 0.0) for e in events)
    assert rep["phase_seconds"]["GBDT::tree"] == pytest.approx(
        want_tree, abs=1e-5)
    assert len(rep["slowest"]) == 2
    assert rep["slowest"][0]["wall_s"] >= rep["slowest"][1]["wall_s"]
    auc = rep["eval"]["valid_0"]["auc"]
    assert auc["n"] == 4 and 0.0 <= auc["last"] <= 1.0
    assert rep["incidents"]["nan"] == []

    # CLI entry: both formats, through the real __main__ router
    from lightgbm_tpu import cli
    assert cli.main(["obs-report", path, "--format=json"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out)["iterations"] == 4
    assert cli.main(["obs-report", path, "--format=table", "--top=3"]) == 0
    out = capsys.readouterr().out
    assert "per-phase wall time" in out and "eval trajectory" in out
    assert cli.main(["obs-report"]) == 2
    assert cli.main(["obs-report", path, "--format=yaml"]) == 2


def test_obs_report_comm_totals_sum_per_file(tmp_path):
    """Each event file is an independent cumulative comm account
    (per-rank / per-fold): totals are the SUM of per-file maxima, not
    the max over the concatenation."""
    from lightgbm_tpu.obs import report
    paths = []
    for rank, total in enumerate((1000, 3000)):
        p = tmp_path / f"rank{rank}.jsonl"
        with open(p, "w") as fh:
            for it, frac in enumerate((0.5, 1.0)):
                fh.write(json.dumps({
                    "iter": it, "wall_s": 0.01,
                    "comm_bytes_cum": int(total * frac),
                    "comm_calls_cum": 2 * (it + 1)}) + "\n")
        paths.append(str(p))
    rep = report.summarize(paths)
    assert rep["comm"]["bytes_cum"] == 4000       # 1000 + 3000
    assert rep["comm"]["calls_cum"] == 8          # 4 + 4


def test_obs_report_torn_events_file(tmp_path, capsys):
    """A torn final JSONL line (crashed writer) exits 1 with a one-line
    error, not a JSONDecodeError traceback."""
    from lightgbm_tpu import cli
    p = tmp_path / "torn.jsonl"
    p.write_text('{"iter": 0, "wall_s": 0.1}\n{"iter": 1, "wal')
    assert cli.main(["obs-report", str(p)]) == 1
    err = capsys.readouterr().err
    assert err.startswith("obs-report:") and "Traceback" not in err


@pytest.mark.faults
def test_obs_report_nan_incidents(tmp_path):
    """A real nan_policy=skip_tree run's poisoned round shows up in the
    report's incident list (acceptance: obs-report reproduces nan_policy
    incidents recorded by the fault-tolerance layer)."""
    from lightgbm_tpu.obs import report
    from lightgbm_tpu.testing import faults
    X, y = _data(300, 4, seed=13)
    path = str(tmp_path / "events.jsonl")
    ds = lgb.Dataset(X, label=y)
    booster = lgb.Booster(params={"objective": "binary", "num_leaves": 4,
                                  "verbose": -1,
                                  "nan_policy": "skip_tree"}, train_set=ds)
    rec = obs.EventRecorder(path)
    booster.set_event_recorder(rec)
    with faults.poison_gradients(booster, at_iteration=1):
        for _ in range(4):
            booster.update()
    booster.num_trees()                  # flush the pipelined iteration
    rec.close()
    booster.set_event_recorder(None)

    rep = report.summarize([path])
    assert rep["incidents"]["nan"] == [
        {"iter": 1, "what": "gradients/hessians", "policy": "skip_tree"}]
    # 4 updates, one dropped+retried at the same index -> 3 committed
    assert rep["iterations"] == 3
    table = report.render_table(rep)
    assert "non-finite gradients/hessians" in table


# ---------------------------------------------------------------------------
# bench regression gate
# ---------------------------------------------------------------------------

def test_bench_regress_gate(tmp_path, capsys):
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "bench_regress", pathlib.Path(__file__).resolve().parent.parent
        / "tools" / "bench_regress.py")
    br = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(br)

    def write(name, obj):
        p = tmp_path / name
        p.write_text(json.dumps(obj))
        return str(p)

    base = write("base.json", {"metric": "m", "value": 10.0,
                               "unit": "iters/sec"})
    # driver-envelope form (BENCH_rNN.json): result under "parsed"
    ok = write("ok.json", {"n": 5, "rc": 0,
                           "parsed": {"metric": "m", "value": 9.7,
                                      "unit": "iters/sec"}})
    bad = write("bad.json", {"metric": "m", "value": 9.0,
                             "unit": "iters/sec"})
    better = write("better.json", {"metric": "m", "value": 12.0,
                                   "unit": "iters/sec"})
    other = write("other.json", {"metric": "other", "value": 9.9})

    assert br.main(["--baseline", base, "--candidate", ok,
                    "--threshold", "5"]) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["ok"] and verdict["delta_pct"] == pytest.approx(-3.0)
    assert br.main(["--baseline", base, "--candidate", bad,
                    "--threshold", "5"]) == 1
    assert br.main(["--baseline", base, "--candidate", better,
                    "--threshold", "5"]) == 0
    assert br.main(["--baseline", base, "--candidate", other,
                    "--threshold", "5"]) == 2
    # tail-transcript envelope form
    tail = write("tail.json", {"tail": "noise\n" + json.dumps(
        {"metric": "m", "value": 9.9, "unit": "iters/sec"}) + "\n# done"})
    assert br.main(["--baseline", base, "--candidate", tail,
                    "--threshold", "5"]) == 0
