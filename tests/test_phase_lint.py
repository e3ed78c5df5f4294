"""tools/lint_phase_scopes.py as a tier-1 test: the host span phase
taxonomy and the device named_scope taxonomy must both match
lightgbm_tpu/obs/phases.py, so the two accounts can't silently drift."""

import importlib.util
import pathlib


def _load_lint():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "tools" / "lint_phase_scopes.py")
    spec = importlib.util.spec_from_file_location("lint_phase_scopes", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_taxonomies_in_sync():
    assert _load_lint().check() == []


def test_lint_recognizes_obs_span_sites():
    """obs.span("X") is the one entry point of a host phase; the
    timetag.scope form is gone and no longer counts."""
    lint = _load_lint()
    m = lint.SCOPE_RE.search('with obs.span("GBDT::iteration"):')
    assert m and m.group(1) == "GBDT::iteration"
    m = lint.SCOPE_RE.search('with obs.span("GBDT::tree") as tt:')
    assert m and m.group(1) == "GBDT::tree"
    assert not lint.SCOPE_RE.search('with timetag.scope("GBDT::tree"):')


def test_lint_accepts_nested_device_phase_names(tmp_path, monkeypatch):
    """The fused round's taxonomy nests with ``/`` (``split/sort``,
    ``hist/kernel``): the rule reads such names whole, in every device
    file, and still reports one that is not declared."""
    lint = _load_lint()
    m = lint.NAMED_RE.search('with jax.named_scope("split/window_read"):')
    assert m and m.group(1) == "split/window_read"
    assert {"models/gbdt.py", "ops/ordered_grow.py",
            "ops/leafhist.py"} <= set(lint.DEVICE_FILES)
    pkg = tmp_path / "lightgbm_tpu"
    (pkg / "obs").mkdir(parents=True)
    (pkg / "ops").mkdir()
    real = (pathlib.Path(lint.__file__).resolve().parent.parent
            / "lightgbm_tpu" / "obs" / "phases.py")
    (pkg / "obs" / "phases.py").write_text(real.read_text())
    (pkg / "ops" / "grow.py").write_text("")
    (pkg / "ops" / "ordered_grow.py").write_text(
        'with jax.named_scope("split/sort"):\n    pass\n'
        'with jax.named_scope("split/rogue"):\n    pass\n')
    monkeypatch.setattr(lint, "ROOT", tmp_path)
    monkeypatch.setattr(lint, "PKG", pkg)
    errors = lint.check()
    assert any("split/rogue" in e for e in errors)
    assert not any("'split/sort'" in e and "not declared" in e
                   for e in errors)


def test_lint_recognizes_trace_span_sites():
    """The causal-tracing call forms (obs/tracing.py) count as phase
    users too: a span name invented at a tracing call site must fail
    the lint instead of minting an unregistered series."""
    lint = _load_lint()
    m = lint.SCOPE_RE.search('with obs.trace_span("Serve::request"):')
    assert m and m.group(1) == "Serve::request"
    m = lint.SCOPE_RE.search('obs.trace_begin("Serve::queue",')
    assert m and m.group(1) == "Serve::queue"
    m = lint.SCOPE_RE.search('with TRACER.span("GBDT::iteration"):')
    assert m and m.group(1) == "GBDT::iteration"


def test_lint_catches_undeclared_trace_span(tmp_path, monkeypatch):
    """A tracing span name outside the taxonomy is a lint error."""
    lint = _load_lint()
    pkg = tmp_path / "lightgbm_tpu"
    (pkg / "obs").mkdir(parents=True)
    (pkg / "ops").mkdir()
    real = (pathlib.Path(lint.__file__).resolve().parent.parent
            / "lightgbm_tpu" / "obs" / "phases.py")
    (pkg / "obs" / "phases.py").write_text(real.read_text())
    (pkg / "server.py").write_text(
        'with obs.trace_span("Serve::rogue"):\n    pass\n')
    (pkg / "ops" / "grow.py").write_text("")
    (pkg / "ops" / "ordered_grow.py").write_text("")
    monkeypatch.setattr(lint, "ROOT", tmp_path)
    monkeypatch.setattr(lint, "PKG", pkg)
    errors = lint.check()
    assert any("Serve::rogue" in e for e in errors)


def test_every_phase_resolves_to_unique_span_series():
    """Check 4: the phase taxonomy maps 1:1 onto valid histogram series
    names, so the metrics namespace cannot diverge from phases.py."""
    import pathlib
    import importlib.util
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "lightgbm_tpu" / "obs" / "phases.py")
    spec = importlib.util.spec_from_file_location("phases_standalone", path)
    phases = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(phases)          # no package/jax import
    lint = _load_lint()
    seen = {}
    for name in phases.HOST_PHASES | phases.DEVICE_PHASES:
        series = phases.span_series(name)
        assert lint.SERIES_RE.match(series), (name, series)
        assert series not in seen, (name, seen[series])
        seen[series] = name


def test_lint_catches_span_series_collision(tmp_path, monkeypatch):
    """Two phases aliasing onto one series name is a lint error."""
    lint = _load_lint()
    pkg = tmp_path / "lightgbm_tpu"
    (pkg / "obs").mkdir(parents=True)
    (pkg / "ops").mkdir()
    real = (pathlib.Path(lint.__file__).resolve().parent.parent
            / "lightgbm_tpu" / "obs" / "phases.py")
    # "Gbdt.tree" sanitizes to the same series as "GBDT::tree"
    (pkg / "obs" / "phases.py").write_text(
        real.read_text()
        + '\nHOST_PHASES = frozenset(HOST_PHASES | {"Gbdt.tree"})\n')
    (pkg / "ops" / "grow.py").write_text("")
    (pkg / "ops" / "ordered_grow.py").write_text("")
    monkeypatch.setattr(lint, "ROOT", tmp_path)
    monkeypatch.setattr(lint, "PKG", pkg)
    errors = lint.check()
    assert any("collide" in e and "Gbdt.tree" in e for e in errors)


def test_lint_catches_undeclared_scope(tmp_path, monkeypatch):
    """Sanity: a scope name outside the taxonomy is reported."""
    lint = _load_lint()
    pkg = tmp_path / "lightgbm_tpu"
    (pkg / "obs").mkdir(parents=True)
    (pkg / "ops").mkdir()
    real_phases = (pathlib.Path(lint.__file__).resolve().parent.parent
                   / "lightgbm_tpu" / "obs" / "phases.py")
    (pkg / "obs" / "phases.py").write_text(real_phases.read_text())
    (pkg / "models.py").write_text(
        'with obs.span("GBDT::rogue"):\n    pass\n')
    (pkg / "ops" / "grow.py").write_text(
        'with jax.named_scope("hist"):\n    pass\n'
        'with jax.named_scope("find_split"):\n    pass\n'
        'with jax.named_scope("split"):\n    pass\n')
    (pkg / "ops" / "ordered_grow.py").write_text("")
    monkeypatch.setattr(lint, "ROOT", tmp_path)
    monkeypatch.setattr(lint, "PKG", pkg)
    errors = lint.check()
    assert any("GBDT::rogue" in e for e in errors)


SETUP_PHASES = ("Setup::import", "Dataset::construct", "Booster::init",
                "GBDT::setup", "GBDT::first_round")
# the Bin::* host phases as PR 36 left them.  benchmarks/harness/kinds/
# train_sparse.py ingest_counters sums EVERY phase_seconds_bin_* series
# into the one-hot cell's bin_sparse_s, so a new phase under Bin:: moves
# an accepted metric: a new binning phase is a benchmark issue's
BIN_PHASES = {"Bin::bundle", "Bin::linear_fit", "Bin::sample",
              "Bin::find_bin", "Bin::apply", "Bin::fingerprint"}


def _load_phases():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "lightgbm_tpu" / "obs" / "phases.py")
    spec = importlib.util.spec_from_file_location("phases_standalone", path)
    phases = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(phases)
    return phases


def test_setup_phases_are_declared_and_used():
    """The set-up account's spans (ISSUE 38) are host phases like any
    other: declared, entered through ``obs.span`` at a site the lint
    sees, one series each."""
    phases = _load_phases()
    assert set(SETUP_PHASES) <= phases.HOST_PHASES
    assert _load_lint().check() == []
    lint = _load_lint()
    m = lint.SCOPE_RE.search(
        'with obs.span("Setup::import", start=_T0_PERF):')
    assert m and m.group(1) == "Setup::import"
    series = {phases.span_series(n) for n in SETUP_PHASES}
    assert len(series) == len(SETUP_PHASES)
    assert not any(s.startswith("phase_seconds_bin_") for s in series)


def test_nothing_new_starts_with_bin():
    """A span named ``Bin::setup`` would be summed into bin_sparse_s."""
    phases = _load_phases()
    under_bin = {n for n in phases.HOST_PHASES
                 if phases.span_series(n).startswith("phase_seconds_bin_")}
    assert under_bin == BIN_PHASES
    assert phases.span_series("Bin::setup").startswith(
        "phase_seconds_bin_")           # what the assertion above guards
