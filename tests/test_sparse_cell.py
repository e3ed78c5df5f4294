"""The one-hot cell's own comparison at its rehearsal size on the CPU
(PR 36): ``lightgbm_tpu.train`` on the refusing CSC matrix against the
plain sparse reference, through ``benchmarks/harness/kinds/
train_sparse.py``, ``correct_sparse.py`` and the limits of
``workloads/allstate12m-onehot-train.json``; and the planted faults,
which must read not correct.  (``benchmarks/test_control_sparse.py`` has
the rest of the faults; it is not tier-1.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)

pytestmark = pytest.mark.sparse


def test_the_cell_is_what_the_issue_names():
    import run as bench_run
    from harness import data_sparse
    cell = bench_run.load_cell("allstate12m-onehot-train")
    cfg = cell["config_file"]
    assert (cell["kind"], cell["traffic"], cell["chips"]) == \
        ("train_sparse", "train-closed", 1)
    assert cfg["num_data"] == 12184290 and cfg["num_features"] == 4228
    assert cfg["reduced"] == [] and cfg["params"]["num_leaves"] == 255
    assert "enable_bundle" not in cfg["params"]         # the defaults
    assert data_sparse.NUM_COLUMNS == 4228
    X, y = data_sparse.make(cfg["data"], 5000, 3000000001)
    assert X.shape == (5000, 4228) and X.nnz == 5000 * 30
    assert X.has_canonical_format
    with pytest.raises(MemoryError, match="GB"):
        X.toarray()
    with pytest.raises(MemoryError):
        X.todense()
    again, _ = data_sparse.make(cfg["data"], 5000, 3000000001)
    assert (X != again).nnz == 0


def test_three_rounds_pass_the_sparse_cells_own_comparison(monkeypatch):
    """255 leaves, the source's settings, 20,000 rows: ``correct`` true,
    grown leaf-ordered on the bundles, both ingest metrics read."""
    import test_control_sparse as controls
    from harness.kinds import train_sparse
    out = controls.drive(monkeypatch, controls.unbroken, seed=2147483659)
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == {
        "bin_table_faults", "bin_table_gap", "count_mismatch", "value_gap",
        "gain_gap", "split_gap", "loss_gap"}
    ingest = train_sparse.ingest_counters()
    assert ingest["bin_sparse_s"] > 0 and 40 <= ingest["efb_columns"] < 200


def test_a_member_decoded_one_slot_off_is_not_correct(monkeypatch):
    import test_control_sparse as controls
    out = controls.drive(monkeypatch, controls.member_decoded_one_slot_off)
    assert not out["correct"], out["compared"]
    assert out["compared"]["count_mismatch"]["value"] > 0


@pytest.mark.parametrize("mode,trees", [
    ("float32", 2), ("bfloat16", 2), ("half_rows", 1),
    ("zero_bin_dropped", 1), ("decode_off_by_one", 1), ("table_bins80", 1)])
def test_reference_in_the_programs_place(mode, trees):
    """The plain reference grown in the program's place: sound in
    float32; the bfloat16 control and every planted fault not correct
    under the cell's own limits."""
    import control_sparse
    import run as bench_run
    cell = bench_run.load_cell("allstate12m-onehot-train")
    got = control_sparse.read(
        cell["config_file"], cell["limits"], cell["rehearse"]["num_data"],
        101, [mode], n_trees=trees)[0]
    assert got["correct"] == (mode == "float32"), got
