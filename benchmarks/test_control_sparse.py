"""``test_control.py``'s twin for kind ``train_sparse``, at the sparse
cell's rehearsal size on the CPU.  Not part of the repo's tier-1 suite
(that is ``tests/``; ``tests/test_sparse_cell.py`` runs the unbroken cell
and two of the faults there); run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_control_sparse.py -q

1. The control: the plain sparse reference put in the program's place in
   bfloat16 comes out not correct under the cell's own limits; the same in
   float32 comes out correct.
2. Each planted fault comes out not correct: half of the rows left out, a
   default bin left unreconstructed, a member decoded one slot off at the
   partition, a bound table with four fifths of the bins (that one by
   ``bin_table_gap`` alone).
3. The rest of a run with the timed path broken underneath: half of the
   rows not handed to the program, the bound table made with four fifths
   of the bins, the state put back after every step, and two faults of
   the program's bundle tables (the search's slots one off; the split
   member's offset at the partition one off).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import control_sparse  # noqa: E402
import run as bench_run  # noqa: E402
from harness import device  # noqa: E402

CELL = "allstate12m-onehot-train"


def _read(mode, seed, n_trees):
    cell = bench_run.load_cell(CELL)
    return cell, control_sparse.read(
        cell["config_file"], cell["limits"], cell["rehearse"]["num_data"],
        seed, [mode], n_trees=n_trees)[0]


@pytest.mark.parametrize("seed", [101, 2147483659])
def test_control_in_lower_precision_is_not_correct(seed):
    cell = bench_run.load_cell(CELL)
    got = control_sparse.read(cell["config_file"], cell["limits"],
                              cell["rehearse"]["num_data"], seed,
                              ["float32", "bfloat16"], n_trees=2)
    by_mode = {g["mode"]: g for g in got}
    assert by_mode["float32"]["correct"], by_mode["float32"]
    assert not by_mode["bfloat16"]["correct"], by_mode["bfloat16"]


@pytest.mark.parametrize("fault", ["half_rows", "zero_bin_dropped",
                                   "decode_off_by_one"])
def test_fault_planted_in_the_reference_is_not_correct(fault):
    _, got = _read(fault, 77, 1)
    assert not got["correct"], got


def test_bound_table_of_four_fifths_of_the_bins_is_not_correct():
    cell, got = _read("table_bins80", 55, 1)
    v, limits = got["values"], cell["limits"]
    assert not got["correct"], got
    assert v["bin_table_gap"] > limits["bin_table_gap"], v
    assert all(v[k] <= limits[k] for k in v if k != "bin_table_gap"), v


def drive(monkeypatch, breaker, seed=9):
    """One rehearsal run of the cell with ``breaker`` applied to the
    program first (``tests/test_sparse_cell.py`` calls this too)."""
    import lightgbm_tpu as lgb
    breaker(monkeypatch, lgb)
    cell = bench_run.load_cell(CELL)
    args = argparse.Namespace(workload=cell["name"], seed=seed, seconds=0.5,
                              trace=0, rehearse=True, out=None)
    kind = __import__("harness.kinds." + cell["kind"], fromlist=["measure"])
    out = kind.measure(cell, args, device.rehearsal_device(), time.time())
    assert out is not None
    return out


def unbroken(monkeypatch, lgb):
    pass


def half_of_the_rows(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, **k):
        n = data.shape[0] // 2
        orig(self, data[:n], label[:n], *a, **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def table_of_four_fifths_of_the_bins(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, **k):
        params = dict(k.get("params") or {})
        params["max_bin"] = int(params["max_bin"]) * 4 // 5
        k["params"] = params
        orig(self, data, label, *a, **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def state_unchanged(monkeypatch, lgb):
    import jax.numpy as jnp
    orig = lgb.Booster.update

    def update(self, *a, **k):
        r = orig(self, *a, **k)
        td = self._booster.train_data
        td.score = jnp.zeros_like(td.score)     # the step's state, put back
        return r
    monkeypatch.setattr(lgb.Booster, "update", update)


def search_reads_the_neighbouring_slot(monkeypatch, lgb):
    """Every two-bin member's slot one too high in the tables the
    column-space search reads (the partition's are left): a member is
    credited with its neighbour's rows."""
    import numpy as np
    from lightgbm_tpu.io import bundling
    orig = bundling.BundlePlan.decode_arrays

    def decode_arrays(self, *a, **k):
        tables = orig(self, *a, **k)
        moved = np.roll(tables["slot_feat"], 1, axis=1)
        moved[:, 0] = -1
        tables["slot_feat"] = moved
        return tables
    monkeypatch.setattr(bundling.BundlePlan, "decode_arrays", decode_arrays)


def member_decoded_one_slot_off(monkeypatch, lgb):
    """Every bundle member's offset one slot too high in the tables the
    grower decodes the split column with (the search's are left)."""
    from lightgbm_tpu.io import bundling
    orig = bundling.BundlePlan.decode_arrays

    def decode_arrays(self, *a, **k):
        tables = orig(self, *a, **k)
        tables["off"] = tables["off"] + (tables["off"] > 0)
        return tables
    monkeypatch.setattr(bundling.BundlePlan, "decode_arrays", decode_arrays)


BREAKERS = [half_of_the_rows, table_of_four_fifths_of_the_bins,
            state_unchanged, search_reads_the_neighbouring_slot,
            member_decoded_one_slot_off]


@pytest.mark.parametrize("breaker", BREAKERS, ids=lambda b: b.__name__)
def test_run_with_the_timed_path_broken_is_not_correct(monkeypatch, breaker):
    out = drive(monkeypatch, breaker)
    assert not out["correct"], out["compared"]


def test_unbroken_run_is_correct(monkeypatch):
    out = drive(monkeypatch, unbroken)
    assert out["correct"], out["compared"]
