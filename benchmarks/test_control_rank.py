"""``test_control.py``'s twin for kind ``train_rank``, at the ranking
cell's rehearsal size on the CPU.  Not part of the repo's tier-1 suite
(that is ``tests/``); run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_control_rank.py -q

1. The control: the plain ranking reference put in the program's place in
   bfloat16 comes out not correct under the cell's own limits; the same in
   float32 comes out correct.
2. Each planted fault comes out not correct: the ``0.01 + |gap|``
   normaliser left out, the discount dropped from delta-NDCG, half of the
   queries left out, a bound table with four fifths of the bins (that one
   by ``bin_table_gap`` alone).
3. The rest of a run with the timed path broken underneath: half of the
   queries not handed to the program, the bound table made with four
   fifths of the bins, the state put back after every step.  Unbroken, it
   is correct (tests/test_lambdarank.py runs that one in tier-1).

(``test_control.py`` takes the last cell by name for its planted faults,
which is now this ranking cell, whose data ``control.py`` cannot make: that
test of it fails with ``KeyError: 'mslr_like'`` until it picks its cell by
kind.  PERF.md section 7.)
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

import control_rank  # noqa: E402
import run as bench_run  # noqa: E402
from harness import device  # noqa: E402

CELL = "mslr30k-lambdarank-train"


def _read(mode, seed, n_trees):
    cell = bench_run.load_cell(CELL)
    return cell, control_rank.read(
        cell["config_file"], cell["limits"], cell["rehearse"]["num_data"],
        seed, [mode], n_trees=n_trees)[0]


@pytest.mark.parametrize("seed", [101, 2147483659])
def test_control_in_lower_precision_is_not_correct(seed):
    cell = bench_run.load_cell(CELL)
    got = control_rank.read(cell["config_file"], cell["limits"],
                            cell["rehearse"]["num_data"], seed,
                            ["float32", "bfloat16"], n_trees=2)
    by_mode = {g["mode"]: g for g in got}
    assert by_mode["float32"]["correct"], by_mode["float32"]
    assert not by_mode["bfloat16"]["correct"], by_mode["bfloat16"]


@pytest.mark.parametrize("fault", ["no_normaliser", "no_discount",
                                   "half_queries"])
def test_fault_planted_in_the_reference_is_not_correct(fault):
    # the normaliser enters with the first scores that differ: two trees
    _, got = _read(fault, 77, 2)
    assert not got["correct"], got


def test_bound_table_of_four_fifths_of_the_bins_is_not_correct():
    cell, got = _read("table_bins80", 55, 1)
    v, limits = got["values"], cell["limits"]
    assert not got["correct"], got
    assert v["bin_table_gap"] > limits["bin_table_gap"], v
    assert all(v[k] <= limits[k] for k in v if k != "bin_table_gap"), v


def _drive(monkeypatch, breaker):
    import lightgbm_tpu as lgb
    breaker(monkeypatch, lgb)
    cell = bench_run.load_cell(CELL)
    args = argparse.Namespace(workload=cell["name"], seed=9, seconds=0.5,
                              trace=0, rehearse=True, out=None)
    kind = __import__("harness.kinds." + cell["kind"], fromlist=["measure"])
    out = kind.measure(cell, args, device.rehearsal_device(), time.time())
    assert out is not None
    return out


def _half_of_the_queries(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, group=None, **k):
        q = len(group) // 2
        n = int(sum(group[:q]))
        orig(self, data[:n], label[:n], *a, group=group[:q], **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def _table_of_four_fifths_of_the_bins(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, **k):
        params = dict(k.get("params") or {})
        params["max_bin"] = int(params["max_bin"]) * 4 // 5
        k["params"] = params
        orig(self, data, label, *a, **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def _state_unchanged(monkeypatch, lgb):
    import jax.numpy as jnp
    orig = lgb.Booster.update

    def update(self, *a, **k):
        r = orig(self, *a, **k)
        td = self._booster.train_data
        td.score = jnp.zeros_like(td.score)     # the step's state, put back
        return r
    monkeypatch.setattr(lgb.Booster, "update", update)


@pytest.mark.parametrize("breaker", [_half_of_the_queries,
                                     _table_of_four_fifths_of_the_bins,
                                     _state_unchanged],
                         ids=lambda b: b.__name__.strip("_"))
def test_run_with_the_timed_path_broken_is_not_correct(monkeypatch, breaker):
    out = _drive(monkeypatch, breaker)
    assert not out["correct"], out["compared"]
