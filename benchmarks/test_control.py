"""Tests of the comparison that decides ``correct``, at a size a test run
can hold (the cells' rehearsal size, on the CPU).  Not part of the repo's
tier-1 suite (that is ``tests/``); run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_control.py -q

1. The control: the reference put in the program's place in bfloat16
   comes out not correct under the cell's own limits, on three seeds;
   the same in float32 comes out correct.
2. The faults a one-chip training cell can have, planted in the reference
   put in the program's place: each comes out not correct.  So does a
   degraded bound table (a twentieth of the sample, four fifths of the
   bins)
   under sound float32 trees.
3. The rest of a run driven with the timed path broken underneath (the
   harness's look for a chip skipped): a step that returns its state
   unchanged, half of the batch left out, an answer altered where it is
   produced, the bound table made from a twentieth of the sample or with
   four fifths of the bins.  Each sees ``correct`` come out false; unbroken,
   true.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import run as bench_run  # noqa: E402
from harness import device  # noqa: E402

CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "workloads"))
               if f.endswith(".json"))


def _cell(name):
    return bench_run.load_cell(name)


@pytest.mark.parametrize("seed", [101, 2147483659, 303])
def test_control_in_lower_precision_is_not_correct(seed):
    cell = _cell(CELLS[0])
    got = control.read(cell["config_file"], cell["limits"],
                       cell["rehearse"]["num_data"], seed,
                       ["float32", "bfloat16"], n_trees=2)
    by_mode = {g["mode"]: g for g in got}
    assert by_mode["float32"]["correct"], by_mode["float32"]
    assert not by_mode["bfloat16"]["correct"], by_mode["bfloat16"]


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged",
                                   "leaf_altered"])
def test_fault_planted_in_the_reference_is_not_correct(fault):
    cell = _cell(CELLS[-1])
    got = control.read(cell["config_file"], cell["limits"],
                       cell["rehearse"]["num_data"], 77, [fault], n_trees=2)
    assert not got[0]["correct"], got[0]


@pytest.mark.parametrize("table", ["table_twentieth_sample", "table_bins80"])
def test_degraded_bound_table_is_not_correct(table):
    cell = _cell(CELLS[0])
    got = control.read(cell["config_file"], cell["limits"],
                       cell["rehearse"]["num_data"], 55, [table], n_trees=1)
    v = got[0]["values"]
    limits = cell["limits"]
    assert not got[0]["correct"], got[0]
    assert v["bin_cdf_gap"] > limits["bin_cdf_gap"], v
    assert all(v[k] <= limits[k] for k in v if k != "bin_cdf_gap"), v


def _drive(monkeypatch, breaker):
    """The rest of a run (everything after the look for a chip) at the
    rehearsal size, with ``breaker`` applied to the program first."""
    import lightgbm_tpu as lgb
    if breaker is not None:
        breaker(monkeypatch, lgb)
    cell = _cell(CELLS[0])
    args = argparse.Namespace(workload=cell["name"], seed=9, seconds=0.5,
                              trace=0, rehearse=True, out=None)
    kind = __import__("harness.kinds." + cell["kind"],
                      fromlist=["measure"])
    out = kind.measure(cell, args, device.rehearsal_device(), time.time())
    assert out is not None
    return out


def _state_unchanged(monkeypatch, lgb):
    import jax.numpy as jnp
    orig = lgb.Booster.update

    def update(self, *a, **k):
        r = orig(self, *a, **k)
        td = self._booster.train_data
        td.score = jnp.zeros_like(td.score)     # the step's state, put back
        return r
    monkeypatch.setattr(lgb.Booster, "update", update)


def _half_batch(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, **k):
        n = len(data) // 2
        orig(self, data[:n], label[:n], *a, **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def _answer_altered(monkeypatch, lgb):
    orig = lgb.Booster.update

    def update(self, *a, **k):
        r = orig(self, *a, **k)
        models = self._booster.models
        if len(models) == 2 and not getattr(self, "_altered", False):
            models[0].leaf_value[3] *= 1.01     # where it is produced
            self._altered = True
        return r
    monkeypatch.setattr(lgb.Booster, "update", update)


def _table_from_a_twentieth_of_the_sample(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, **k):
        k["params"] = dict(k.get("params") or {},
                           bin_construct_sample_cnt=len(data) // 20)
        orig(self, data, label, *a, **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def _table_of_four_fifths_of_the_bins(monkeypatch, lgb):
    orig = lgb.Dataset.__init__

    def init(self, data, label=None, *a, **k):
        params = dict(k.get("params") or {})
        params["max_bin"] = int(params["max_bin"]) * 4 // 5
        k["params"] = params
        orig(self, data, label, *a, **k)
    monkeypatch.setattr(lgb.Dataset, "__init__", init)


def test_unbroken_run_is_correct(monkeypatch):
    out = _drive(monkeypatch, None)
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("breaker", [_state_unchanged, _half_batch,
                                     _answer_altered,
                                     _table_from_a_twentieth_of_the_sample,
                                     _table_of_four_fifths_of_the_bins],
                         ids=lambda b: b.__name__.strip("_"))
def test_run_with_the_timed_path_broken_is_not_correct(monkeypatch, breaker):
    out = _drive(monkeypatch, breaker)
    assert not out["correct"], out["compared"]
