"""Self-test of the harness, for the sandbox: no chip, no program run.

    python3 benchmarks/selftest.py

Checks the trace reduction against a small recorded event list, the count
functions against a hand-worked three-leaf tree, the percentile and the
last line, the readers' way of leaving out what they cannot read, every
data file's shape against ``BENCHMARK.json``, and that the command
refuses to measure without a TPU.  Exits non-zero on the first failure.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import counts, layers, result, trace  # noqa: E402


def close(a, b, tol=1e-12):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def test_trace_reduction():
    # recorded by hand: a while loop [0, 100) holding a fusion [10, 40) and
    # a kernel call [50, 90); a gap; then a copy [150, 170).  ns.
    ev = [("while.1", "while.1 hlo_category=while", 0, 100),
          ("fusion.7", "fusion.7 tf_op=jit(train_step)/split/sort", 10, 30),
          ("digit_hist", "digit_hist hlo_category=custom-call "
           "tf_op=jit(train_step)/hist/pallas_call", 50, 40),
          ("copy.3", "copy.3 tf_op=jit(train_step)/split/copy", 150, 20)]
    st = {n: d for n, _, _, d in trace.self_times(ev)}
    assert st == {"while.1": 30, "fusion.7": 30, "digit_hist": 40,
                  "copy.3": 20}, st
    assert trace.busy_ns(ev) == 120
    assert trace.span_ns(ev) == 170 and trace.span_ns([]) == 0
    timed = trace.self_times(ev)
    assert trace.matching_ns(timed, r"/split/") == 50
    assert trace.matching_ns(timed, r"/hist/|custom-call") == 40
    assert trace.matching_ns(timed, r"train_step", exclude=r"/hist/") == 50
    assert trace.matching_ns(timed, r"nothing_like_it") == 0
    assert trace.top_ops(timed, 2) == [["digit_hist", 40e-9],
                                       ["while.1", 30e-9]]
    gaps = trace.idle_gaps(ev, [("bench_round_5", "", 90, 100)], 3)
    assert gaps == [["bench_round_5", 50e-9]], gaps


def test_histogram_kernel_is_told_from_other_kernels():
    # names as this chip's trace prints them (my chip run, PR 27, call 1),
    # and a Pallas kernel of another shape, as a partition kernel would be
    tail = ', custom_call_target="tpu_custom_call", operand_layout...'
    ev = [("%grow_tree_ordered.1 = s32[28,9,256]{2,1,0:T(8,128)S(1)} "
           "custom-call(u8[6029312,28]{1,0:T(8,128)(4,1)} %copy.311, "
           "s8[6029312,9]{1,0} %p)" + tail, 0, 40),
          ("%branch_0_fun.19 = s32[28,9,128]{2,1,0:T(8,128)} custom-call("
           "u8[4194304,28]{1,0:T(8,128)(4,1)} %and_convert_fusion.37, s8["
           "4194304,9]{1,0} %q)" + tail, 50, 30),
          ("%count_partition.3 = u8[4194304,28]{1,0} custom-call(u8[4194304"
           ",28]{1,0} %x, s32[4194304]{0} %leaf)" + tail, 90, 20),
          ("%sort.890 = (u8[16777216]{0}, s32[16777216]{0}) sort(...)",
           120, 10)]
    timed = trace.self_times([(n, n, s, d) for n, s, d in ev])
    specs = {sp["name"]: sp for sp in
             layers.specs("higgs10m-255bin-train", "train")}
    hist, part = specs["hist_ms_per_round"], specs["partition_ms_per_round"]
    assert specs["hist_roofline"]["match"] == hist["match"] == part["exclude"]
    assert trace.matching_ns(timed, hist["match"]) == 70
    assert trace.matching_ns(timed, part["match"], part["exclude"]) == 30
    assert trace.matching_ns(timed, specs["sort_ms_per_round"]["match"]) == 10
    named = [("x", "%k = f32[8] custom-call() name=digit_histogram_pallas",
              0, 5)]
    assert trace.matching_ns(trace.self_times(named), hist["match"]) == 5


def three_leaf_tree():
    # root 1000 rows -> left 300 (leaf 0), right 700 -> 650 (leaf 1), 50
    return {"num_leaves": 3, "tree_structure": {
        "split_index": 0, "internal_count": 1000,
        "left_child": {"leaf_index": 0, "leaf_count": 300},
        "right_child": {"split_index": 1, "internal_count": 700,
                        "left_child": {"leaf_index": 1, "leaf_count": 650},
                        "right_child": {"leaf_index": 2, "leaf_count": 50}}}}


def test_counts():
    t = three_leaf_tree()
    # the root's 1000, then the smaller child of each split: 300 and 50
    assert counts.visited_rows(t, 1000) == 1350
    w = counts.histogram_work([t], 1000, 28)
    assert w == {"visited_rows": 1350, "bytes": 1350 * 36,
                 "ops": 1350 * 84}, w
    r = counts.round_work([t], 1000, 28)
    assert r["bytes"] == 1350 * 36 + 1000 * 24
    assert r["ops"] == 1350 * 84 + 1000 * 11
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    least = counts.least_seconds(w, peaks)
    assert least["bound"] == "bytes"
    assert close(least["seconds"], 1350 * 36 / 819e9)


def test_percentile_and_last_line():
    assert result.percentile([1, 2, 3, 4, 5], 50) == 3
    assert close(result.percentile(range(1, 101), 95), 95.05)
    assert result.percentile([7], 95) == 7
    compared = {"a": {"value": 0.5, "limit": 1.0},
                "b": {"value": 0.0, "limit": 0.0}}
    assert result.verdict(compared)
    assert not result.verdict({"a": {"value": None, "limit": 1.0}})
    assert not result.verdict({"a": {"value": 2.0, "limit": 1.0}})
    assert not result.verdict({"a": {"value": float("nan"), "limit": 1.0}})
    assert not result.verdict({})
    line = result.last_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1}, compared=compared)
    obj = json.loads(line)
    assert "\n" not in line and list(obj)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(obj)


def test_readers_leave_out():
    ctx = layers.Context(trace_dir="/nonexistent", traced=None,
                         traced_trees=None, rows=1000, features=28,
                         peaks=None, compiles_in_window=0, peak_bytes=2 ** 30,
                         setup_compile_s=0.0, chips=1)
    got = {}
    for spec in layers.specs("higgs10m-255bin-train", "train"):
        got[spec["name"]] = layers.REDUCTIONS[spec["reduce"]](spec, ctx)
    assert got["compiles_in_window"] == 0
    assert close(got["peak_hbm_gib.train"], 1.0, 1e-9)
    for name, v in got.items():
        if name not in ("compiles_in_window", "peak_hbm_gib.train"):
            assert v is None, (name, v)        # never 0 for a share
    # with a trace: a roofline from the three-leaf tree
    ctx.events = trace.self_times(
        [("k", "k hist custom-call", 0, 1000)])
    ctx.rounds, ctx.window_s, ctx.busy_s = 1, 2e-6, 1e-6
    ctx.traced_trees = [three_leaf_tree()]
    ctx.peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    spec = {"reduce": "roofline_pct", "count_function": "histogram_work",
            "match": "custom-call"}
    assert close(layers.roofline_pct(spec, ctx),
                 100 * (1350 * 36 / 819e9) / 1e-6)
    assert close(layers.share_of_window({"of": "idle"}, ctx), 50.0)


def test_files_agree_with_benchmark_json():
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(path):
        print("  (no BENCHMARK.json yet: skipped)")
        return
    with open(path) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        with open(os.path.join(HERE, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
    on_file = {s["name"]: s for s in layers.specs("", "train")
               if "workloads" not in s}
    for s in layers.specs(bench["workloads"][0]["name"], "train"):
        on_file[s["name"]] = s
    for m in bench["per_layer"]:
        s = on_file[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert s[key] == m[key], (m["name"], key)
    assert set(on_file) == {m["name"] for m in bench["per_layer"]}


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = sorted(os.listdir(os.path.join(HERE, "workloads")))[0][:-5]
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0, "measured on a CPU"
    assert p.stdout.strip() == "", "printed a result without a chip"
    assert "not 'tpu'" in p.stderr


TESTS = [test_trace_reduction,
         test_histogram_kernel_is_told_from_other_kernels, test_counts, test_percentile_and_last_line,
         test_readers_leave_out, test_files_agree_with_benchmark_json,
         test_refuses_without_a_chip]

if __name__ == "__main__":
    for t in TESTS:
        print(t.__name__)
        t()
    print(f"selftest: {len(TESTS)} checks passed")
