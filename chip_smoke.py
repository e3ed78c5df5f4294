#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, data made from ``--seed``, no network, no reference checkout.
Refuses to go on unless JAX's first device is a TPU.  With no arguments
(one chip) it drives the two main paths through the entry points a user
calls and checks what comes out:

  train   ``lightgbm_tpu.train`` on ``bench.make_higgs_like`` at the one
          shape the repo has a record for (28 features, max_bin=255,
          num_leaves=63, binary, min_data_in_leaf=50, 1,000,000 rows).
          Widths are never cut; ``--rows`` cuts rows only and says so.
  serve   the forest just trained, frozen (``CompiledForest``), behind
          the fleet and ``PredictServer`` IN THIS PROCESS (a child could
          not get the chip), answering HTTP ``/predict`` over localhost.
  parity  the Pallas histogram kernel against its scatter reference on
          the device (exact integers), and one ordered tree grown on the
          chip against the same tree grown on the host CPU device.

``--chips 4`` runs only the multi-chip path and what it is compared with:
``tree_learner=data`` over four chips against the serial learner on one
of them, then a four-replica fleet with each replica on its own chip.

Every failed check raises; nothing is caught and passed over.  Numbers
printed on the way (seconds, AUC, rounds per second) are smoke figures,
not benchmark results.  The last line of stdout is the contract's JSON.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

ROWS = 1_000_000
WARMUP_ROUNDS = 3
TIMED_ROUNDS = 10
PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 63,
          "max_bin": 255, "learning_rate": 0.1, "min_data_in_leaf": 50,
          "verbose": -1}
# Train AUC after WARMUP_ROUNDS + TIMED_ROUNDS rounds of the same seed
# (42) and parameters on the CPU backend reads 0.8450 at 50,000 rows and
# 0.8354 at 1,000,000 (PERF.md, "On the chip, PR 24"): fewer rows fit
# tighter, so a floor under the full-size reading holds for every cut.
AUC_FLOOR = 0.82
SERVE_BUCKETS = [16, 64, 256]
REQUEST_SIZES = [1, 7, 64, 200]
PREDICT_ATOL = 1e-5      # f32 serving path against f64 Booster.predict


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


@contextlib.contextmanager
def phase(name: str, seconds: dict):
    t0 = time.time()
    say(f"== phase {name}")
    yield
    seconds[name] = round(time.time() - t0, 1)
    say(f"== phase {name}: {seconds[name]} s")


# ---------------------------------------------------------------------------
# train

def train_once(lgb, params, dataset, rounds: int, warmup: int):
    """``lightgbm_tpu.train`` with one callback that reads the train AUC
    and the clock after the warm-up rounds and after the last round, each
    behind ``block_until_ready`` on the score buffer."""
    import jax
    marks = {}

    def probe(env):
        if env.iteration + 1 in (warmup, rounds):
            jax.block_until_ready(env.model._booster.train_data.score)
            t = time.time()
            auc = {m: v for _, m, v, _ in env.model.eval_train()}["auc"]
            marks[env.iteration + 1] = (t, float(auc))

    bst = lgb.train(dict(params), dataset, num_boost_round=rounds,
                    verbose_eval=False, callbacks=[probe])
    (t_w, auc_w), (t_e, auc_e) = marks[warmup], marks[rounds]
    return bst, {"auc_warmup": auc_w, "auc_final": auc_e,
                 "rounds_per_sec": (rounds - warmup) / max(t_e - t_w, 1e-9)}


def compiled_step_text(bst) -> str:
    """Compiled text of the booster's fused train step at its current
    shapes (served by the persistent cache: the step was just compiled)."""
    g = bst._booster
    lowered = g._train_step.lower(g.train_data.score, g._full_feat_masks,
                                  g._row_weight, g._lr_cache[1],
                                  g._full_view)
    return lowered.compile().as_text()


def train_phase(rows: int, seed: int, rounds: int = WARMUP_ROUNDS
                + TIMED_ROUNDS, warmup: int = WARMUP_ROUNDS):
    import lightgbm_tpu as lgb
    from bench import make_higgs_like
    from lightgbm_tpu.obs import compile_ledger

    t0 = time.time()
    X, y = make_higgs_like(rows, seed=seed)
    dataset = lgb.Dataset(X, label=y)
    dataset.construct()
    say(f"train: {rows} rows x {X.shape[1]} features made and binned in "
        f"{time.time() - t0:.1f} s" + (f" (rows cut from {ROWS})"
                                       if rows != ROWS else ""))

    n0 = len(compile_ledger.events())
    t0 = time.time()
    bst, obs = train_once(lgb, PARAMS, dataset, rounds, warmup)
    obs["train_seconds"] = time.time() - t0
    events = compile_ledger.events()[n0:]
    obs["compiled_programs"] = sorted({e["program"] for e in events})
    obs["compile_seconds"] = round(sum(e["seconds"] for e in events), 1)
    for e in events:
        say(f"train: compiled {e['program']} in {e['seconds']:.1f} s")
    say(f"train: AUC {obs['auc_warmup']:.4f} after {warmup} rounds, "
        f"{obs['auc_final']:.4f} after {rounds}; "
        f"{obs['rounds_per_sec']:.2f} rounds/s over the last "
        f"{rounds - warmup} (smoke, not a benchmark)")
    check(obs["auc_final"] > obs["auc_warmup"], "train AUC did not rise")
    check(obs["auc_final"] >= AUC_FLOOR,
          f"train AUC {obs['auc_final']:.4f} under the floor {AUC_FLOOR}")
    check("train_step" in obs["compiled_programs"],
          f"compile ledger names no train_step: {obs['compiled_programs']}")

    t0 = time.time()
    obs["custom_calls"] = compiled_step_text(bst).count("tpu_custom_call")
    say(f"train: compiled train_step holds {obs['custom_calls']} "
        f"tpu_custom_call instance(s) (text read in {time.time() - t0:.1f} s)")

    n1 = len(compile_ledger.events())
    train_once(lgb, PARAMS, dataset, warmup, warmup - 1)
    again = compile_ledger.events()[n1:]
    check(not again, "a second lightgbm_tpu.train with the same parameters "
          f"compiled {[e['program'] for e in again]}")
    say("train: second lightgbm_tpu.train compiled nothing")
    return bst, X, obs


# ---------------------------------------------------------------------------
# serve

def http_json(url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def wait_ready(server, base: str, timeout: float) -> None:
    """Poll ``/readyz``.  A warm-up that died leaves readiness down for
    good (the server only logs it), so that is a failure here, at once."""
    deadline = time.time() + timeout
    while not server.is_ready():
        warming = server._warm_thread.is_alive()
        check(warming or server.is_ready(),
              "serve: warm-up thread ended and readiness never came up")
        check(time.time() < deadline,
              f"serve: not ready after {timeout:.0f} s")
        time.sleep(0.2)
    http_json(base + "/readyz")                # 200, or urlopen raises


def serve_phase(bst, X, devices, sizes=REQUEST_SIZES):
    """Freeze, serve over HTTP on ``devices`` (one replica each), compare
    with ``Booster.predict``.  Returns what ``/healthz`` and the fleet
    report, for the caller's chip-only checks."""
    from lightgbm_tpu.serve import CompiledForest, Fleet, PredictServer

    forest = CompiledForest.from_booster(bst, buckets=SERVE_BUCKETS)
    fleet = Fleet.build(forest, devices=devices,
                        max_batch=SERVE_BUCKETS[-1], warm=False)
    server = PredictServer(fleet, port=0, max_batch=SERVE_BUCKETS[-1],
                           warm_in_background=True).start()
    try:
        base = "http://%s:%d" % server.address
        t0 = time.time()
        wait_ready(server, base, timeout=600.0)
        warm_s = time.time() - t0
        info = http_json(base + "/healthz")
        compiles0 = http_json(base + "/stats")["counters"]["compile_count"]

        # several clients at once, so every replica takes traffic
        jobs = [(n, off) for off in range(len(devices)) for n in sizes]
        replies = [None] * len(jobs)

        def client(i):
            n, off = jobs[i]
            replies[i] = http_json(
                base + "/predict",
                {"rows": X[off:off + n].astype(np.float32).tolist()})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        worst = 0.0
        for (n, off), rep in zip(jobs, replies):
            check(rep is not None, f"serve: no reply to a {n}-row request")
            got = np.asarray(rep["predictions"], np.float64)
            want = bst.predict(X[off:off + n])
            check(got.shape == want.shape and np.isfinite(got).all(),
                  f"serve: bad reply to a {n}-row request")
            worst = max(worst, float(np.abs(got - want).max()))
        check(worst <= PREDICT_ATOL, "serve: /predict differs from "
              f"Booster.predict by {worst:.3g} > {PREDICT_ATOL}")
        stats = http_json(base + "/stats")
        check(stats["counters"]["compile_count"] == compiles0,
              "serve: a request compiled after warm-up")
        replicas = stats["fleet"]["replicas"]
        obs = {"walk": info["serve_walk"],
               "interpreted": info.get("walk_interpreted"),
               "replica_devices": [r["device"] for r in replicas],
               "replica_requests": [r["requests"] for r in replicas],
               "table_devices": [
                   sorted({str(d) for a in (rep.forest._tree_dev
                                            + (rep.forest._walk_dev or ()))
                           for d in a.devices()})
                   for rep in fleet._primary.replicas]}
        say(f"serve: {len(replicas)} replica(s) warm in {warm_s:.1f} s; "
            f"walk strategy served: {obs['walk']}, interpreted: "
            f"{obs['interpreted']}; {len(jobs)} requests of "
            f"{sorted(set(sizes))} rows agree with Booster.predict to "
            f"{worst:.2g}; no compile after warm-up")
        return obs
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# kernel parity (until PR 24 a test file behind an opt-in variable)

def digit_parity(n: int = 100_000, f: int = 28, b: int = 255) -> None:
    import jax.numpy as jnp
    from lightgbm_tpu.ops import leafhist as lh

    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, b, size=(n, f)), jnp.uint8)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.abs(g) + 0.1
    w = jnp.asarray(rng.uniform(size=n) < 0.8, jnp.float32)
    digits = lh.quantize_digits(g, h, w, lh.compute_scales(g, h, w))
    got = np.asarray(lh.digit_histogram_pallas(bins, digits, b))
    want = np.asarray(lh.digit_histogram_scatter(bins, digits, b))
    check(np.array_equal(got, want),
          "parity: digit_histogram_pallas != digit_histogram_scatter")
    say(f"parity: digit_histogram_pallas == scatter reference, exactly "
        f"({n} x {f}, {b} bins)")
    # the same rows as the leaf-ordered layout's word lanes, a segment
    # inside a window of whole kernel steps
    import jax
    from lightgbm_tpu.ops import ordered_grow as og
    rows = n // lh.STEP_ROWS * lh.STEP_ROWS
    first, scnt = 1234, rows // 2
    lanes = lh.digit_histogram_lanes(
        tuple(x[:rows] for x in og.pack_u8_words(bins)),
        tuple(x[:rows] for x in og.pack_u8_words(
            jax.lax.bitcast_convert_type(digits, jnp.uint8))),
        jnp.int32(first), jnp.int32(scnt), f, b)
    want = lh.digit_histogram_scatter(bins[first:first + scnt],
                                      digits[first:first + scnt], b)
    check(np.array_equal(np.asarray(lanes), np.asarray(want)),
          "parity: digit_histogram_lanes != digit_histogram_scatter")
    say(f"parity: digit_histogram_lanes == scatter reference, exactly "
        f"({scnt} of {rows} x {f}, {b} bins)")


def ordered_tree_parity(chip, n: int = 60_000, f: int = 10, b: int = 64):
    """One ordered tree on ``chip`` against the same tree on the host CPU
    device.  The platform probe is process-global, so the CPU run steers
    it to the scatter path explicitly, and the jit caches are dropped in
    between (the traced program holds the kernel choice)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.ops.ordered_grow import grow_tree_ordered
    from lightgbm_tpu.utils import device

    rng = np.random.RandomState(2)
    bins_rm = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = (np.abs(rng.normal(size=n)) + 0.1).astype(np.float32)
    params = GrowParams(num_leaves=31, max_bin=b, min_data_in_leaf=20,
                        min_sum_hessian_in_leaf=1.0)

    def grow(dev):
        with jax.default_device(dev):
            t, leaf, delta = grow_tree_ordered(
                jnp.asarray(bins_rm.T), jnp.full((f,), b, jnp.int32),
                jnp.zeros((f,), bool), jnp.ones((f,), bool),
                jnp.asarray(g), jnp.asarray(h), jnp.ones((n,), jnp.float32),
                jnp.float32(0.1), params, bins_rm=jnp.asarray(bins_rm))
            return [np.asarray(a) for a in
                    (t.split_feature, t.split_bin, leaf, delta)]

    on_chip = grow(chip)
    jax.clear_caches()
    probe = device.on_tpu
    device.on_tpu = lambda: False
    try:
        on_cpu = grow(jax.devices("cpu")[0])
    finally:
        device.on_tpu = probe
        jax.clear_caches()
    for name, a, c in zip(("split features", "thresholds", "routing"),
                          on_chip, on_cpu):
        check(np.array_equal(a, c), f"parity: ordered tree {name} differ "
              "between the chip and the CPU")
    # identical splits and routing; leaf VALUES round differently in f32
    check(np.allclose(on_chip[3], on_cpu[3], rtol=2e-4, atol=1e-6),
          "parity: ordered tree leaf values differ beyond f32 rounding")
    say(f"parity: ordered tree on {chip} == the same tree on the CPU "
        f"device ({n} x {f}, {b} bins, 31 leaves)")


# ---------------------------------------------------------------------------
# four chips

def multichip_phase(rows: int, seed: int, chips: int, rounds: int = 5):
    import jax
    import lightgbm_tpu as lgb
    from bench import make_higgs_like

    X, y = make_higgs_like(rows, seed=seed)
    dataset = lgb.Dataset(X, label=y)
    dataset.construct()
    say(f"chips: {rows} rows x {X.shape[1]} features"
        + (f" (rows cut from {ROWS})" if rows != ROWS else ""))

    t0 = time.time()
    par, par_obs = train_once(
        lgb, dict(PARAMS, tree_learner="data", num_machines=chips),
        dataset, rounds, 1)
    say(f"chips: tree_learner=data over {chips} trained {rounds} rounds in "
        f"{time.time() - t0:.1f} s, AUC {par_obs['auc_final']:.4f}, "
        f"{par_obs['rounds_per_sec']:.2f} rounds/s (smoke, not a benchmark)")
    g = par._booster
    # models/gbdt.py warns and grows serially when it finds fewer devices
    check(g._parallel_grow_active, "chips: the parallel learner is not "
          "active (fell back to serial)")
    # placement by shard (models/gbdt.py _DeviceData): every array of the
    # resident training state is one equal block on each device
    td = g.train_data
    for name, arr in (("binned matrix", td.bins),
                      ("row-major bins", td.bins_rm),
                      ("bin words", td.bins_words[0]),
                      ("scores", td.score),
                      ("labels", g._grad_arrays["label"])):
        shards = arr.addressable_shards
        check(len(shards) == chips
              and len({s.device for s in shards}) == chips
              and all(s.data.size * chips == arr.size for s in shards),
              f"chips: {name} is not one equal block on each of {chips} "
              f"devices: {[(str(s.device), s.data.shape) for s in shards]}")
    # leaf-ordered shards: one all-reduce of digit sums a split step (the
    # kernel is the serial learner's digit_histogram)
    text = compiled_step_text(par)
    obs = {"all_reduce": text.count(" all-reduce("),
           "custom_calls": text.count("tpu_custom_call"),
           "comm_calls_per_tree": g._comm_traffic_totals[0]}
    say(f"chips: one block of bins, words, scores and labels on each of "
        f"{chips} devices; compiled step holds {obs['all_reduce']} "
        f"all-reduce, {obs['custom_calls']} tpu_custom_call; "
        f"{obs['comm_calls_per_tree']} collective calls a tree")
    check(obs["all_reduce"] > 0,
          "chips: the compiled step holds no all-reduce")
    check("digit_histogram" in text,
          "chips: the sharded step does not run the leaf-ordered grower's "
          "kernel")

    t0 = time.time()
    ser, ser_obs = train_once(lgb, PARAMS, dataset, rounds, 1)
    say(f"chips: serial learner on {jax.devices()[0]} trained {rounds} "
        f"rounds in {time.time() - t0:.1f} s, AUC "
        f"{ser_obs['auc_final']:.4f}")
    check(abs(par_obs["auc_final"] - ser_obs["auc_final"]) <= 1e-3,
          f"chips: AUC {par_obs['auc_final']:.5f} (data-parallel) vs "
          f"{ser_obs['auc_final']:.5f} (serial) differ by more than 1e-3")
    compare_first_trees(par._booster.models[0], ser._booster.models[0])

    obs.update(serve_phase(par, X, list(jax.local_devices())[:chips]))
    check(len({tuple(d) for d in obs["table_devices"]}) == chips
          and all(len(d) == 1 for d in obs["table_devices"]),
          f"chips: replicas do not each hold their tables on their own "
          f"device: {obs['table_devices']}")
    say(f"chips: {chips} replicas, tables on {obs['table_devices']}, "
        f"requests per replica {obs['replica_requests']}")
    return obs


def compare_first_trees(par, ser, tie: float = 1e-3) -> None:
    """Same splits in the same order, or the first difference is a
    near-tie in gain (the two learners sum f32 histograms in different
    orders; after a tie breaks the other way the trees differ honestly
    and the AUC check is what holds them together)."""
    n = min(par.num_leaves, ser.num_leaves) - 1
    for i in range(n):
        same = (par.split_feature[i] == ser.split_feature[i]
                and par.threshold[i] == ser.threshold[i])
        if same:
            continue
        gp, gs = float(par.split_gain[i]), float(ser.split_gain[i])
        check(abs(gp - gs) <= tie * max(abs(gp), abs(gs)),
              f"chips: first trees differ at split {i} and it is no "
              f"near-tie: data-parallel f{par.split_feature[i]}<="
              f"{par.threshold[i]} gain {gp}, serial "
              f"f{ser.split_feature[i]}<={ser.threshold[i]} gain {gs}")
        say(f"chips: first trees agree on splits 0..{i - 1}, then break a "
            f"near-tie differently (gains {gp:.6g} vs {gs:.6g})")
        return
    say(f"chips: first trees agree on all {n} split features and "
        "thresholds")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="cut rows (never widths); the cut is printed")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()

    # the parity phase grows one tree on the host CPU device too: keep
    # that backend reachable where the platform list is pinned to the chip
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and "cpu" not in pinned.split(","):
        os.environ["JAX_PLATFORMS"] = pinned + ",cpu"
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devices)}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: {device}")

    from lightgbm_tpu.utils import compile_cache
    say(f"compile cache: {compile_cache.setup()}")
    cache_events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **_: cache_events.update([name.rsplit("/", 1)[-1]]))

    def on_chip_checks(obs):
        """What only the chip can show: the Pallas kernels are in the
        compiled step, and the walk kernel itself served — compiled,
        never the interpreter."""
        check(obs["custom_calls"] > 0,
              "the compiled train step holds no tpu_custom_call")
        check(obs["walk"] == "fused" and obs["interpreted"] is False,
              f"serve: expected the fused walk kernel, compiled; got "
              f"{obs['walk']}, interpreted={obs['interpreted']}")

    seconds: dict = {}
    if args.chips == 4:
        with phase("chips", seconds):
            on_chip_checks(multichip_phase(args.rows, args.seed, args.chips))
    else:
        with phase("train", seconds):
            bst, X, obs = train_phase(args.rows, args.seed)
        say(f"train: compiles took {obs['compile_seconds']} s of "
            f"{obs['train_seconds']:.1f} s (cold where the cache missed: "
            f"see the hit count below)")
        with phase("serve", seconds):
            obs.update(serve_phase(bst, X, [devices[0]]))
            on_chip_checks(obs)
        # parity last: it drops the jit caches between chip and CPU
        with phase("parity", seconds):
            digit_parity()
            ordered_tree_parity(devices[0])
    say(f"seconds per phase: {seconds}; compile cache: "
        f"{cache_events['cache_hits']} hit(s), "
        f"{cache_events['cache_misses']} entr(ies) written")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
