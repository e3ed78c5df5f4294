"""Warmup-tax fixes: persistent compile cache setup, shared row buckets,
process-wide training programs, and score-buffer donation.

The tier-1 acceptance for round 7 (ISSUE 7): training the same config
twice in one process — and once more after a snapshot-resume — must show
ZERO new ``train_step``/``grow_tree`` XLA compiles in the compile ledger
on the repeat run, and the donated score buffer must not be
double-allocated round to round.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.utils import compile_cache  # noqa: E402
from lightgbm_tpu.obs import compile_ledger  # noqa: E402

# programs whose re-compilation on a repeat run would mean the warmup
# tax is back (growth programs inline into train_step on the fused path
# but are listed for the per-stage paths too)
TRAIN_PROGRAMS = {"train_step", "train_gradients", "grow_tree",
                  "grow_tree_ordered", "pack_words", "pack_tree",
                  "bag_mask", "finite_guard", "score_update"}


def _train_events():
    return [e for e in compile_ledger.events()
            if e["program"] in TRAIN_PROGRAMS]


def _make_binary(n=1237, f=7, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] + rng.normal(scale=0.5, size=n) > 0)
    return X, y.astype(np.float64)


def _booster(X, y, **extra):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 20,
              "max_bin": 63, "num_iterations": 4}
    params.update(extra)
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20)
    return GBDT(Config(params), ds)


# ---------------------------------------------------------------------------
# bucket_rows: the shared shape ladder


def test_bucket_rows_basics():
    assert compile_cache.bucket_rows(0) == 0
    assert compile_cache.bucket_rows(1) == 1
    for n in (2, 31, 32, 33, 1000, 987, 65_537, 1_000_000):
        b = compile_cache.bucket_rows(n)
        assert b >= n
        # overhead bounded by 2^(1-ROW_BUCKET_BITS) (worst just past a
        # power of two, where the step doubles)
        assert b - n < max(n / (1 << (compile_cache.ROW_BUCKET_BITS - 1))
                           + 1, 2)
        # idempotent: a bucket is its own bucket
        assert compile_cache.bucket_rows(b) == b


def test_bucket_rows_collapses_nearby_sizes():
    """The whole point: many nearby row counts -> few shapes."""
    buckets = {compile_cache.bucket_rows(n)
               for n in range(1_000_000, 1_015_000)}
    assert len(buckets) <= 2


# ---------------------------------------------------------------------------
# setup(): one helper for every entry point


_DEFAULT = object()   # "the fixed directory inside the checkout"

# (JAX_COMPILATION_CACHE_DIR, LIGHTGBM_TPU_COMPILE_CACHE, param) -> dir
_PRECEDENCE = {
    "unset-default": (None, None, None, _DEFAULT),
    "unset-param": (None, None, "/x", "/x"),
    "unset-param-off": (None, None, "off", None),
    "unset-switch-off": (None, "none", "/x", None),
    "unset-switch-dir-ignored": (None, "/envdir", None, _DEFAULT),
    "jax-alone": ("/jaxdir", None, None, "/jaxdir"),
    "jax-beats-param": ("/jaxdir", None, "/x", "/jaxdir"),
    "jax-beats-switch-dir": ("/jaxdir", "/envdir", "/x", "/jaxdir"),
    "jax-param-off": ("/jaxdir", None, "off", None),
    "jax-switch-off": ("/jaxdir", "0", "/x", None),
}


@pytest.mark.parametrize("case", sorted(_PRECEDENCE))
def test_resolve_dir_precedence(monkeypatch, case):
    """The cache is placed from outside: JAX_COMPILATION_CACHE_DIR, where
    set, wins over the parameter and the repo's own variable (which only
    switches off); unset, the parameter, else the in-checkout default."""
    jax_dir, switch, param, want = _PRECEDENCE[case]
    for name, value in ((compile_cache.JAX_ENV_DIR, jax_dir),
                        (compile_cache.ENV_SWITCH, switch)):
        if value is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, value)
    if want is _DEFAULT:
        want = compile_cache.DEFAULT_CACHE_DIR
    assert compile_cache.resolve_dir(param) == want
    assert compile_cache.resolve_dir(param) == want      # stable per call


def test_default_dir_is_fixed_inside_the_checkout():
    """No temporary name, pid or time in the default: the path is part of
    the cache key, so two processes must resolve the same directory —
    inside the checkout, beside the package."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(
        repo, ".jax_compile_cache")
    env = {k: v for k, v in os.environ.items()
           if k not in (compile_cache.JAX_ENV_DIR, compile_cache.ENV_SWITCH)}
    env["PYTHONPATH"] = repo
    code = ("from lightgbm_tpu.utils import compile_cache as c; "
            "print(c.resolve_dir())")
    outs = {subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, check=True,
                           timeout=120).stdout.strip() for _ in range(2)}
    assert outs == {compile_cache.DEFAULT_CACHE_DIR}


def test_setup_applies_and_disables(tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_SWITCH, raising=False)
    monkeypatch.delenv(compile_cache.JAX_ENV_DIR, raising=False)
    d = str(tmp_path / "cache")
    assert compile_cache.setup(d) == d
    assert compile_cache.configured_dir() == d
    assert jax.config.jax_compilation_cache_dir == d
    # placed from outside: the variable wins over the parameter
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.JAX_ENV_DIR, placed)
    assert compile_cache.setup(d) == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert compile_cache.setup("off") is None
    assert compile_cache.configured_dir() is None


# ---------------------------------------------------------------------------
# zero recompiles on repeat runs (the tier-1 acceptance)


def test_second_training_run_zero_train_compiles():
    X, y = _make_binary()
    b1 = _booster(X, y)
    for _ in range(4):
        b1.train_one_iter()
    m1 = b1.eval_metrics()
    before = len(_train_events())
    assert before > 0 or len(compile_ledger.events()) >= 0  # ledger alive

    # fresh dataset object, fresh booster, same config: every training
    # program must come from the shared in-process registry
    b2 = _booster(X, y)
    for _ in range(4):
        b2.train_one_iter()
    new = _train_events()[before:]
    assert new == [], f"repeat run recompiled: {new}"
    assert b2.eval_metrics() == m1


def test_training_after_snapshot_resume_zero_train_compiles(tmp_path):
    import lightgbm_tpu as lgb

    X, y = _make_binary(n=1151, seed=3)
    params = {"objective": "binary", "num_leaves": 7,
              "min_data_in_leaf": 20, "max_bin": 63, "verbose": -1,
              "snapshot_dir": str(tmp_path), "snapshot_freq": 2}
    bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                    num_boost_round=4)
    assert bst.current_iteration() == 4
    before = len(_train_events())

    # same command again: auto-resumes from the newest snapshot and
    # trains the remaining rounds with ZERO new training-program compiles
    bst2 = lgb.train(dict(params), lgb.Dataset(X, label=y),
                     num_boost_round=6)
    assert bst2.current_iteration() == 6
    new = _train_events()[before:]
    assert new == [], f"resumed run recompiled: {new}"


# ---------------------------------------------------------------------------
# donation: the round-to-round score buffer is updated in place


def test_donation_gated_to_accelerators(monkeypatch):
    """XLA:CPU's input-output aliasing corrupts donated buffers on this
    jax build (intermittent segfaults in later host reads), so donation
    must be OFF on the cpu backend by default, env-overridable, and ON
    for accelerator backends."""
    from lightgbm_tpu.models import gbdt as gbdt_mod

    monkeypatch.delenv("LIGHTGBM_TPU_DONATION", raising=False)
    assert jax.default_backend() == "cpu"
    assert not gbdt_mod._donation_enabled()
    monkeypatch.setenv("LIGHTGBM_TPU_DONATION", "1")
    assert gbdt_mod._donation_enabled()
    monkeypatch.setenv("LIGHTGBM_TPU_DONATION", "0")
    assert not gbdt_mod._donation_enabled()


def test_score_buffer_not_donated_on_cpu():
    """The gate in action: on the cpu backend the previous score buffer
    must survive an iteration (donating it is what corrupted memory)."""
    X, y = _make_binary(n=911, seed=1)
    b = _booster(X, y)
    b.train_one_iter()
    s0 = b.train_data.score
    b.train_one_iter()
    assert not s0.is_deleted()


def test_shared_step_registered_without_donation_under_guard():
    """nan_policy keeps a pre-iteration reference for rollback, so the
    guarded step must be registered donate=False regardless of backend;
    the guarded path still trains finite scores."""
    from lightgbm_tpu.models.gbdt import _SHARED_JITS

    X, y = _make_binary(n=911, seed=2)
    b = _booster(X, y, nan_policy="skip_tree")
    b.train_one_iter()
    s0 = b.train_data.score
    b.train_one_iter()
    assert not s0.is_deleted()
    # key layout: ("train_step", obj_key, num_class, guard, kind,
    # params, donate) — every guarded registration must be donate=False
    keys = [k for k in _SHARED_JITS if k[0] == "train_step"]
    assert any(k[3] for k in keys), "no guarded train_step registered"
    assert all(not k[-1] for k in keys if k[3])
    assert np.isfinite(b.train_data.host_score()).all()


def test_peak_live_bytes_flat_across_rounds():
    """memwatch bound: with donation, continuing to train must not grow
    the live-array watermark by more than one score buffer's worth of
    slack — a round-to-round double-allocation leak would."""
    from lightgbm_tpu.obs import memwatch

    X, y = _make_binary(n=1499, seed=4)
    b = _booster(X, y)
    for _ in range(3):
        b.train_one_iter()
    jax.block_until_ready(b.train_data.score)
    memwatch.reset_peak()
    base = memwatch.sample("test")["peak_live_bytes"]
    for _ in range(8):
        b.train_one_iter()
    jax.block_until_ready(b.train_data.score)
    peak = memwatch.sample("test")["peak_live_bytes"]
    score_bytes = int(np.asarray(b.train_data.score).nbytes)
    # the pipelined pending iteration legitimately holds one packed tree
    # + deltas; two score buffers of slack is far below the leak regime
    assert peak - base <= 2 * score_bytes + (1 << 20), \
        f"live watermark grew {peak - base} bytes over 8 rounds"


# ---------------------------------------------------------------------------
# row buckets: padded state invariants


def test_row_bucket_padding_preserves_model_and_crops_reads():
    X, y = _make_binary(n=987, seed=5)
    b_pad = _booster(X, y)
    b_off = _booster(X, y, row_buckets=False)
    assert b_pad._padded_rows == compile_cache.bucket_rows(987)
    assert b_off._padded_rows == 987
    for _ in range(3):
        b_pad.train_one_iter()
        b_off.train_one_iter()
    # identical split structure (exact int histogram sums); leaf values
    # may wiggle in the last float bit (reduction order vs shape)
    for t_pad, t_off in zip(b_pad.models, b_off.models):
        assert t_pad.num_leaves == t_off.num_leaves
        np.testing.assert_array_equal(t_pad.split_feature,
                                      t_off.split_feature)
        np.testing.assert_allclose(t_pad.leaf_value, t_off.leaf_value,
                                   rtol=1e-5, atol=1e-7)
    # host reads crop the pad
    assert b_pad.train_data.host_score().shape == (1, 987)
    assert np.asarray(b_pad.train_data.score).shape[1] == \
        compile_cache.bucket_rows(987)


def test_legacy_objective_subclass_still_trains():
    """Back-compat: a custom objective written against the pre-round-7
    contract (override gradients() only) must keep training — routed
    outside the shared registry (id-keyed) with row bucketing off, so
    its closure-captured arrays still match the score shapes."""
    import jax.numpy as jnp
    from lightgbm_tpu.objective import ObjectiveFunction

    class LegacySquares(ObjectiveFunction):
        name = "legacy_l2"

        def gradients(self, score):
            g = score[0] - self.label
            return g[None], jnp.ones_like(g)[None]

    X, y = _make_binary(n=640, seed=6)
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.gbdt import GBDT

    cfg = Config({"objective": "regression", "num_leaves": 7,
                  "min_data_in_leaf": 20, "max_bin": 63, "metric": "l2"})
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20)
    obj = LegacySquares()
    assert obj.uses_legacy_gradients()
    b = GBDT(cfg, ds, objective=obj)
    assert b._padded_rows == b.num_data  # bucketing opts out
    traj = []
    for _ in range(3):
        b.train_one_iter()
        traj.append(b.eval_metrics()["training"]["l2"])
    assert len(b.models) == 3
    assert np.isfinite(traj).all()
    assert traj[2] < traj[1] < traj[0], f"l2 not improving: {traj}"


def test_program_holder_drops_dataset_arrays():
    """The shared registry retains scalar-only holders: the per-dataset
    device arrays must NOT be reachable from a holder (registry pinning
    a dead dataset's HBM was the round-7 review finding)."""
    X, y = _make_binary(n=512, seed=7)
    b = _booster(X, y)
    holder = b.objective.program_holder()
    assert not hasattr(holder, "label")
    assert not hasattr(holder, "weights")
    # and the holder still traces: its gradients_with reads arrays from
    # the argument pytree only
    arrs = b.objective.gradient_arrays(b._padded_rows)
    g, h = holder.gradients_with(arrs, b.train_data.score)
    assert g.shape == b.train_data.score.shape


def test_bagging_never_draws_pad_rows():
    from lightgbm_tpu.models.gbdt import _device_bag_mask

    key = jax.random.PRNGKey(0)
    n_real, n_pad = 1000, 1024
    mask = np.asarray(_device_bag_mask(key, n_pad, 700, n_real))
    assert mask.shape == (n_pad,)
    assert int(mask.sum()) == 700
    assert mask[n_real:].sum() == 0
