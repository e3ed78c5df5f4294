"""GOSS as a stage of the fused round (ops/goss.py, models/goss.py).

The draw against a plain numpy one (the top set by a stable argsort, the
rest by the documented stateless hash), the ``goss_sample`` kernel
interpreted against its ``jax.numpy`` form, the sampled tree against the
cached grower's over the same weights, one compiled ``train_step`` for
the rounds that sample and those that do not, and resume."""

import numpy as np
import pytest
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import goss as goss_ops
from lightgbm_tpu.ops.goss import GossParams


def _fmix32(x):
    x = np.asarray(x, np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def plain_draw(score, real, top_cnt, seed, iteration, params):
    """The draw as docs/Parameters.md states it, in numpy."""
    n = len(score)
    order = np.argsort(-np.where(real, score, -np.inf), kind="stable")
    top = np.zeros(n, bool)
    top[order[:max(top_cnt, 0)]] = True
    top &= real
    key = _fmix32(np.uint32(seed) ^ _fmix32(np.uint32(iteration)
                                            + np.uint32(0x9E3779B9)))
    u = _fmix32(np.arange(n, dtype=np.uint32) * np.uint32(0x9E3779B9)
                + key) >> np.uint32(8)
    other = real & ~top & (u < params.keep_threshold())
    return ((top | other).astype(np.float32),
            np.where(other, np.float32(params.amplification()),
                     np.float32(1.0)))


def _scores(kind, n, rng):
    if kind == "ties":
        return rng.randint(0, 5, n).astype(np.float32) * 0.25
    if kind == "equal":
        return np.full(n, 0.5, np.float32)
    if kind == "zeros":
        return np.zeros(n, np.float32)
    return rng.exponential(size=n).astype(np.float32)


@pytest.mark.parametrize("kind,n,top_share", [
    ("random", 1000, 0.2), ("ties", 5000, 0.2), ("equal", 3000, 0.2),
    ("zeros", 3000, 0.3), ("random", 3000, 0.0), ("random", 3000, 0.999),
    ("ties", 3000, 2.0), ("random", 140000, 0.2)])
def test_goss_sample_matches_the_plain_draw(kind, n, top_share):
    """Exactly ``top_cnt`` rows by score, the lower index first among
    ties; none where ``top_cnt`` is 0 and every real row where it passes
    them; pad rows never drawn; the kernel (interpreted), its
    ``jax.numpy`` form and numpy agree to the row.  140,000 rows take two
    of the kernel's blocks and five index passes."""
    rng = np.random.RandomState(n)
    score = _scores(kind, n, rng)
    real = np.ones(n, bool)
    real[-37:] = False
    keys = jnp.asarray(np.where(real, score.view(np.int32), -1), jnp.int32)
    params = GossParams(0.2, 0.1)
    top_cnt = int(top_share * (n - 37))
    want_mask, want_factor = plain_draw(score, real, top_cnt, 3, 11, params)
    draw = goss_ops.draw_key(3, 11)
    kw = dict(keep_thr=params.keep_threshold(), amp=params.amplification())
    for mask, factor in (goss_ops.select_jnp(keys, top_cnt, draw, **kw),
                         goss_ops.goss_sample(keys, top_cnt, draw, **kw,
                                              interpret=True)):
        np.testing.assert_array_equal(np.asarray(mask), want_mask)
        np.testing.assert_array_equal(np.asarray(factor), want_factor)
        assert not np.asarray(mask)[~real].any()
    top = want_mask.astype(bool) & (want_factor == 1.0)
    assert top.sum() == min(max(top_cnt, 0), n - 37)


def test_draw_is_the_same_under_any_padding():
    """The random part is a function of (seed, iteration, row): a row's
    draw does not move with the rows padded behind it."""
    rows = jnp.arange(5000, dtype=jnp.int32)
    key = goss_ops.draw_key(12345, 40)
    a = np.asarray(goss_ops.row_draw(key, rows))
    b = np.asarray(goss_ops.row_draw(key, jnp.arange(9000,
                                                     dtype=jnp.int32)))
    np.testing.assert_array_equal(a, b[:5000])
    assert 0.11 < (a < GossParams(0.2, 0.1).keep_threshold()).mean() < 0.14


@pytest.mark.parametrize("n", [9000, 40000])
def test_sampled_tree_matches_the_cached_growers(n):
    """The round's stage against the plain grower: the drawn weights and
    amplified gradients grown by the leaf-ordered grower with the kept
    rows compacted by the partition, and by ops/grow.py's cached grower
    over every row with the same weights: the same tree, and every row's
    leaf and delta, the rows outside the sample routed through the tree.
    40,000 rows take three size classes."""
    from lightgbm_tpu.ops.grow import GrowParams, grow_tree
    from lightgbm_tpu.ops.ordered_grow import grow_tree_ordered
    rng = np.random.RandomState(n)
    f = 6
    bins = rng.randint(0, 32, size=(f, n)).astype(np.uint8)
    grad = rng.normal(size=(1, n)).astype(np.float32)
    hess = (np.abs(rng.normal(size=(1, n))) + 0.1).astype(np.float32)
    real = np.ones(n, np.float32)
    real[-100:] = 0.0
    params = GossParams(0.2, 0.1)
    top_cnt = int(0.2 * (n - 100))
    state = jnp.asarray([14, 7, 1, top_cnt], jnp.int32)
    w, factor = goss_ops.sample(jnp.asarray(grad), jnp.asarray(hess),
                                jnp.asarray(real), state, params)
    score = np.abs(grad * hess).sum(axis=0)
    want_w, want_f = plain_draw(score, real > 0, top_cnt, 7, 14, params)
    np.testing.assert_array_equal(np.asarray(w), want_w)
    np.testing.assert_array_equal(np.asarray(factor), want_f)

    g = jnp.asarray(grad[0]) * factor
    h = jnp.asarray(hess[0]) * factor
    num_bin = jnp.full(f, 32, jnp.int32)
    is_cat = jnp.asarray(np.arange(f) == 1)
    mask = jnp.ones(f, bool)
    gp = GrowParams(num_leaves=15, max_bin=32, min_data_in_leaf=20,
                    min_sum_hessian_in_leaf=1.0)
    lr = jnp.float32(0.1)
    t_ref, leaf_ref, delta_ref = grow_tree(
        jnp.asarray(bins), num_bin, is_cat, mask, g, h, w, lr, gp)
    t_cmp, leaf_cmp, delta_cmp = grow_tree_ordered(
        jnp.asarray(bins), num_bin, is_cat, mask, g, h, w, lr,
        gp._replace(compact_inactive=True))
    assert int(t_cmp.num_leaves) == int(t_ref.num_leaves) == 15
    for field in t_ref._fields:
        np.testing.assert_array_equal(np.asarray(getattr(t_cmp, field)),
                                      np.asarray(getattr(t_ref, field)),
                                      err_msg=field)
    np.testing.assert_array_equal(np.asarray(leaf_cmp),
                                  np.asarray(leaf_ref))
    np.testing.assert_array_equal(np.asarray(delta_cmp),
                                  np.asarray(delta_ref))


def _binary(n=3000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.normal(size=n) > 0)
    return X, y.astype(float)


GOSS_PARAMS = {"objective": "binary", "boosting_type": "goss",
               "top_rate": 0.2, "other_rate": 0.1, "num_leaves": 7,
               "min_data_in_leaf": 20, "max_bin": 63, "learning_rate": 0.5,
               "verbose": -1}


def test_goss_rounds_are_one_train_step(monkeypatch):
    """Two unsampled rounds (1 / learning_rate), then sampled ones: one
    ``train_step`` compile for all of them, no per-stage program, the
    draw's counter and gauges set; and the per-stage path
    (``LGBT_NO_FUSED_STEP``), which runs the same stage alone, grows the
    same model to the byte."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import compile_ledger
    X, y = _binary()
    params = {**GOSS_PARAMS, "bagging_seed": 11}
    before = len(compile_ledger.events())
    sampled0 = obs.snapshot()["counters"].get("goss_sampled_rounds", 0)
    fused = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    programs = [e["program"] for e in compile_ledger.events()[before:]]
    assert programs.count("train_step") <= 1
    assert not {"goss_draw", "grow_tree_ordered"} & set(programs)
    snap = obs.snapshot()
    assert snap["counters"]["goss_sampled_rounds"] - sampled0 == 6
    assert snap["gauges"]["goss_top_rows"] == int(0.2 * 3000)
    assert snap["gauges"]["goss_other_rows"] == int(0.1 * 3000)
    w = np.asarray(fused._booster._row_weight)
    f = np.asarray(fused._booster._goss_factor)
    assert np.count_nonzero(w * (f == 1.0)) == int(0.2 * 3000)
    assert 0 < np.count_nonzero(f > 1.0) < 3000 // 4

    monkeypatch.setenv("LGBT_NO_FUSED_STEP", "1")
    staged = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    assert staged.model_to_string() == fused.model_to_string()


def test_goss_resumes_from_a_snapshot_with_or_without_a_key():
    """The draw needs no generator state: a booster restored from four
    rounds' state (with the ``goss`` key of snapshots taken before the
    draw was stateless, or without it) grows the uninterrupted run's
    model."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    from lightgbm_tpu.models.goss import GOSS
    X, y = _binary(seed=2)
    cfg = Config({**GOSS_PARAMS, "learning_rate": 0.3})
    ds = BinnedDataset.from_matrix(X, y, max_bin=63, min_data_in_leaf=20)
    whole = GOSS(cfg, ds)
    for _ in range(8):
        whole.train_one_iter()
    for old_key in (False, True):
        first = GOSS(cfg, ds)
        for _ in range(4):
            first.train_one_iter()
        state = first.snapshot_state()
        if old_key:
            state["goss"] = {"key": np.zeros(2, np.uint32)}
        resumed = GOSS(cfg, ds)
        resumed.restore_state(state)
        for _ in range(4):
            resumed.train_one_iter()
        assert resumed.save_model_to_string() == whole.save_model_to_string()


def test_sharded_goss_grows_the_serial_model():
    """The draw is a function of (seed, iteration, row): under the
    data-parallel learner (rows in blocks over four devices, the
    ``jax.numpy`` form of the select on the global rows) the same rows
    are drawn and the same model grows as on one device."""
    X, y = _binary(n=8000, seed=4)
    serial = lgb.train(GOSS_PARAMS, lgb.Dataset(X, label=y),
                       num_boost_round=6)
    sharded = lgb.train({**GOSS_PARAMS, "tree_learner": "data",
                         "num_machines": 4}, lgb.Dataset(X, label=y),
                        num_boost_round=6)
    np.testing.assert_array_equal(sharded.predict(X), serial.predict(X))
    np.testing.assert_array_equal(
        np.asarray(sharded._booster._row_weight)[:8000],
        np.asarray(serial._booster._row_weight)[:8000])
