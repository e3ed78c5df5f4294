"""Lambdarank size-class bucketing (objective/__init__.py): per-class
padding must not change the math — gradients are identical to padding
every query to the global maximum, and per-query lambda sums are zero
(pairwise antisymmetry, rank_objective.hpp:83-137)."""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Metadata
from lightgbm_tpu.objective import LambdarankNDCG


def _make(seed=0):
    rng = np.random.RandomState(seed)
    # heavily skewed query sizes: 17 small, one big (pad classes 4x apart)
    sizes = [5, 9, 17, 33] * 4 + [210]
    n = sum(sizes)
    label = rng.randint(0, 4, size=n).astype(np.float32)
    md = Metadata(n)
    md.set_label(label)
    md.set_query(np.asarray(sizes))
    score = rng.normal(size=(1, n)).astype(np.float32)
    return md, n, score


def test_bucketing_matches_single_class_padding(monkeypatch):
    md, n, score = _make()
    cfg = Config({"objective": "lambdarank"})

    obj = LambdarankNDCG(cfg)
    obj.init(md, n)
    assert len(obj.query_classes) > 1    # bucketing actually happened
    g1, h1 = obj.gradients(score)

    # force one global class: re-pad every bucket to the same width
    big = 256
    obj3 = LambdarankNDCG(cfg)
    obj3.init(md, n)
    import jax.numpy as jnp
    merged_idx, merged_valid, merged_label, merged_inv = [], [], [], []
    for cls in obj3.query_classes:
        P = cls["P"]
        pad = big - P
        merged_idx.append(np.pad(np.asarray(cls["doc_idx"]),
                                 ((0, 0), (0, pad))))
        merged_valid.append(np.pad(np.asarray(cls["doc_valid"]),
                                   ((0, 0), (0, pad))))
        merged_label.append(np.pad(np.asarray(cls["label"]),
                                   ((0, 0), (0, pad))))
        merged_inv.append(np.asarray(cls["inv_max_dcg"]))
    obj3.query_classes = [{
        "P": big,
        "doc_idx": jnp.asarray(np.concatenate(merged_idx)),
        "doc_valid": jnp.asarray(np.concatenate(merged_valid)),
        "label": jnp.asarray(np.concatenate(merged_label)),
        "inv_max_dcg": jnp.asarray(np.concatenate(merged_inv)),
    }]
    g2, h2 = obj3.gradients(score)

    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-5, atol=1e-6)


def test_per_query_lambda_sum_is_zero():
    md, n, score = _make(seed=3)
    cfg = Config({"objective": "lambdarank"})
    obj = LambdarankNDCG(cfg)
    obj.init(md, n)
    g, h = obj.gradients(score)
    g = np.asarray(g)[0]
    h = np.asarray(h)[0]
    qb = np.asarray(md.query_boundaries)
    for q in range(len(qb) - 1):
        seg = g[qb[q]:qb[q + 1]]
        np.testing.assert_allclose(seg.sum(), 0.0, atol=1e-4)
    assert np.all(h >= 0)
    assert np.isfinite(g).all() and np.isfinite(h).all()


@pytest.mark.parametrize("cutoffs,low,high", [
    ("1,3,5,10,100", 1, 40),    # ragged, some shorter than every cut-off
    ("1,3,5,10", 1, 9),         # the ranking cell's cut-offs; every query
                                # shorter than 10, some shorter than 3
    ("1,3,5,10", 11, 300),      # every query longer than the cut-offs
    ("10", 1, 3),               # the cut-off past every query
])
def test_rank_metrics_vectorized_match_naive_loop(cutoffs, low, high):
    """NDCG@k / MAP@k: the bucket-vectorized eval (round-3, replacing the
    per-query Python loop of round-2 VERDICT weak #7) must match a naive
    per-query reference on ragged weighted queries, including all-zero-
    relevance queries (NDCG 1.0 per the reference) and k > query size.
    The ranking cell's ``ndcg_gap`` leans on this NDCG (benchmarks/
    harness/correct_rank.py): cut-offs 1, 3, 5, 10 and queries shorter
    than the cut-off are cases of their own."""
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metric import create_metric

    rng = np.random.RandomState(5)
    sizes = rng.randint(low, high + 1, size=120)
    n = int(sizes.sum())
    labels = rng.randint(0, 5, size=n).astype(np.float64)
    # a few queries with zero relevance everywhere
    qb = np.concatenate([[0], np.cumsum(sizes)])
    for q in (3, 17):
        labels[qb[q]:qb[q + 1]] = 0
    scores = rng.normal(size=n)
    qweights = rng.uniform(0.5, 2.0, size=len(sizes))

    md = Metadata(n)
    md.set_label(labels)
    md.set_query(list(sizes))
    md.query_weights = qweights

    cfg = Config({"objective": "lambdarank", "metric": "ndcg,map",
                  "ndcg_at": cutoffs})
    ndcg = create_metric("ndcg", cfg)
    m_ap = create_metric("map", cfg)
    ndcg.init(md, n)
    m_ap.init(md, n)
    got_ndcg = ndcg.eval(scores[None, :])
    got_map = m_ap.eval(scores[None, :])

    gains = ndcg.label_gain
    eval_at = ndcg.eval_at
    want_ndcg = np.zeros(len(eval_at))
    want_map = np.zeros(len(eval_at))
    for q in range(len(sizes)):
        lbl = labels[qb[q]:qb[q + 1]].astype(np.int64)
        sc = scores[qb[q]:qb[q + 1]]
        nq = len(lbl)
        disc = 1.0 / np.log2(np.arange(nq) + 2.0)
        order = np.argsort(-sc, kind="stable")
        ideal = np.sort(lbl)[::-1]
        rel = lbl[order] > 0
        hits = np.cumsum(rel)
        prec = hits / (np.arange(nq) + 1.0)
        for i, k in enumerate(eval_at):
            kk = min(k, nq)
            max_dcg = (gains[ideal[:kk]] * disc[:kk]).sum()
            if max_dcg <= 0:
                want_ndcg[i] += qweights[q]
            else:
                dcg = (gains[lbl[order[:kk]]] * disc[:kk]).sum()
                want_ndcg[i] += dcg / max_dcg * qweights[q]
            nh = hits[kk - 1] if kk > 0 else 0
            want_map[i] += ((prec[:kk] * rel[:kk]).sum() / nh
                            if nh > 0 else 0.0) * qweights[q]
    sw = qweights.sum()
    np.testing.assert_allclose(got_ndcg, want_ndcg / sw, rtol=1e-9)
    np.testing.assert_allclose(got_map, want_map / sw, rtol=1e-9)


# ---- the pair gradient against a plain loop over pairs ---------------------

def _pair_loop(label, score, sizes, sigma=1.0, max_position=20):
    """LambdaRank's gradients and hessians by a plain loop over the
    documents of every query and, for each, over its pairs (the inner
    loop a numpy row), float64: rank_objective.hpp:83-137."""
    n = len(label)
    g, h = np.zeros(n), np.zeros(n)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    gain = 2.0 ** np.arange(31) - 1.0
    for q in range(len(sizes)):
        lo, hi = qb[q], qb[q + 1]
        s = score[lo:hi].astype(np.float64)
        lbl = label[lo:hi].astype(np.int64)
        cnt = hi - lo
        order = np.argsort(-s, kind="stable")
        rank = np.empty(cnt, np.int64)
        rank[order] = np.arange(cnt)
        disc = 1.0 / np.log2(rank + 2.0)
        k = min(max_position, cnt)
        ideal = np.sort(lbl)[::-1][:k]
        max_dcg = (gain[ideal] / np.log2(np.arange(k) + 2.0)).sum()
        inv = 1.0 / max_dcg if max_dcg > 0 else 0.0
        spread = s.max() != s.min()
        for i in range(cnt):                  # i the higher-labelled one
            low = lbl[i] > lbl
            if not low.any():
                continue
            gap = s[i] - s
            delta = (gain[lbl[i]] - gain[lbl]) * np.abs(disc[i] - disc) * inv
            if spread:
                delta = delta / (0.01 + np.abs(gap))
            p = 2.0 / (1.0 + np.exp(2.0 * sigma * gap))
            lam = np.where(low, p * delta, 0.0)
            hes = np.where(low, p * (2.0 - p) * 2.0 * delta, 0.0)
            g[lo + i] -= lam.sum()
            g[lo:hi] += lam
            h[lo + i] += hes.sum()
            h[lo:hi] += hes
    return g, h


def _case(name):
    rng = np.random.RandomState(11)
    sizes = [1, 2, 15, 16, 17, 130, 1100, 3, 64]
    n = sum(sizes)
    label = rng.randint(0, 5, size=n).astype(np.float32)
    score = rng.normal(size=n).astype(np.float32)
    if name == "equal_labels":              # the 130-document query
        lo = sum(sizes[:5])
        label[lo:lo + 130] = 2
    elif name == "tied_scores":             # ties within and across labels
        score = np.round(score * 2) / 2
    elif name == "zero_scores":             # round 1
        score[:] = 0.0
    return sizes, label, score


CASES = ["seeded", "equal_labels", "tied_scores", "zero_scores"]


def _objective(sizes, label, kernel):
    md = Metadata(len(label))
    md.set_label(label)
    md.set_query(np.asarray(sizes))
    obj = LambdarankNDCG(Config({"objective": "lambdarank"}))
    obj.use_kernel = kernel
    obj.init(md, len(label))
    return obj


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "slabs"])
@pytest.mark.parametrize("case", CASES)
def test_gradients_equal_the_plain_pair_loop(case, kernel):
    """``gradients_with`` in both forms (the ``jax.numpy`` classes; the
    slab frame with ``rank_lambda`` interpreted) against the plain loop,
    on query sizes 1, 2, 15, 16, 17, 130 and 1,100.  Tolerance: float32
    sums of up to 1,099 pair terms against float64 ones, 2e-5 of the
    largest gradient; zero where a query has no label-differing pair."""
    sizes, label, score = _case(case)
    obj = _objective(sizes, label, kernel)
    g, h = obj.gradients(score[None])
    g, h = np.asarray(g)[0], np.asarray(h)[0]
    want_g, want_h = _pair_loop(label, score, sizes)
    np.testing.assert_allclose(g, want_g, rtol=0,
                               atol=2e-5 * np.abs(want_g).max())
    np.testing.assert_allclose(h, want_h, rtol=0,
                               atol=2e-5 * np.abs(want_h).max())
    assert (g[:1] == 0).all() and (h[:1] == 0).all()     # the lone document
    if case == "equal_labels":
        lo = sum(sizes[:5])
        assert (g[lo:lo + 130] == 0).all() and (h[lo:lo + 130] == 0).all()


@pytest.mark.parametrize("case", CASES)
def test_rank_lambda_interpreted_equals_the_oracle(case):
    """The kernel (``interpret=True``) against the ``jax.numpy`` oracle.
    Not bit for bit: the oracle sums a document's pairs in rank order,
    the kernel in position order folded eight sublanes at a time, and the
    kernel computes ``ln 2 / log(2 + rank)`` where the oracle looks
    ``1 / log2`` up in a table rounded from float64.  Both are float32
    roundings of the same terms: 4e-6 of the largest value."""
    sizes, label, score = _case(case)
    g0, h0 = _objective(sizes, label, False).gradients(score[None])
    g1, h1 = _objective(sizes, label, True).gradients(score[None])
    for a, b in ((g0, g1), (h0, h1)):
        a, b = np.asarray(a)[0], np.asarray(b)[0]
        np.testing.assert_allclose(b, a, rtol=0, atol=4e-6 * np.abs(a).max())
        assert ((a == 0) == (b == 0)).all()


def test_slab_tables_count_what_they_pad():
    """The host's bucketing: every document is its own query's in exactly
    one slab slot, the pad's queries have no rows, and the counters say
    what was bucketed."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.ops import rank_lambda
    sizes, label, _ = _case("seeded")
    obj = _objective(sizes, label, True)
    seen = np.zeros(len(label), np.int64)
    for c in obj.query_slabs:
        lab, row0 = np.asarray(c["lab"]), np.asarray(c["row0"])
        assert lab.shape[0] % rank_lambda.QUERIES_PER_STEP == 0
        assert (np.diff(row0) >= 0).all()
        R = lab.shape[1]
        pos = (row0[:, None] + np.arange(R))[:, :, None] * 128 \
            + np.arange(128)
        own = lab >= 0
        np.add.at(seen, pos[own], 1)
        assert (lab[own] == label[pos[own]]).all()
        assert (np.asarray(c["nr"]) <= R).all()
    assert (seen == 1).all()
    snap = obs.snapshot()["gauges"]
    assert snap["rank_queries"] == len(sizes)
    assert snap["rank_size_classes"] == len(obj.query_slabs)
    per_query = np.split(label, np.cumsum(sizes)[:-1])
    pairs = sum((len(l) ** 2 - (np.bincount(l.astype(int)) ** 2).sum()) // 2
                for l in per_query)
    assert snap["rank_pairs_real"] == pairs
    assert snap["rank_pair_slots"] >= 2 * pairs


def test_three_rounds_pass_the_ranking_cells_own_comparison(monkeypatch):
    """Three rounds of ``lightgbm_tpu.train`` with ``group=`` at the
    ranking cell's configuration (136 columns, 255 leaves, the source's
    settings) against the plain ranking reference, through the comparison
    and the limits the cell itself uses (benchmarks/harness/
    correct_rank.py, workloads/mslr30k-lambdarank-train.json), at the
    cell's rehearsal size."""
    import os
    import sys
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench_run
    from harness import correct_rank, data_rank, reference, result
    import lightgbm_tpu as lgb
    # the comparison puts the ranking objective in the binary reference's
    # place for the process; the sparse cell's tests, which may run in the
    # same worker after this one, need the binary one back
    monkeypatch.setattr(reference, "objective", reference.objective)

    cell = bench_run.load_cell("mslr30k-lambdarank-train")
    cfg = cell["config_file"]
    assert cfg["num_features"] == 136 and cfg["params"]["num_leaves"] == 255
    # the cell's rehearsal size: at a third of it the trees stop at a few
    # dozen leaves whose gains are small enough for float32 to show
    X, y, group = data_rank.make(cfg["data"], cell["rehearse"]["num_data"],
                                 2147483659)
    params = dict(cfg["params"])
    ds = lgb.Dataset(X, label=y, group=group, params=dict(params))
    ds.construct()
    bounds = [np.asarray(m.bin_upper_bound, np.float64)
              for m in ds._binned.mappers]
    names = [f"ndcg@{k}" for k in cfg["stated"]["eval_at"]]
    ndcg = []

    def read(env):
        jax.block_until_ready(env.model._booster.train_data.score)
        got = {n: float(v) for _, n, v, _ in env.model.eval_train()}
        ndcg.append([got[n] for n in names])
    booster = lgb.train(params, ds, num_boost_round=3, verbose_eval=False,
                        callbacks=[read])
    trees = booster.dump_model(num_iteration=3)["tree_info"]
    compared, notes = correct_rank.check_train(
        X, y, group, bounds, trees, ndcg, cfg, cell["limits"])
    assert result.verdict(compared), (compared, notes)


def test_a_parallel_learner_keeps_the_form_the_partitioner_can_split(
        monkeypatch):
    """One program over several devices cannot hold the Pallas kernel (a
    Mosaic kernel is not partitioned automatically), so under a learner's
    mesh the objective keeps the ``jax.numpy`` form whatever the backend,
    and the sharded round grows the serial round's trees."""
    import lightgbm_tpu as lgb
    init = LambdarankNDCG.__init__

    def as_on_a_tpu(self, config):
        init(self, config)
        self.use_kernel = True
    monkeypatch.setattr(LambdarankNDCG, "__init__", as_on_a_tpu)
    rng = np.random.RandomState(3)
    sizes = [1, 40, 130, 200, 29, 300, 77, 23]
    X = rng.normal(size=(sum(sizes), 6))
    y = rng.randint(0, 5, size=len(X)).astype(np.float64)
    params = {"objective": "lambdarank", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1e-3}
    out = {}
    for learner in ("serial", "data"):
        b = lgb.train(dict(params, tree_learner=learner, num_machines=4),
                      lgb.Dataset(X, label=y, group=sizes),
                      num_boost_round=2, verbose_eval=False)
        obj = b._booster.objective
        assert obj.use_kernel == (learner == "serial")
        assert ("slabs" in obj.gradient_arrays()) == (learner == "serial")
        out[learner] = b.predict(X)
    np.testing.assert_allclose(out["data"], out["serial"], atol=1e-6)
