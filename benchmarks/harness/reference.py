"""The plain reference: a leaf-wise histogram GBDT for the binary
objective in straightforward ``numpy`` / ``jax.numpy``.

It imports nothing of the program.  It bins the raw rows itself, routes
them itself, sums its own gradients into its own histograms (a one-hot
matrix product in float32 at ``highest`` precision; counts are exact),
and searches its own splits in float64 on the host.  Two uses:

- ``follow``: teacher-forced over a tree that someone else grew (the
  program, or ``grow`` below put in the program's place).  It walks the
  raw rows down that tree's structure and says, from its own gradients,
  what every node's count and value should have been and how far each
  chosen split lies below the best split the reference sees at that node.
- ``grow``: best-first growth with its own argmax, so that the reference
  can stand in the program's place for the lower-precision control and
  the planted faults (``control.py``).  ``precision="bfloat16"`` computes
  gradients, histogram products, leaf values and scores in bfloat16.
"""

from __future__ import annotations

import functools
import time

import numpy as np

CHUNK = 16384


# ---- binning ---------------------------------------------------------------

def floor_f32(bounds: np.ndarray) -> np.ndarray:
    """The largest float32 at or under each float64 bound, so that for a
    float32 ``x``: ``x <= bound`` exactly when ``x <= floor_f32(bound)``."""
    b32 = bounds.astype(np.float32)
    over = b32.astype(np.float64) > bounds
    b32[over] = np.nextafter(b32[over], np.float32(-np.inf))
    return b32


def quantile_bounds(X: np.ndarray, max_bin: int, seed: int,
                    sample: int = 200000):
    """Plain equal-count bin bounds of the reference's own, for the runs in
    which it stands in the program's place: midpoints between neighbouring
    sample quantiles, the last bound infinite."""
    rng = np.random.RandomState(seed % (2 ** 32))
    idx = rng.choice(X.shape[0], min(sample, X.shape[0]), replace=False)
    out = []
    for f in range(X.shape[1]):
        v = np.sort(X[idx, f].astype(np.float64))
        cuts = []
        for i in range(1, max_bin):
            j = int(round(i * len(v) / max_bin))
            while 0 < j < len(v) and v[j - 1] == v[j]:
                j += 1                  # a cut falls between distinct values
            if 0 < j < len(v):
                cuts.append((v[j - 1] + v[j]) / 2.0)
        out.append(np.asarray(sorted(set(cuts)) + [np.inf]))
    return out


def check_bounds(bounds, max_bin: int) -> int:
    """How many features' bound tables break the rules a bin table keeps
    whoever made it: at most ``max_bin`` bins, strictly rising, the last
    infinite."""
    bad = 0
    for b in bounds:
        b = np.asarray(b, np.float64)
        if len(b) < 1 or len(b) > max_bin or not np.isinf(b[-1]) \
                or np.any(np.diff(b) <= 0):
            bad += 1
    return bad


class Rows:
    """The raw rows on the device, binned by the reference: ``binsT``
    [F, Np] uint8, labels, a mask of real rows; Np is N padded to whole
    chunks."""

    def __init__(self, X32: np.ndarray, y: np.ndarray, bounds):
        import jax
        import jax.numpy as jnp
        n, f = X32.shape
        self.n, self.f = n, f
        self.n_bins = max(len(b) for b in bounds)
        self.B = max(8, 1 << (self.n_bins - 1).bit_length())
        pad = (-n) % CHUNK
        self.np_rows = n + pad
        table = np.full((f, self.B - 1), np.inf, np.float32)
        for i, b in enumerate(bounds):
            table[i, :len(b) - 1] = floor_f32(np.asarray(b[:-1], np.float64))

        @jax.jit
        def bin_all(xt, tab):
            idx = jax.vmap(lambda t, x: jnp.searchsorted(t, x, side="left"))(
                tab, xt)
            return idx.astype(jnp.uint8)

        xt = jnp.asarray(np.ascontiguousarray(X32.T))
        bins = bin_all(xt, jnp.asarray(table))
        del xt
        self.binsT = jnp.pad(bins, ((0, 0), (0, pad)))
        self.valid = jnp.arange(self.np_rows) < n
        self.y = jnp.pad(jnp.asarray(y, jnp.float32), (0, pad))
        self.y_sign = np.where(np.asarray(y) > 0, 1.0, -1.0).astype(
            np.float32)                                 # host, real rows


# ---- device pieces ---------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _kernels(precision: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    low = precision == "bfloat16"

    @jax.jit
    def route(binsT, valid, leaf_of, feat, thr):
        """Walk every row down the tree: split k sends the rows of leaf
        ``leaf_of[k]`` whose bin lies over ``thr[k]`` to the new leaf
        ``k + 1``.  Rows of the pad stay at -1."""
        def step(k, leaf):
            col = lax.dynamic_index_in_dim(binsT, feat[k], 0, keepdims=False)
            go = (leaf == leaf_of[k]) & (col.astype(jnp.int32) > thr[k])
            return jnp.where(go, k + 1, leaf)
        start = jnp.where(valid, 0, -1).astype(jnp.int32)
        return lax.fori_loop(0, feat.shape[0], step, start)

    @functools.partial(jax.jit, static_argnames=("B", "L"))
    def histograms(binsT, group, g, h, B, L):
        """[F, B, 3 L]: per feature and bin, the sums of gradient, hessian
        and one over the rows of each group (a leaf, or 0 for a mask);
        rows whose group is negative count nowhere.

        A one-hot matrix product in float32 at ``highest`` precision
        (the chip's default would round both operands to bfloat16); the
        control (``low``) runs it in bfloat16.  Do not split the float32
        weights into bfloat16 pieces by hand instead: the chip's compiler
        drops a float32 -> bfloat16 -> float32 round trip
        (``xla_allow_excess_precision``) and the pieces collapse into one,
        which read like the control (my chip run, PR 27, call 3)."""
        F, Np = binsT.shape
        bins_iota = jnp.arange(B, dtype=jnp.int32)
        grp_iota = jnp.arange(L, dtype=jnp.int32)
        wt = jnp.bfloat16 if low else jnp.float32
        prec = None if low else lax.Precision.HIGHEST

        def body(acc, i):
            b = lax.dynamic_slice_in_dim(binsT, i * CHUNK, CHUNK, 1)
            l = lax.dynamic_slice_in_dim(group, i * CHUNK, CHUNK)
            gg = lax.dynamic_slice_in_dim(g, i * CHUNK, CHUNK)
            hh = lax.dynamic_slice_in_dim(h, i * CHUNK, CHUNK)
            ob = (b.astype(jnp.int32)[:, :, None] == bins_iota).astype(wt)
            ol = (l[:, None] == grp_iota).astype(jnp.float32)
            a = jnp.concatenate([ol * gg[:, None], ol * hh[:, None], ol],
                                axis=1).astype(wt)
            part = jnp.einsum("fcb,ck->fbk", ob, a, precision=prec,
                              preferred_element_type=jnp.float32)
            return acc + part, None

        acc0 = jnp.zeros((F, B, 3 * L), jnp.float32)
        acc, _ = lax.scan(body, acc0, jnp.arange(Np // CHUNK))
        return acc

    @jax.jit
    def add_leaf_values(score, leaf, values):
        """score + values[leaf], rows of the pad (leaf -1) unchanged.  A
        select per leaf and not a gather: the chip takes seconds to gather
        11M entries from a table of 63 (my chip run, PR 27, call 5)."""
        def step(l, acc):
            return jnp.where(leaf == l, values[l], acc)
        v = lax.fori_loop(0, values.shape[0], step,
                          jnp.zeros(leaf.shape, jnp.float32))
        return (score.astype(jnp.float32) + v).astype(score.dtype)

    @jax.jit
    def split_rows(binsT, leaf, which, feat, thr, new):
        col = lax.dynamic_index_in_dim(binsT, feat, 0, keepdims=False)
        go = (leaf == which) & (col.astype(jnp.int32) > thr)
        return jnp.where(go, new, leaf)

    return dict(route=route, histograms=histograms,
                add_leaf_values=add_leaf_values, split_rows=split_rows)


# ---- the objective ---------------------------------------------------------

def objective(score, rows, sigmoid, low=False):
    """From the scores (a device array; the first ``rows.n`` are real):
    the binary log-loss over the real rows, and every row's gradient and
    hessian as float32 device arrays (rounded to bfloat16 where ``low``).
    Gradients in plain float32 on the device.  The loss on the host,
    float32 terms summed in float64: the chip's log and exp are good to
    about 1e-5 of a loss, where the program's own read-out (float64 on
    the host) and this one agree to 1e-8."""
    import jax.numpy as jnp
    s32 = score.astype(jnp.float32)
    lbl = jnp.where(rows.y > 0, 1.0, -1.0)
    resp = -lbl * sigmoid / (1.0 + jnp.exp(lbl * sigmoid * s32))
    a = jnp.abs(resp)
    g, h = resp, a * (sigmoid - a)
    if low:
        g, h = g.astype(jnp.bfloat16), h.astype(jnp.bfloat16)
    z = rows.y_sign * np.float32(sigmoid) * np.asarray(s32)[:rows.n]
    loss = float(np.logaddexp(np.float32(0), -z).mean(dtype=np.float64))
    return loss, g.astype(jnp.float32), h.astype(jnp.float32)


# ---- host pieces: split search in float64 ---------------------------------

def leaf_gain(g, h, l2):
    return g * g / (h + l2)


def leaf_value(g, h, l2):
    return -g / (h + l2)


def split_gains(hist, stated, min_data, n_bins_f):
    """Gains of every (feature, threshold) of one node, parent's own gain
    taken off; -inf where the split is not allowed.  ``hist`` is
    [F, B, 3] float64."""
    l2 = float(stated["lambda_l2"])
    tot = hist[0].sum(axis=0)
    G, H, C = tot
    cum = np.cumsum(hist, axis=1)
    lg, lh, lc = cum[..., 0], cum[..., 1], cum[..., 2]
    rg, rh, rc = G - lg, H - lh, C - lc
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = leaf_gain(lg, lh, l2) + leaf_gain(rg, rh, l2)
    shift = leaf_gain(G, H, l2) + float(stated["min_gain_to_split"])
    t = np.arange(hist.shape[1])[None, :]
    ok = t < (np.asarray(n_bins_f)[:, None] - 1)
    ok &= (lc >= min_data) & (rc >= min_data)
    ok &= (lh >= stated["min_sum_hessian_in_leaf"]) \
        & (rh >= stated["min_sum_hessian_in_leaf"])
    ok &= gain > shift
    return np.where(ok, gain - shift, -np.inf)


# ---- a tree as arrays ------------------------------------------------------

def parse_tree(tree: dict, bounds):
    """``dump_model``'s nested tree as arrays in split order.  For split k:
    the leaf it split (its left child keeps that index, the right child
    becomes leaf k + 1), the feature, the threshold's bin in ``bounds``.
    Raises where the structure is not such a tree or a threshold is no
    bound of its feature."""
    root = tree["tree_structure"]
    n_leaves = int(tree["num_leaves"])
    n_splits = n_leaves - 1
    S = {"feat": np.zeros(n_splits, np.int32),
         "thr": np.zeros(n_splits, np.int32),
         "leaf_of": np.zeros(n_splits, np.int32),
         "count": np.zeros(n_splits, np.int64),
         "value": np.zeros(n_splits), "gain": np.zeros(n_splits),
         "parent": np.full(n_splits, -1, np.int32),
         "leaves": [[] for _ in range(n_splits)]}
    leaf = {"value": np.zeros(n_leaves), "count": np.zeros(n_leaves, np.int64),
            "parent": np.zeros(n_leaves, np.int32)}
    seen = set()

    def leftmost(node):
        while "split_index" in node:
            node = node["left_child"]
        return int(node["leaf_index"])

    def walk(node, parent):
        if "split_index" not in node:
            i = int(node["leaf_index"])
            leaf["value"][i] = float(node["leaf_value"])
            leaf["count"][i] = int(node["leaf_count"])
            leaf["parent"][i] = parent
            return [i]
        k = int(node["split_index"])
        if k in seen or not 0 <= k < n_splits:
            raise ValueError(f"split index {k} twice or out of range")
        seen.add(k)
        f = int(node["split_feature"])
        b = np.asarray(bounds[f], np.float64)
        thr = float(node["threshold"])
        t = int(np.searchsorted(b, thr, side="left"))
        if t >= len(b) - 1 or not np.isclose(b[t], thr, rtol=1e-12, atol=0):
            raise ValueError(f"split {k}: threshold {thr!r} is no bound of "
                             f"feature {f}")
        if node.get("decision_type", "no_greater") != "no_greater":
            raise ValueError("only numerical splits are read here")
        S["feat"][k], S["thr"][k] = f, t
        S["leaf_of"][k] = leftmost(node)
        if leftmost(node["right_child"]) != k + 1:
            raise ValueError(f"split {k}: right child is not leaf {k + 1}")
        S["count"][k] = int(node["internal_count"])
        S["value"][k] = float(node["internal_value"])
        S["gain"][k] = float(node["split_gain"])
        S["parent"][k] = parent
        S["leaves"][k] = walk(node["left_child"], k) \
            + walk(node["right_child"], k)
        return S["leaves"][k]

    if n_leaves > 1:
        walk(root, -1)
        if len(seen) != n_splits:
            raise ValueError("tree has fewer splits than leaves - 1")
    return S, leaf


# ---- follow: teacher-forced over someone's trees ---------------------------

def follow(rows: Rows, trees, bounds, cfg, precision="float32"):
    """Walks the trees in order with the reference's own gradients and
    scores.  Returns per tree what the comparison needs."""
    import jax.numpy as jnp
    K = _kernels(precision)
    stated = cfg["stated"]
    lr = float(cfg["learning_rate"])
    l2 = float(stated["lambda_l2"])
    sig = float(stated["sigmoid"])
    min_data = int(cfg["min_data_in_leaf"])
    n_bins_f = [len(b) for b in bounds]
    score = jnp.full((rows.np_rows,), float(stated["init_score"]),
                     jnp.float32)
    out = []
    spent = {"objective": 0.0, "route": 0.0, "histograms": 0.0, "host": 0.0}

    def timed(what, t0, *wait):
        import jax
        jax.block_until_ready(wait)
        spent[what] += time.time() - t0
        return time.time()

    t = time.time()
    _, g, h = objective(score, rows, sig)
    t = timed("objective", t, g, h)
    for tree in trees:
        S, leaf = parse_tree(tree, bounds)
        L = len(leaf["value"])
        leaf_id = K["route"](rows.binsT, rows.valid, jnp.asarray(S["leaf_of"]),
                             jnp.asarray(S["feat"]), jnp.asarray(S["thr"]))
        t = timed("route", t, leaf_id)
        hist = np.asarray(K["histograms"](rows.binsT, leaf_id, g, h,
                                          B=rows.B, L=L), np.float64)
        t = timed("histograms", t)
        hist = hist.reshape(rows.f, rows.B, 3, L)       # g, h, count by leaf
        per_leaf = np.transpose(hist, (3, 0, 1, 2))     # [L, F, B, 3]
        tot_leaf = per_leaf[:, 0].sum(axis=1)           # [L, 3]
        ref_leaf_value = lr * leaf_value(tot_leaf[:, 0], tot_leaf[:, 1], l2)
        # every node's histogram is the sum of its leaves'
        node = {"count": [], "value": [], "best": [], "chosen": []}
        for k in range(len(S["feat"])):
            hk = per_leaf[S["leaves"][k]].sum(axis=0)
            G, H, C = hk[0].sum(axis=0)
            gains = split_gains(hk, stated, min_data, n_bins_f)
            node["count"].append(int(round(C)))
            node["value"].append(lr * leaf_value(G, H, l2))
            node["best"].append(float(gains.max()))
            node["chosen"].append(float(gains[S["feat"][k], S["thr"][k]]))
        leaf_best = [float(split_gains(per_leaf[i], stated, min_data,
                                       n_bins_f).max()) for i in range(L)]
        t = timed("host", t)
        score = K["add_leaf_values"](
            score, leaf_id, jnp.asarray(ref_leaf_value, jnp.float32))
        loss, g, h = objective(score, rows, sig)
        t = timed("objective", t, g, h)
        out.append({"S": S, "leaf": leaf,
                    "ref_leaf_count": np.rint(tot_leaf[:, 2]).astype(np.int64),
                    "ref_leaf_value": ref_leaf_value,
                    "ref_node_count": np.asarray(node["count"], np.int64),
                    "ref_node_value": np.asarray(node["value"]),
                    "best": np.asarray(node["best"]),
                    "chosen": np.asarray(node["chosen"]),
                    "leaf_best": np.asarray(leaf_best), "loss": loss,
                    "occupancy": per_leaf[..., 2].sum(axis=0)})   # [F, B]
    out[0]["spent"] = {k: round(v, 1) for k, v in spent.items()}
    return out


# ---- grow: the reference in the program's place ----------------------------

def grow(rows: Rows, bounds, cfg, n_trees: int, precision="float32",
         fault=None):
    """Best-first trees with the reference's own argmax, in ``dump_model``'s
    form, and the loss after each.  ``fault`` plants one of: ``half_batch``
    (the second half of the rows left out of every sum), ``state_unchanged``
    (scores never updated), ``leaf_altered`` (the largest leaf value of the
    last tree 1% off)."""
    import jax.numpy as jnp
    K = _kernels(precision)
    stated = cfg["stated"]
    lr = float(cfg["learning_rate"])
    l2 = float(stated["lambda_l2"])
    sig = float(stated["sigmoid"])
    min_data = int(cfg["min_data_in_leaf"])
    max_leaves = int(cfg["num_leaves"])
    n_bins_f = [len(b) for b in bounds]
    low = precision == "bfloat16"
    sdt = jnp.bfloat16 if low else jnp.float32
    score = jnp.full((rows.np_rows,), float(stated["init_score"]), sdt)
    counted = rows.valid
    if fault == "half_batch":
        counted = rows.valid & (jnp.arange(rows.np_rows) < rows.n // 2)
    trees, losses = [], []

    def hist_of(leaf_id, which, g, h):
        grp = jnp.where(counted & (leaf_id == which), 0, -1)
        return np.asarray(K["histograms"](rows.binsT, grp, g, h, B=rows.B,
                                          L=1), np.float64)

    def best_of(hk):
        gains = split_gains(hk, stated, min_data, n_bins_f)
        f, t = np.unravel_index(int(np.argmax(gains)), gains.shape)
        return float(gains[f, t]), int(f), int(t)

    _, g, h = objective(score, rows, sig, low)
    for ti in range(n_trees):
        leaf_id = jnp.where(rows.valid, 0, -1).astype(jnp.int32)
        hists = {0: hist_of(leaf_id, 0, g, h)}
        best = {0: best_of(hists[0])}
        nodes = {}                          # leaf index -> node dict (tree)
        root_holder = {"node": None}
        where = {0: (root_holder, "node")}  # leaf -> (parent dict, key)
        n_leaves = 1
        for k in range(max_leaves - 1):
            cand = max(best, key=lambda i: best[i][0])
            gain, f, t = best[cand]
            if not np.isfinite(gain):
                break
            new = n_leaves
            leaf_id = K["split_rows"](rows.binsT, leaf_id, cand, f, t, new)
            hp = hists.pop(cand)
            cl = np.cumsum(hp[f, :, 2])[t]
            cr = hp[f, :, 2].sum() - cl
            small = cand if cl <= cr else new
            hs = hist_of(leaf_id, small, g, h)
            hl, hr = (hs, hp - hs) if small == cand else (hp - hs, hs)
            G, H, C = hp[0].sum(axis=0)
            node = {"split_index": k, "split_feature": f,
                    "split_gain": gain, "threshold": float(bounds[f][t]),
                    "decision_type": "no_greater",
                    "internal_value": leaf_value(G, H, l2) if k else 0.0,
                    "internal_count": int(round(C)),
                    "left_child": None, "right_child": None}
            holder, key = where.pop(cand)
            holder[key] = node
            where[cand] = (node, "left_child")
            where[new] = (node, "right_child")
            hists[cand], hists[new] = hl, hr
            best[cand], best[new] = best_of(hl), best_of(hr)
            n_leaves += 1
        values = np.zeros(n_leaves)
        counts = np.zeros(n_leaves, np.int64)
        for i in range(n_leaves):
            G, H, C = hists[i][0].sum(axis=0)
            v = lr * leaf_value(G, H, l2)
            if low:
                v = float(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32))
            values[i], counts[i] = v, int(round(C))
        if fault == "leaf_altered" and ti == n_trees - 1:
            values[int(np.argmax(np.abs(values)))] *= 1.01
        for i in range(n_leaves):
            holder, key = where[i]
            holder[key] = {"leaf_index": i, "leaf_value": float(values[i]),
                           "leaf_count": int(counts[i])}
        trees.append({"num_leaves": n_leaves, "shrinkage": lr,
                      "tree_structure": root_holder["node"]})
        if fault != "state_unchanged":
            score = K["add_leaf_values"](score, leaf_id,
                                         jnp.asarray(values, jnp.float32))
        loss, g, h = objective(score, rows, sig, low)
        losses.append(loss)
    return trees, losses
