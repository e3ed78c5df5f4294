"""The work a boosting round needs, whatever implements it.

Counted from the trees that the traced rounds grew (``dump_model``'s
``internal_count`` and ``leaf_count``), not from the program's kernels: a
leaf-wise learner with histogram subtraction has to visit N rows for the
root and, for every split, the rows of the smaller child.  Per visited
row: F bin bytes and 8 bytes of gradient and hessian read, and 3F integer
additions (gradient, hessian, count).  Nothing is padded: one-hot
formulations that do 256 times the additions get no credit for them.
"""

from __future__ import annotations


def node_count(node: dict) -> int:
    return int(node["internal_count"] if "split_index" in node
               else node["leaf_count"])


def visited_rows(tree: dict, rows: int) -> int:
    """N for the root plus the smaller child's rows at every split."""
    total = rows
    stack = [tree["tree_structure"]]
    while stack:
        node = stack.pop()
        if "split_index" not in node:
            continue
        left, right = node["left_child"], node["right_child"]
        total += min(node_count(left), node_count(right))
        stack += [left, right]
    return total


def histogram_work(trees, rows: int, features: int) -> dict:
    """Bytes and integer operations of the histograms of ``trees``."""
    visited = sum(visited_rows(t, rows) for t in trees)
    return {"visited_rows": visited,
            "bytes": visited * (features + 8),
            "ops": visited * 3 * features}


def round_work(trees, rows: int, features: int) -> dict:
    """The whole round: the histograms, one pass over N rows for the
    gradients (score and label read, gradient and hessian written: 16
    bytes, about 10 operations) and one for the score update (score read
    and written: 8 bytes, 1 addition)."""
    w = histogram_work(trees, rows, features)
    n = rows * len(trees)
    return {"visited_rows": w["visited_rows"],
            "bytes": w["bytes"] + n * (16 + 8),
            "ops": w["ops"] + n * (10 + 1)}


def least_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip could take: the larger of bytes over the
    memory bandwidth and operations over the int8 peak; says which."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["ops"] / peaks["int8_ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "ops",
            "by_bytes_s": by_bytes, "by_ops_s": by_ops}


COUNT_FUNCTIONS = {"histogram_work": histogram_work,
                   "round_work": round_work}
