"""Per-feature host passes over a few threads."""

from __future__ import annotations

import os

# rows from which a column's numpy pass outlasts a thread's start
_MIN_ROWS = 1 << 20
# every worker holds a column's float64 copy and its int64 bin indices
# (16 B a row: 11 GB over 16 workers at 42M rows), and past this many the
# passes are bound by the host's memory, not by its cores
_MAX_WORKERS = 16


def map_features(fn, items, num_rows: int) -> list:
    """``[fn(i) for i in items]``, over a few threads once the columns
    are long enough to pay for them (the per-feature numpy passes of
    binning release the interpreter's lock)."""
    items = list(items)
    workers = min(len(items), os.cpu_count() or 1, _MAX_WORKERS)
    if num_rows < _MIN_ROWS or workers < 2:
        return [fn(i) for i in items]
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(fn, items))
