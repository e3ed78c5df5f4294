"""Kind ``train_rowblocks``: kind ``train``, for a table whose raw rows
the plain reference cannot bin in one piece on one chip.

Everything that is measured and everything that is compared is kind
``train``'s (``train.py``, ``correct.py``, ``reference.py``; none of them
is edited).  One thing differs.  ``reference.Rows`` puts the whole
transposed float matrix on one chip and bins it there in one call: at
42M x 28 that is 4.7 GB of floats and a binary search that carries two
int32 indices a value beside them, and the chip's compiler refuses it
("Used 21.28G of 15.75G hbm"; my chip run, PR 30, call 1).
``BlockRows`` feeds ``reference.Rows`` the same rows a block at a time
and joins what it returns: a block is a whole number of the reference's
chunks, so only the last one is padded and the joined ``binsT`` is, bin
for bin, what one call would have made.  The reference then follows the
program's trees over all the rows at once, as in kind ``train``; it
knows nothing of shards.
"""

from __future__ import annotations

import time

import numpy as np

from .. import correct, reference
from . import train

# 16,777,216 rows: 1.9 GB of floats and, by the refusal above (0.51 GB a
# million rows), 8.5 GB while a block is binned; 42M rows are three blocks
BLOCK = 1024 * reference.CHUNK


class BlockRows:
    """``reference.Rows`` of all the rows, binned a block at a time."""

    def __init__(self, X32: np.ndarray, y: np.ndarray, bounds):
        import jax.numpy as jnp
        n = X32.shape[0]
        parts = [reference.Rows(X32[lo:lo + BLOCK], y[lo:lo + BLOCK], bounds)
                 for lo in range(0, n, BLOCK)]
        first = parts[0]
        self.n, self.f = n, first.f
        self.n_bins, self.B = first.n_bins, first.B
        self.binsT = jnp.concatenate([p.binsT for p in parts], axis=1)
        self.np_rows = int(self.binsT.shape[1])
        self.valid = jnp.arange(self.np_rows) < n
        self.y = jnp.concatenate([p.y for p in parts])
        self.y_sign = np.concatenate([p.y_sign for p in parts])


def check_train(X32, y, bounds, trees, program_losses, cfg, limits):
    """``correct.check_train`` over ``BlockRows``."""
    t0 = time.time()
    rows = BlockRows(X32, y, bounds)
    t1 = time.time()
    compared, notes = correct.compare(rows, bounds, trees, program_losses,
                                      cfg, limits)
    return compared, (f"binning in row blocks {t1 - t0:.1f} s, following "
                      f"{time.time() - t1:.1f} s; {notes}")


def run(cell, args, chip, t_process_start) -> int:
    # train.measure looks ``correct.check_train`` up when the window has
    # closed: the one seam through which the blocks are fed.  The readers
    # of the per-layer metrics go by kind, and this is a training cell.
    correct.check_train = check_train
    return train.run(dict(cell, kind="train"), args, chip, t_process_start)
