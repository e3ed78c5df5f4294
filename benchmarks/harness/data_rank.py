"""Query-grouped data for ranking cells, by name; everything from ``--seed``.

For a further ranking cell: add a generator here with ``mslr_like``'s
signature and return, enter it in ``GENERATORS``, and name it under
``data.generator`` in the configuration's file; ``kinds/train_rank.py``
finds it through ``make``.  ``harness/data.py`` (the generators of the
cells without queries) is the yardstick's and is not edited.

``mslr_like`` stands in for MSLR-WEB30K (Qin & Liu, arXiv:1306.2597):
31,531 queries and 3,771,125 documents, 1 to 1,251 documents a query,
labels 0 to 4, 136 columns = 25 kinds x 5 streams (body, anchor, title,
url, whole document; column ``5 k + s``) + 11 document columns.  What is
kept of the source is its SHAPE, recalled from the data set's description
and not read from it (no data set and no network here):

- query sizes heavy-tailed (log-normal, clipped to 1 .. 1,251, one query
  of each end forced), rescaled to the row count exactly;
- labels at shares 0.52 / 0.32 / 0.13 / 0.02 / 0.01, cut from a latent
  relevance of document quality, per-stream match strength, a query
  effect and noise, so that the columns carry signal a tree can find;
- kinds 0 to 7 (40 columns) integer-valued counts: kinds 0 to 3 take a
  dozen values (``FindBin`` gives each a bin of its own), kinds 4 to 7
  thousands (stream lengths, summed term frequencies); the others
  continuous;
- the 25 anchor columns are zero together for the 60% of documents that
  have no anchor text; three document columns (in-links, clicks, dwell)
  are zero for over half of the rows as well.  The zero-heavy columns are
  non-zero TOGETHER, as the source's are, so no two of them are mutually
  exclusive and EFB bundles nothing.

The same seed gives the same rows whatever the thread count: ``BLOCKS``
Philox streams over fixed row blocks, as ``data.higgs_like``.
"""

from __future__ import annotations

import numpy as np

BLOCKS = 16
KINDS, STREAMS, DOC_COLS = 25, 5, 11
NUM_FEATURES = KINDS * STREAMS + DOC_COLS            # 136
MAX_QUERY = 1251
LABEL_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
ANCHOR = 1                                           # the sparse stream


def query_sizes(num_data: int, num_queries: int, rng) -> np.ndarray:
    """``num_queries`` sizes in 1 .. ``MAX_QUERY`` that sum to
    ``num_data``: log-normal draws scaled to the mean, the largest and
    the smallest possible size forced where they fit, the rounding's
    remainder spread one document at a time."""
    top = min(MAX_QUERY, num_data - (num_queries - 1))
    raw = np.exp(0.85 * rng.standard_normal(num_queries))
    sizes = np.ones(num_queries, np.int64)
    for _ in range(40):                     # scale, clip, rescale the rest
        free = sizes < top
        scale = (num_data - sizes[~free].sum()) / raw[free].sum()
        new = np.clip(np.rint(raw * scale), 1, top).astype(np.int64)
        if np.array_equal(new, sizes):
            break
        sizes = new
    if num_queries > 2:
        sizes[int(np.argmax(raw))] = top
        sizes[int(np.argmin(raw))] = 1
    order = rng.permutation(num_queries)
    k = 0
    while sizes.sum() != num_data:          # the remainder, a document each
        step = 1 if sizes.sum() < num_data else -1
        q = order[k % num_queries]
        k += 1
        if 1 < sizes[q] + step < top:
            sizes[q] += step
    return sizes


def mslr_like(seed: int, num_data: int = 3771125, num_queries: int = 31531):
    """float32 features [num_data, 136], float32 labels in 0 .. 4 and the
    query sizes (int32, summing to ``num_data``; a query's documents are
    contiguous rows)."""
    from concurrent.futures import ThreadPoolExecutor
    root = np.random.SeedSequence(int(seed))
    seeds = root.spawn(BLOCKS + 1)
    qrng = np.random.Generator(np.random.Philox(seeds[BLOCKS]))
    sizes = query_sizes(num_data, num_queries, qrng)
    q_effect = qrng.standard_normal(num_queries).astype(np.float32)
    q_of_row = np.repeat(np.arange(num_queries, dtype=np.int32), sizes)

    X = np.empty((num_data, NUM_FEATURES), np.float32)
    latent = np.empty(num_data, np.float32)
    edges = np.linspace(0, num_data, BLOCKS + 1).astype(np.int64)
    col = lambda k, s: STREAMS * k + s

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        n = hi - lo
        rng = np.random.Generator(np.random.Philox(seeds[i]))
        x = X[lo:hi]
        rng.standard_normal(out=x, dtype=np.float32)
        u = rng.standard_normal(n, dtype=np.float32)          # quality
        m = 0.6 * u[:, None] + 0.8 * rng.standard_normal(
            (n, STREAMS), dtype=np.float32)                   # match, a stream
        has_anchor = rng.random(n, dtype=np.float32) < 0.4
        noise = rng.standard_normal(n, dtype=np.float32)
        # column (k, s) = share of the stream's match + its own draw
        for k in range(KINDS):
            c = slice(col(k, 0), col(k, 0) + STREAMS)
            a = np.float32(0.3 + 0.5 * ((k * 7) % 10) / 10.0)
            x[:, c] = a * m + np.float32(np.sqrt(1 - a * a)) * x[:, c]
            if k < 4:       # a dozen values: covered terms and their like
                x[:, c] = np.floor(np.clip(4.0 + 2.0 * x[:, c], 0.0, 12.0))
            elif k < 8:     # thousands: lengths, summed term frequencies
                x[:, c] = np.floor(np.exp(np.float32(3.5 + 0.5 * (k - 4))
                                          + 0.9 * x[:, c]))
        x[~has_anchor, ANCHOR:KINDS * STREAMS:STREAMS] = 0.0
        d = KINDS * STREAMS                                   # 11 doc columns
        x[:, d + 0] = np.floor(np.clip(3.0 + 1.5 * x[:, d + 0], 0, 9))  # slashes
        x[:, d + 1] = np.floor(np.exp(3.6 + 0.5 * x[:, d + 1]))     # url length
        inl = 0.5 * u + 0.87 * x[:, d + 2]
        x[:, d + 2] = np.where(inl > 0.4, np.floor(np.exp(2.0 * inl)), 0.0)
        x[:, d + 3] = np.floor(np.exp(2.5 + 0.8 * x[:, d + 3]))     # out-links
        x[:, d + 4] = 0.6 * u + 0.8 * x[:, d + 4]                   # page rank
        x[:, d + 5] = 0.4 * u + 0.92 * x[:, d + 5]                  # site rank
        clk = 0.7 * u + 0.71 * x[:, d + 8]
        x[:, d + 8] = np.where(clk > 0.5, np.floor(np.exp(1.5 * clk)), 0.0)
        x[:, d + 9] = np.where(clk > 0.5, np.exp(0.5 * x[:, d + 9] + 2.0), 0.0)
        x[:, d + 10] = np.where(clk > 0.5,
                                np.floor(3.0 + np.abs(x[:, d + 10]) * 2.0), 0.0)
        latent[lo:hi] = (0.9 * u + 0.5 * m[:, 2] + 0.4 * m[:, 0]
                         + 0.3 * has_anchor * m[:, ANCHOR]
                         + 0.25 * x[:, d + 4]
                         + 0.5 * q_effect[q_of_row[lo:hi]] + 0.9 * noise)

    with ThreadPoolExecutor(4) as pool:
        list(pool.map(fill, range(BLOCKS)))
    cuts = np.quantile(latent, np.cumsum(LABEL_SHARES)[:-1])
    y = np.searchsorted(cuts, latent, side="right").astype(np.float32)
    return X, y, sizes.astype(np.int32)


GENERATORS = {"mslr_like": mslr_like}


def make(spec: dict, rows: int, seed: int):
    """``spec``: the configuration's ``data`` (``generator``,
    ``num_features``, ``docs_per_query``).  ``rows`` is the cell's size or
    its rehearsal size; the queries go with it at the source's mean."""
    gen = GENERATORS[spec["generator"]]
    queries = max(3, int(round(rows / float(spec["docs_per_query"]))))
    X, y, group = gen(seed, num_data=rows, num_queries=queries)
    assert X.shape[1] == int(spec["num_features"]) and group.sum() == rows
    return X, y, group
