"""Canonical phase taxonomies — the single source of truth that
``tools/lint_phase_scopes.py`` enforces against the code.

Two taxonomies exist because the host and the device see different
boundaries:

- HOST_PHASES are ``obs.span("...")`` names: host wall-clock phases of
  set-up and of one boosting round, the reference's TIMETAG taxonomy
  (gbdt.cpp:20-59 boosting/train_score/valid_score/metric/bagging/tree
  plus the TPU port's host_tree materialization phase and the binning
  phases of ``Dataset.construct``).  Each also enters a
  ``jax.profiler.TraceAnnotation("lgbt:" + name)`` (obs/spans.py), so a
  profiler window holds them on the device trace's clock.
- DEVICE_PHASES are ``jax.named_scope("...")`` names inside the jitted
  programs.  The fused round (models/gbdt.py ``train_step`` over
  ops/ordered_grow.py) is covered whole: every operation traced into it
  sits under exactly one LEAF phase, the innermost declared scope of its
  ``op_name`` (``leaf_phase`` below); so is the data-parallel learner's
  round over leaf-ordered shards, whose collectives are the two
  ``exchange/*`` phases (parallel/comm.py).  The chip's trace names a device
  event by its HLO text and carries no scope path, so the program joins
  the two itself: ``obs/compile_ledger.py`` exports
  ``{instruction -> leaf phase}`` from the compiled text and
  ``obs/devtrace.py`` reduces a profiler window by it.  ops/grow.py
  keeps the reference's serial_tree_learner.cpp:10-37 three
  (hist/find_split/split).
- ROUND_PHASES is the top level of the fused round's taxonomy, in
  program order.  PERF.md names metrics after these: a rename is a
  ``benchmark`` issue's.
- DEVICE_PARENT maps each device phase to the host phase whose dispatch
  contains it, so trace time can be attributed back to the host account.
- JITTED_HOST_PHASES are the host phases whose time is device work; each
  must be covered by at least one device phase or traces go dark there.

This module must stay import-free (pure literals): the lint loads it by
file path without importing the package (and its jax dependency).
"""

HOST_PHASES = frozenset({
    # set-up, from package import to the end of the first round: the
    # set-up account's tree (obs/setup.py).  None of these may start
    # with ``Bin::``: the one-hot cell's ``bin_sparse_s`` sums every
    # ``phase_seconds_bin_*`` series (tests/test_phase_lint.py)
    "Setup::import",      # lightgbm_tpu/__init__, top to bottom (jax's
                          # import where the package imports it first)
    "Dataset::construct",  # the whole of Dataset.construct, parent of
                          # the Bin::* five
    "Booster::init",      # Booster.__init__ with a train set, parent of
    "GBDT::setup",        # GBDT._setup: objective init, bundles, the
                          # device placement, the grower and the step
    "GBDT::first_round",  # the whole of a booster's first
                          # GBDT::iteration, just inside it: the
                          # train_step compile or cache load is in it
    "Bin::bundle",        # EFB bundle planning over the mapper sample
                          # (io/bundling.py, docs/SPARSE.md)
    "Bin::linear_fit",    # per-stage batched leaf ridge solve
                          # (models/linear.py, docs/LINEAR_TREES.md;
                          # the fused path folds it into GBDT::tree)
    # set-up: Dataset.construct (io/dataset.py) and the device upload
    "Bin::sample",        # the bin-construct row sample and its columns
    "Bin::find_bin",      # FindBin over the sample, feature by feature
    "Bin::apply",         # raw matrix -> bin indices (float64 widening
                          # of the matrix included)
    "Bin::fingerprint",   # drift fingerprint (obs/drift.py): missing
                          # rates and a second, exact binning of all rows
    "Dataset::to_device",  # binned matrix, labels, word packing up
    "Rank::bucket",       # LambdarankNDCG.init: queries to size classes,
                          # inverse max DCG, the slab tables (host)
    "GBDT::iteration",    # whole boosting round (obs.span, always on)
    "GBDT::boosting",
    "GBDT::bagging",
    "GBDT::tree",
    "GBDT::train_score",
    "GBDT::valid_score",
    "GBDT::host_tree",
    "GBDT::metric",
    # distributed training (parallel/multihost.py, models/gbdt.py)
    "Comm::grow",         # one round's cross-process growth, collectives
                          # included (promote -> grow -> gather)
    "Dist::consistency",  # periodic replicated-state digest allgather
                          # (distributed_consistency_check)
    # serving subsystem (lightgbm_tpu/serve/, docs/SERVING.md)
    "Serve::request",     # whole HTTP request (causal-trace root)
    "Serve::queue",       # enqueue -> coalesced-batch pickup wait
    "Serve::batch",       # micro-batch assembly + device dispatch
    "Predict::forest",    # one CompiledForest bucket call
    # serving fleet (serve/fleet.py: replicas, hot reload, admission)
    "Serve::dispatch",    # routing decision: canary split + least-loaded
    "Serve::reload",      # hot swap: build + warm a new generation
    "Serve::drain",       # old generation: wait out in-flight, close
    # serving fault tolerance (serve/health.py: replica health machine)
    "Serve::hedge",       # one retried dispatch onto a different replica
    "Serve::eject",       # watchdog removing a bad replica from dispatch
    "Serve::probe",       # synthetic probe of an ejected replica
    # guarded model lifecycle (serve/lifecycle.py)
    "Serve::verdict",     # promotion controller ending an observation
                          # window: promote / rollback / extend
    "Serve::shadow",      # one mirrored batch scored on the canary off
                          # the response path
})

# The fused round, top level, in program order (models/gbdt.py
# _round_step, ops/ordered_grow.py, ops/leafhist.py).
ROUND_PHASES = (
    "gradients",          # objective gradients, weighting, root sums
    "layout",             # digit quantisation, word packing, the
                          # compaction sort
    "exchange/root",      # data-parallel shards only (parallel/comm.py
                          # HistExchange): the scales' max and the
                          # root's sums and histogram over the shards
    "hist/root",          # root pass: combine, cache seed (the kernel
                          # itself is hist/kernel, its feed hist/window)
    "grow_loop",          # the fori_loop itself, best-leaf pick, the
                          # size-class dispatch (a chain of conds)
    "split/window_read",  # dynamic slices of the parent's window (on a
                          # TPU they fuse into the partition kernel's
                          # feed and count under split/sort)
    "split/key",          # split column pick, goes-left bit, counts
    "split/sort",         # the window's stable two-way partition: on a
                          # TPU the segment_partition kernel with its
                          # feed and the slices of its output
                          # (ops/partition.py), else the stable sort; the
                          # phase keeps its name
    "split/window_write",  # partitioned window written back in place
    "hist/window",        # the slices that cut a histogram's window out
                          # of the word lanes (the root's too); off the
                          # TPU also the unpacking for the scatter
    "hist/kernel",        # digit_histogram (Pallas) or its scatter twin
    "exchange/hist",      # data-parallel shards only: the one all-reduce
                          # of a split step, a shard's left child's digit
                          # sums; its device time holds the wait for the
                          # slowest shard
    "hist/subtract",      # sibling subtraction and the cache update
    "find_split",         # root and children
    "leaf_table",         # node and leaf rows, TreeArrays
    "leaf_delta",         # ops/ordered_grow.py leaf_delta: the L
                          # segment starts sorted, every position's leaf
                          # selected by compares against them, scattered
                          # back to row order, the value selected by the
                          # leaf; the out-of-bag walk
    "score_update",
    "pack_tree",
)

# What objective=lambdarank adds to ``gradients`` on a TPU
# (ops/rank_lambda.py); a round of another objective has neither.
RANK_PHASES = (
    "gradients/rank_slab",    # scores to query slabs (whole rows of 128
                              # documents by row number) and the slabs of
                              # gradients and hessians added back
    "gradients/rank_lambda",  # the pair kernel
)

# What an EFB-bundled dataset adds to the leaf-ordered grower's round
# (ops/ordered_grow.py with ``bundle``, ops/bundle.py); a round over
# plain columns has neither.
BUNDLE_PHASES = (
    "split/decode",           # the split member's column, offset and
                              # width, and its own bin from the column's
                              # byte before the threshold compare
    "find_split/columns",     # the search over original features where
                              # they lie in the columns (root and
                              # children) and its per-tree tables; takes
                              # ``find_split``'s place
)

DEVICE_PHASES = frozenset(ROUND_PHASES) | frozenset(RANK_PHASES) \
    | frozenset(BUNDLE_PHASES) | frozenset({
    # ops/grow.py (cached and parallel learners): the reference's three
    "hist",
    "split",
    # CompiledForest fused inference program (serve/forest.py)
    "bin_lookup",
    "forest_walk",
    "linear_fit",         # per-leaf affine epilogue of a linear forest
                          # (docs/LINEAR_TREES.md; also the training-side
                          # batched solve in models/linear.py)
    "transform",
})

DEVICE_PARENT = {
    **{p: "GBDT::tree"
       for p in ROUND_PHASES + RANK_PHASES + BUNDLE_PHASES},
    "hist": "GBDT::tree",
    "split": "GBDT::tree",
    "bin_lookup": "Predict::forest",
    "forest_walk": "Predict::forest",
    "linear_fit": "Predict::forest",
    "transform": "Predict::forest",
}

JITTED_HOST_PHASES = frozenset({
    "GBDT::tree",
    "Predict::forest",
})

# Host<->device transfer accounting phases (obs/devprof.py transfer()):
# every H2D/D2H feed point charges its bytes to one of these, so the
# h2d_bytes_<phase>/d2h_bytes_<phase> counter namespace stays closed.
TRANSFER_PHASES = frozenset({
    "dataset",     # _DeviceData construction: binned matrix + labels up
    "host_tree",   # grown-tree materialization: device tree arrays down
    "predict",     # chunked training-side predict feeding
    "forest",      # CompiledForest build / to_device weight placement
    "serve",       # serve-path request payloads (batcher/forest calls)
})


def leaf_phase(op_name):
    """The leaf phase of an HLO ``op_name`` path
    (``jit(step_fn)/grow_loop/while/body/.../split/sort/segment_partition``): the
    INNERMOST declared device phase on the path, which is the one that
    ends last; the longer name where two end at the same component
    (``hist/kernel`` over ``hist`` after it, ``exchange/hist`` over the
    ``hist`` inside it).  The last component is the primitive and never
    a scope.  None when no declared phase is on the path."""
    path = "/" + str(op_name).rpartition("/")[0] + "/"
    best, best_end = None, -1
    for phase in DEVICE_PHASES:
        at = path.rfind("/" + phase + "/")
        end = at + len(phase)
        if at >= 0 and (end > best_end or (end == best_end
                                           and len(phase) > len(best))):
            best, best_end = phase, end
    return best


def sanitize(name):
    """Deterministic Prometheus-safe stem for any series/phase name:
    ``GBDT::tree`` -> ``gbdt_tree``.  The single sanitization rule for
    the whole metrics namespace — ``span_series`` below and
    ``obs/prom.py::metric_name`` both build on it, so the phase taxonomy
    and the exposition names cannot drift apart.  Pure string math only:
    this module must stay importable by file path without the package."""
    stem = []
    for ch in str(name).replace("::", "_").lower():
        stem.append(ch if (ch.isalnum() or ch == "_") else "_")
    s = "".join(stem).strip("_") or "unnamed"
    if s[0].isdigit():
        s = "_" + s
    return s


def span_series(name):
    """Histogram series name for a phase's span timer (obs/spans.py):
    ``GBDT::tree`` -> ``phase_seconds_gbdt_tree``.  The lint
    (tools/lint_phase_scopes.py) asserts the mapping yields a valid,
    unique series name for every declared phase."""
    return "phase_seconds_" + sanitize(name)
