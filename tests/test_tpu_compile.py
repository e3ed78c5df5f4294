"""The Pallas kernels of the main paths, compiled for a DESCRIBED TPU v5e
(no chip attached; nothing runs).

Tier-1 runs every kernel with ``interpret=True`` on the CPU, which says
nothing about what the chip's compiler accepts: kernels that passed every
interpreter test were refused for unaligned slices, missing casts and
missing primitives (PERF.md, "On the chip, PR 24").  The TPU compiler is
installed here and compiles for a topology that is only described, so a
few real-width compiles guard every later PR at no chip time.  A compile
that passes is not a chip run — ``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and under pytest-xdist every worker imports every test file.
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

F, B = 28, 255                      # the recorded training widths
T, L, CUTS = 40, 31, 255            # bench.py --mode predict's forest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> a ShapeDtypeStruct placed on the first
    described chip.  The persistent compile cache is off while the module
    runs: what is compiled for a described chip is written to it but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _assert_kernel_compiles(fn, *args, name=None):
    """The kernel is in the compiled text and, where ``name`` is given,
    its instruction carries the kernel's own name: a device event of it
    then reads ``%<name>.N`` whatever function traced the call
    (obs/devtrace.py, benchmarks/layer_metrics)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if name is not None:
        kernels = [ln for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        assert kernels and all(
            re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", ln)
            for ln in kernels), [ln[:80] for ln in kernels]


def test_digit_histogram_kernel_compiles(spec):
    """The default (ordered) grower's histogram kernel at one size class:
    4096 rows is the smallest child window (ops/ordered_grow.py P8)."""
    from lightgbm_tpu.ops import leafhist
    _assert_kernel_compiles(
        lambda b, d: leafhist.digit_histogram_pallas(b, d, B),
        spec((4096, F), jnp.uint8), spec((4096, 9), jnp.int8),
        name="digit_histogram")


@pytest.mark.parametrize("rows", [4096, 1 << 20])
def test_lanes_histogram_kernel_compiles(spec, rows):
    """The ordered grower's histogram kernel at the cells' width (7 bin
    word lanes and 3 digit word lanes at 28 features), at the smallest
    child window and at one of a million rows: the lanes read as they
    lie, the segment's two scalars prefetched, four features' one-hots
    from one word (the int32 block read as int8), the int8 contraction
    over the lanes of both operands, under the row-major kernel's name."""
    from lightgbm_tpu.ops import leafhist
    w = -(-F // 4)
    _assert_kernel_compiles(
        lambda first, scnt, *ls: leafhist.digit_histogram_lanes(
            ls[:w], ls[w:], first, scnt, F, B),
        spec((), jnp.int32), spec((), jnp.int32),
        *[spec((rows,), jnp.int32)] * (w + 3), name="digit_histogram")


@pytest.mark.parametrize("rows", [8192, 1 << 20])
def test_segment_partition_kernel_compiles(spec, rows):
    """The grower's window partition at the cells' width (7 bin words, 3
    digit words and the row order at 28 features), at the smallest size
    class and at one of a million rows: the lanes read as they lie and
    laid along lanes in VMEM, the int8 one-hot contraction, the triangular
    rank contraction, the scalar sums and the output DMA at a dynamic
    offset that is a proven multiple of the tile."""
    from lightgbm_tpu.ops import partition
    lanes = -(-F // 4) + 3 + 1
    _assert_kernel_compiles(
        lambda m, *ls: partition.segment_partition(ls, m),
        spec((rows,), jnp.bool_), *[spec((rows,), jnp.int32)] * lanes,
        name="segment_partition")


@pytest.mark.parametrize("rows", [1, 2, 17])
def test_rank_lambda_kernel_compiles(spec, rows):
    """LambdaRank's pair kernel (ops/rank_lambda.py) at three of the
    ranking cell's size classes: a slab of one row, of two (the class most
    queries of MSLR-WEB30K's sizes fall in) and of 17 (its 1,251-document
    queries); the event name is what ``rank_lambda_ms_per_round`` finds."""
    from lightgbm_tpu.ops import rank_lambda
    q = 8 * rank_lambda.QUERIES_PER_STEP
    slab = spec((q, rows, 128), jnp.float32)
    _assert_kernel_compiles(
        lambda nr, inv, s, lab, gain: rank_lambda.rank_lambda(
            nr, inv, s, lab, gain, sigma=1.0),
        spec((q,), jnp.int32), spec((q,), jnp.float32), slab, slab, slab,
        name="rank_lambda")


def test_rank_slab_feed_moves_whole_rows_not_documents(spec):
    """The feed around the kernel: scores reach the query slabs and the
    sums come back by whole rows of 128 documents (``slice_sizes={1,128}``
    gathers and row scatters), never an element a document, and nothing
    is sorted."""
    from lightgbm_tpu.ops import rank_lambda
    rng = np.random.RandomState(0)
    sizes = rng.randint(1, 700, size=400)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    label = rng.randint(0, 5, size=qb[-1])
    classes, slots = rank_lambda.slab_tables(
        qb, label, 2.0 ** np.arange(31) - 1.0, np.ones(len(sizes)))
    shapes = jax.tree.map(lambda a: spec(a.shape, a.dtype), classes)
    text = jax.jit(lambda c, s: rank_lambda.slab_gradients(
        c, s, sigma=1.0)).lower(
            shapes, spec((int(qb[-1]),), jnp.float32)).compile().as_text()
    gathers = re.findall(r"= \S+ gather\(.*?slice_sizes=\{([\d,]+)\}", text)
    assert gathers and all(g == "1,128" for g in gathers), gathers
    assert " sort(" not in text
    assert text.count('custom_call_target="tpu_custom_call"') == len(classes)
    assert slots >= int((sizes.astype(np.int64) ** 2).sum())


def test_children_histograms_kernel_compiles(spec):
    """The parallel learners' two-children histogram kernel."""
    from lightgbm_tpu.ops.pallas_histogram import children_histograms_pallas
    n = 8192
    text = children_histograms_pallas.lower(
        spec((F, n), jnp.uint8), spec((n,), jnp.float32),
        spec((n,), jnp.float32), spec((n,), jnp.float32),
        spec((n,), jnp.int32), 1, 3, max_bin=B).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%children_histograms(\.\d+)? = .*tpu_custom_call",
                     text)


def _chain_forest_tables():
    """Walk tables of T left-deep L-leaf trees over F features."""
    from lightgbm_tpu.ops import pallas_walk
    m = L - 1
    rng = np.random.RandomState(0)
    sf = rng.randint(0, F, size=(1, T, m)).astype(np.int32)
    sb = rng.randint(0, CUTS, size=(1, T, m)).astype(np.int32)
    ic = np.zeros((1, T, m), bool)
    lc = np.zeros((1, T, m), np.int32)
    rc = np.zeros((1, T, m), np.int32)
    for i in range(m):
        lc[:, :, i] = i + 1 if i + 1 < m else ~m
        rc[:, :, i] = ~i
    lv = rng.normal(size=(1, T, L)).astype(np.float32)
    return pallas_walk.build_walk_tables(sf, sb, ic, lc, rc, lv, F)


@pytest.mark.parametrize("bucket", [16, 256])
@pytest.mark.parametrize("variant", ["binned", "raw"])
def test_forest_walk_kernel_compiles(spec, variant, bucket):
    """Both serve-walk variants (``serve_walk=fused``, and ``auto`` on a
    TPU) at the smallest and a mid serve bucket."""
    from lightgbm_tpu.ops import pallas_walk
    nan_bin = CUTS + 1
    tables = tuple(spec(a.shape, a.dtype) for a in _chain_forest_tables())
    if variant == "binned":
        _assert_kernel_compiles(
            lambda *a: pallas_walk.forest_walk(*a, num_class=1,
                                               nan_bin=nan_bin),
            *tables,
            spec((F, bucket), pallas_walk.bin_index_dtype(nan_bin)),
            name="forest_walk")
    else:
        _assert_kernel_compiles(
            lambda *a: pallas_walk.forest_walk_raw(*a, num_class=1,
                                                   nan_bin=nan_bin),
            *tables, spec((F, CUTS), jnp.float32),
            spec((F, CUTS), jnp.int32), spec((F, 1), jnp.float32),
            spec((F, bucket), jnp.float32), name="forest_walk")


_TEXTS = {}     # compiled texts, by program and shape: a compile each


def _ordered_grower_text(spec, monkeypatch, n, f=4, leaves=7, bundled=0):
    """Compiled text of ``grow_tree_ordered`` at ``n`` rows, ``f``
    features (four keep the kernel's unroll short) and ``leaves`` leaves,
    on the chip's kernel.  ``bundled``: the ``f`` are EFB columns that
    hold this many original features (two-bin members and identity
    columns, as a one-hot table's are)."""
    from lightgbm_tpu.ops.bundle import BundleDecode
    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.ops.ordered_grow import grow_tree_ordered
    from lightgbm_tpu.utils import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    key = ("serial", n, f, leaves, bundled)
    if key not in _TEXTS:
        feats = bundled or f
        i32 = lambda *shape: spec(shape, jnp.int32)
        bundle = BundleDecode(
            col=i32(feats), off=i32(feats), width=i32(feats),
            slot_map=i32(feats, B), default_bin=i32(feats),
            col_feat=i32(f), slot_feat=i32(f, B), multi=i32(0)) \
            if bundled else None
        _TEXTS[key] = grow_tree_ordered.lower(
            spec((f, n), jnp.uint8), spec((feats,), jnp.int32),
            spec((feats,), jnp.bool_), spec((feats,), jnp.bool_),
            spec((n,), jnp.float32), spec((n,), jnp.float32),
            spec((n,), jnp.float32), spec((), jnp.float32),
            GrowParams(num_leaves=leaves, max_bin=B, min_data_in_leaf=50),
            bundle=bundle).compile().as_text()
    return _TEXTS[key]


def _sharded_grower_text(topo, monkeypatch, n, f=4, leaves=7):
    """Compiled text of the data-parallel learner's grow program over the
    four described chips, ``n`` rows a shard, ``f`` features, ``leaves``
    leaves: ``make_parallel_grow``'s ``shard_map`` of the leaf-ordered
    grower with its exchange."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.parallel import make_parallel_grow
    from lightgbm_tpu.utils import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    k, key = len(topo.devices), ("sharded", n, f, leaves)
    if key not in _TEXTS:
        mesh = Mesh(np.array(topo.devices), ("data",))

        def on(shape, dtype, *parts):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, P(*parts)))
        grow = make_parallel_grow(
            mesh, "data",
            GrowParams(num_leaves=leaves, max_bin=B, min_data_in_leaf=50))
        _TEXTS[key] = grow.lower(
            on((f, k * n), jnp.uint8, None, "data"), on((f,), jnp.int32),
            on((f,), jnp.bool_), on((f,), jnp.bool_),
            on((k * n,), jnp.float32, "data"),
            on((k * n,), jnp.float32, "data"),
            on((k * n,), jnp.float32, "data"), on((), jnp.float32)
        ).compile().as_text()
    return _TEXTS[key]


def test_ordered_grower_text_carries_the_phase_paths(spec, monkeypatch):
    """The chip's trace names a device event by its instruction and hands
    out no scope path; the compiled text does (``op_name``), which is
    what the program's phase map (obs/devtrace.py) is parsed from.  The
    ordered grower at ONE size class (8,192 rows; four features keep the
    kernel's unroll short): the partition kernel sits under
    ``split/sort`` by its own name and no HLO ``sort`` is left there (the
    two that remain are ``leaf_delta``'s), the histogram kernel carries
    its own name, and no operation is left under no phase."""
    from lightgbm_tpu.obs import devtrace
    text = _ordered_grower_text(spec, monkeypatch, 8192)
    pm = devtrace.phase_map(text)
    assert pm["module"] == "jit_grow_tree_ordered"
    instrs = devtrace.parse_hlo(text)["instructions"]
    under_sort = {k for k, ph in pm["phases"].items() if ph == "split/sort"}
    assert [k for k in under_sort if k.startswith("segment_partition")], \
        sorted(under_sort)
    assert not [k for k in under_sort if instrs[k]["opcode"] == "sort"]
    sorts = {pm["phases"][k] for k, r in instrs.items()
             if r["opcode"] == "sort"}
    assert sorts <= {"leaf_delta"}, sorts
    kernels = [k for k, ph in pm["phases"].items() if ph == "hist/kernel"
               and k.startswith("digit_histogram")]
    assert kernels, sorted(pm["phases"])[:20]
    assert pm["ops_unscoped"] == 0, pm["unscoped_op_names"]
    assert pm["inserted"], "the chip's compiler inserts copies here"
    # leaf reconstruction selects by compares (PR 37): the one-hot's
    # reduction sits under ``leaf_delta`` and no search loop does
    rebuilt = {k for k, ph in pm["phases"].items() if ph == "leaf_delta"}
    assert [k for k in rebuilt
            if (instrs[k]["op_name"] or "").endswith("/reduce_sum")]
    assert not [k for k in rebuilt if instrs[k]["opcode"] == "while"]
    assert not [k for k, r in instrs.items()
                if "searchsorted" in (r["op_name"] or "")]


def _result_elements(text):
    """``{instruction: [elements of each array of its result]}`` of a
    compiled text (a tuple result has several)."""
    from lightgbm_tpu.obs import devtrace
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if m:
            rest = line[m.end():]
            shape = rest[:len(rest) - len(devtrace._after_shape(rest))]
            out[m.group(1)] = [
                int(np.prod([int(d) for d in dims.split(",") if d]))
                for dims in re.findall(r"\[([\d,]*)\]", shape)]
    return out


@pytest.mark.parametrize("n,leaves,keep", [
    (12_582_912, 255, "delta"), (12_582_912, 255, "both"),
    (11_010_048, 63, "delta")])
def test_leaf_delta_neither_searches_nor_gathers_rows(spec, n, leaves, keep):
    """``ops/ordered_grow.py leaf_delta`` alone at the one-hot cell's and
    cell 1's rows and leaves, as the fused round uses it (``delta``: the
    leaf id dropped) and as the per-stage path does (``both``).  Until PR
    37 the phase was ``searchsorted``'s scan, a ``while`` of 8 steps that
    each gathered all N positions from the table of starts, and two table
    gathers more: ten gathers at 8.2 ns a row, 1,186 ms of a 2,313 ms
    round (PERF.md).  Now: no loop, no gather, the two ``[L, N]``
    compares alive only inside their reductions' fusions, ONE scatter
    either way, and the only sorts the L pairs' and the one the compiler
    makes of that scatter."""
    from lightgbm_tpu.obs import devtrace
    from lightgbm_tpu.ops.ordered_grow import leaf_delta

    def fn(*args):
        out = leaf_delta(*args, n)
        return out[1] if keep == "delta" else out
    compiled = jax.jit(fn).lower(
        spec((leaves,), jnp.int32), spec((leaves,), jnp.int32),
        spec((), jnp.int32), spec((leaves,), jnp.float32),
        spec((n,), jnp.int32)).compile()
    text = compiled.as_text()
    instrs = devtrace.parse_hlo(text)["instructions"]
    elements = _result_elements(text)
    fused = {c for r in instrs.values() if r["opcode"] == "fusion"
             for _, c in r["called"]}
    scoped = [r["op_name"] for r in instrs.values() if r["located"]]
    assert scoped and all("/leaf_delta/" in name for name in scoped)
    opcodes = [r["opcode"] for r in instrs.values()]
    assert "while" not in opcodes and "gather" not in opcodes
    assert opcodes.count("scatter") == 1
    # the one-hots over the segments and over the leaves live inside a
    # fusion each and nowhere else
    assert len({r["comp"] for k, r in instrs.items() if r["comp"] in fused
                and max(elements[k], default=0) == n * leaves}) == 2
    assert not [k for k, r in instrs.items() if r["comp"] not in fused
                and max(elements[k], default=0) > n]
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 4 * n
    sorts = sorted((elements[k][0], instrs[k]["op_name"].rsplit("/")[-1])
                   for k, r in instrs.items() if r["opcode"] == "sort")
    assert sorts in ([(leaves, "sort"), (n, "scatter")],
                     [(leaves, "sort")]), sorts


def test_ordered_grower_builds_nothing_row_major_for_the_histogram(
        spec, monkeypatch):
    """The histogram kernel takes the window's word lanes as they lie:
    what is left under ``hist/window`` is the slices that cut the window,
    all ``s32[rows]``.  Until PR 33 the lanes were stacked on a new minor
    axis, bitcast to bytes and masked there, ``u8[rows, F]`` and
    ``s8[rows, 9]`` written out at every split for the row-major kernel
    (202 ms of a 970 ms round at 10.5M rows: PERF.md, PR 33)."""
    from lightgbm_tpu.obs import devtrace
    text = _ordered_grower_text(spec, monkeypatch, 8192)
    pm = devtrace.phase_map(text)
    bytes_2d = re.compile(r"%([\w.\-]+) = \(?[us]8\[\d+,\d+\]")
    names = [m.group(1) for m in map(bytes_2d.search, text.splitlines())
             if m]
    assert [n for n in names if pm["phases"].get(n) == "layout"], \
        "the digits are quantised row-major, once a tree"
    assert [n for n in names
            if pm["phases"].get(n, "").startswith("hist/")] == []
    feed = [k for k, ph in pm["phases"].items() if ph == "hist/window"]
    assert feed, "the window's slices"


def _reached(instrs, comp):
    """The computations reached from ``comp`` through its instructions'
    called computations, itself included."""
    by_comp = {}
    for name, rec in instrs.items():
        by_comp.setdefault(rec["comp"], []).append(name)
    seen, todo = set(), [comp]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [callee for name in by_comp.get(c, ())
                     for _, callee in instrs[name]["called"]]
    return seen


def _whole_in_grow_loop(text, dims, opcode="copy"):
    """Names of the ``opcode`` instructions whose result is a whole
    carried buffer, ``s32[dims]`` (``dims`` a pattern: a row lane's
    length, the histogram cache's four extents), in the body of the grow
    loop (the ``while`` that reaches the partition kernels) or in any
    computation it calls, fusions included.  ``copy-start``/``copy-done``
    (the compiler's moves of a small buffer into ``S(1)``) are other
    opcodes and are not counted."""
    from lightgbm_tpu.obs import devtrace
    instrs = devtrace.parse_hlo(text)["instructions"]
    loops = [_reached(instrs, dict(rec["called"])["body"])
             for rec in instrs.values() if rec["opcode"] == "while"]
    kernels = {rec["comp"] for name, rec in instrs.items()
               if name.startswith("segment_partition")}
    in_loop = set().union(*(comps for comps in loops if comps & kernels))
    assert in_loop, "no loop reaches the partition kernels"
    whole = re.compile(rf"%([\w.\-]+) = s32\[{dims}\](\{{[^}}]*\}})? "
                       rf"{re.escape(opcode)}\(")
    names = (m.group(1) for m in map(whole.search, text.splitlines()) if m)
    return sorted(n for n in names if instrs[n]["comp"] in in_loop)


def test_ordered_grower_copies_no_whole_lane_in_the_grow_loop(spec,
                                                              monkeypatch):
    """The grow loop carries the row lanes (bin words, digit words, row
    order; ``s32[N + PAD]`` each) and a split step writes one window of
    each with a ``dynamic_update_slice``.  Handed through an N-way
    ``lax.switch`` over the size classes, the chip's compiler copied
    every lane whole before that write in all branches but one (9 copy
    instructions at this size, 447 ms of a 2,073 ms round at 10.5M rows:
    PERF.md, PR 29); through the chain of two-way ``lax.cond``s it writes
    in place.  32,768 rows take three size classes; the copies the
    LOOP makes of its carry show only at 11M rows and are checked by
    hand (PERF.md)."""
    from lightgbm_tpu.ops.ordered_grow import _size_classes
    n = 32768
    classes = _size_classes(n)
    assert len(classes) >= 3
    text = _ordered_grower_text(spec, monkeypatch, n)
    lane = n + classes[-1]
    assert f"s32[{lane}]" in text and "dynamic-update-slice(" in text
    assert _whole_in_grow_loop(text, lane) == []


@pytest.mark.parametrize("program", ["serial", "sharded", "bundled"])
def test_ordered_grower_copies_no_whole_cache_in_the_grow_loop(
        topo, spec, monkeypatch, program):
    """The grow loop carries the histogram cache (``s32[L, F, 9, B]``;
    under the exchange the shard's own and the global one in halves,
    ``s32[L, F, 18, B]``) and a split step reads ONE row of it, the
    parent's, and writes two, the children's.  Left free to fuse that read
    into each consumer, the chip's compiler read the OLD cache again in
    the second row write: the old cache outlived the first write, so it
    was copied whole before it and the second write's result copied back
    into the carry, twice 318 MB at each of 254 split steps, 495 ms of a
    1,444 ms round at 255 leaves x 136 features (PERF.md, PR 35).  The
    copies show only from a few size classes on: the serial program at the
    ranking cell's widths and the sharded one at Higgs's, 262,144 rows (a
    shard) each, held two and four of them.  ``bundled``: the one-hot
    cell's widths (PR 36): the cache in COLUMN space, 60 columns of 4,228
    features, which the column-space search reads where the features
    lie."""
    n = 262144
    if program == "serial":
        f, leaves = 136, 255
        text = _ordered_grower_text(spec, monkeypatch, n, f, leaves)
    elif program == "bundled":
        f, leaves = 60, 255
        text = _ordered_grower_text(spec, monkeypatch, n, f, leaves,
                                    bundled=4228)
        assert "4228,9," not in text, "something is expanded to [F, 9, B]"
    else:
        f, leaves = F, 63
        text = _sharded_grower_text(topo, monkeypatch, n, f, leaves)
    cache = rf"{leaves},{f},(9|18),{B}"
    writes = _whole_in_grow_loop(text, cache, "dynamic-update-slice")
    assert len(writes) >= (4 if program == "sharded" else 2), writes
    assert _whole_in_grow_loop(text, cache) == []


@pytest.mark.parametrize("columns", [28, 80, 136])
def test_pack_words_is_a_small_program(spec, columns):
    """``pack_words`` at a cell's columns x 4M rows: a word is four
    shifted columns ORed.  From the row-major matrix the lanes were
    strided slices of a relayout: 37.4 MiB of code and 70 s of compile at
    80 columns x 12.6M rows (sandbox compile, PR 36) for what is 3.5 MiB
    and 3 s."""
    from lightgbm_tpu.ops import ordered_grow
    lowered = ordered_grow._pack_words_padded.lower(
        spec((columns, 1 << 22), jnp.uint8))
    compiled = lowered.compile()
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < (columns // 4) * (1 << 19), code      # half a MiB a word
    assert "transpose" not in compiled.as_text()


# grow_tree_ordered at 32,768 x 4, 7 leaves, for the described v5e: 3,282
# for the program of PR 29 and, the exchange hook in place, of PR 30;
# PR 31 put the partition kernel, its mask and the slices of its output
# in the segment sort's place in each of three size classes (3,340);
# PR 33 took the row-major feed of the histogram kernel out of every
# child window and moved the root pass onto the word lanes (3,096); PR 35
# reads the cache's parent row once, behind a barrier, where three
# consumers each had their own slice of it (3,090); PR 36 searches the
# integer sums (ops/split.py find_best_split_sums: prefix sums as two
# products, both sides combined, a split record of both sides' sums:
# 3,580); PR 37 selects a position's leaf by compares where
# ``searchsorted`` looped and gathered (3,376).
# Whoever changes the serial grower knowingly changes this number with
# it.
SERIAL_INSTRUCTIONS = 3376
COLLECTIVES = ("all-reduce", "all-reduce-start", "reduce-scatter",
               "all-gather", "all-gather-start", "all-to-all",
               "collective-permute", "collective-permute-start")


def test_exchange_hook_leaves_the_serial_program_as_it_was(spec,
                                                           monkeypatch):
    """``grow_tree_ordered(exchange=None)`` is the serial program:
    the same count of instructions as before the hook, no collective."""
    from lightgbm_tpu.obs import devtrace
    text = _ordered_grower_text(spec, monkeypatch, 32768)
    instrs = devtrace.parse_hlo(text)["instructions"]
    assert len(instrs) == SERIAL_INSTRUCTIONS
    assert not [n for n, r in instrs.items() if r["opcode"] in COLLECTIVES]


def test_sharded_grower_exchanges_once_a_split_outside_every_branch(
        topo, spec, monkeypatch):
    """The data-parallel learner over the four described chips, 32,768
    rows a shard (three size classes).  One histogram collective in the
    grow loop's body, the all-reduce of ``exchange/hist``: each shard is
    in its own size class, so a collective inside a conditional's branch
    would deadlock, and none is reached from one.  Three more before the
    loop, ``exchange/root``.  PR 29's property holds in the sharded
    program: no whole-lane ``copy`` in the grow loop."""
    from lightgbm_tpu.obs import devtrace, phases
    from lightgbm_tpu.ops.ordered_grow import _size_classes
    n = 32768
    text = _sharded_grower_text(topo, monkeypatch, n)
    assert "tpu_custom_call" in text and "digit_histogram" in text
    instrs = devtrace.parse_hlo(text)["instructions"]
    coll = {name: rec for name, rec in instrs.items()
            if rec["opcode"] in COLLECTIVES}
    by_phase = {}
    for name, rec in coll.items():
        by_phase.setdefault(phases.leaf_phase(rec["op_name"]),
                            []).append(name)
    assert sorted(by_phase) == ["exchange/hist", "exchange/root"], by_phase
    assert len(by_phase["exchange/hist"]) == 1
    # scales, rows, histogram (the root's sums are the histogram's own,
    # PR 36): the compiler may combine two
    assert len(by_phase["exchange/root"]) in (2, 3)
    hist = coll[by_phase["exchange/hist"][0]]
    assert re.search(r"s32\[4,18,\d+\]", text.split(
        f"%{by_phase['exchange/hist'][0]} = ")[1].split("\n")[0])
    # its computation is the loop's body (or one the body calls outright),
    # and no computation reached through a conditional holds a collective
    callers = {}
    for name, rec in instrs.items():
        for attr, callee in rec["called"]:
            callers.setdefault(callee, []).append((rec["opcode"], attr))
    body = [c for c, by in callers.items() if ("while", "body") in by]
    assert hist["comp"] in body, (hist["comp"], body)

    under_cond = set().union(*(
        _reached(instrs, callee) for rec in instrs.values()
        if rec["opcode"] == "conditional" for _, callee in rec["called"]))
    assert under_cond, "the size-class dispatch is a chain of conditionals"
    assert not [n_ for n_, r in coll.items() if r["comp"] in under_cond]
    lane = n + _size_classes(n)[-1]
    assert f"s32[{lane}]" in text
    assert _whole_in_grow_loop(text, lane) == []
    pm = devtrace.phase_map(text)
    assert pm["ops_unscoped"] == 0, pm["unscoped_op_names"]
