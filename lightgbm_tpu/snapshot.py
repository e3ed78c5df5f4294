"""Crash-safe training snapshots: atomic, checksummed, bit-exact resume.

Long boosting runs on shared accelerators die for reasons that have
nothing to do with the model: preemption, OOM from a late-attached
validation set, a flaky coordinator.  A snapshot taken every
``snapshot_freq`` iterations into ``snapshot_dir`` makes those failures
recoverable: ``engine.train`` (and therefore the CLI) auto-resumes from
the newest *valid* snapshot and the resumed run is bit-identical to an
uninterrupted one — train(N) == train(k) -> crash -> resume(N).

What a snapshot holds (``GBDT.snapshot_state`` + subclass hooks):

- the host trees themselves (pickled ``Tree`` objects, so the bin-space
  split arrays survive exactly — no text round-trip),
- the device score caches for train and every valid set (restoring them
  directly is what makes resume bit-exact: replaying trees into a fresh
  buffer would re-order float additions),
- bagging PRNG key + the live row-weight mask and bag count,
- the feature-fraction RNG state,
- DART drop-RNG/tree-weight state (GOSS's draw is a function of the
  iteration and needs none; an older snapshot's ``goss`` key is ignored),
- engine-side eval history (``evals_result``) and best-iteration
  bookkeeping,
- the obs counter account (``lightgbm_tpu/obs``), restored so telemetry
  stays cumulative across the crash.

File format (``write_snapshot``): ``MAGIC | payload_len(8B LE) |
sha256(payload) | payload(pickle)``, written to ``<path>.tmp`` +
fsync + ``os.replace`` so a crash mid-write can never produce a file
that parses.  ``read_snapshot`` returns None for anything that fails
the magic/length/checksum/unpickle gauntlet, and
``load_latest_snapshot`` walks the directory newest-first, skipping
corrupt files with a warning naming the file (and a
``snapshot_corrupt_skipped_total`` counter) — a torn or truncated
newest snapshot falls back to the previous one.

Multihost (docs/FAULT_TOLERANCE.md §Distributed): the training state is
replicated across ranks, so ``save_snapshot`` writes on rank 0 ONLY —
N concurrent writers into one ``snapshot_dir`` would race
``prune_snapshots`` and each other's temp files for zero extra
durability.  Each record carries a ``world`` block (process count, rank,
digest of the replicated booster state), and resume runs
``coordinated_resume``: all ranks agree on the minimum common valid
iteration and verify they loaded byte-identical files, so a restarted
pod can never resume desynced.

See docs/FAULT_TOLERANCE.md for the user-facing contract.
"""

from __future__ import annotations

import copy
import hashlib
import os
import pickle
import re
from typing import Any, Dict, List, Optional, Tuple

from .utils import log

SNAPSHOT_MAGIC = b"LGBTSNAP\x01"
SNAPSHOT_VERSION = 1

_HEADER_LEN = len(SNAPSHOT_MAGIC) + 8 + 32
_FILE_RE = re.compile(r"^snapshot_(\d+)\.bin$")


# ---------------------------------------------------------------------------
# file layer
# ---------------------------------------------------------------------------

def snapshot_path(directory: str, rounds_done: int) -> str:
    return os.path.join(directory, f"snapshot_{int(rounds_done):010d}.bin")


def _encode(state: Dict[str, Any]) -> bytes:
    payload = pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)
    return (SNAPSHOT_MAGIC + len(payload).to_bytes(8, "little")
            + hashlib.sha256(payload).digest() + payload)


def write_snapshot(path: str, state: Dict[str, Any]) -> str:
    """Atomically write ``state`` to ``path`` (tmp file + fsync +
    ``os.replace`` via ``utils/diskguard.write_file_atomic``): a crash
    at any byte leaves either the previous file or a ``.tmp`` the
    checksummed reader ignores, and a WRITE failure (ENOSPC mid-fsync)
    removes the orphaned ``.tmp`` and leaves the last-good file intact
    before the ``OSError`` propagates — ``save_snapshot`` turns it into
    warn + retry-on-the-next-interval."""
    from .utils import diskguard
    return diskguard.write_file_atomic(path, _encode(state),
                                       sink="snapshot")


def read_snapshot(path: str) -> Optional[Dict[str, Any]]:
    """Parse + validate one snapshot file.  Returns the state dict, or
    None when the file is missing, truncated, corrupt (checksum), or not
    a snapshot at all — the caller falls back to an older file."""
    try:
        with open(path, "rb") as fh:
            blob = fh.read()
    except OSError:
        return None
    if len(blob) < _HEADER_LEN or not blob.startswith(SNAPSHOT_MAGIC):
        return None
    n = int.from_bytes(blob[len(SNAPSHOT_MAGIC):len(SNAPSHOT_MAGIC) + 8],
                       "little")
    digest = blob[len(SNAPSHOT_MAGIC) + 8:_HEADER_LEN]
    payload = blob[_HEADER_LEN:]
    if len(payload) != n or hashlib.sha256(payload).digest() != digest:
        return None
    try:
        state = pickle.loads(payload)
    except Exception:
        return None
    if not isinstance(state, dict) or "booster" not in state:
        return None
    return state


def list_snapshots(directory: str) -> List[Tuple[int, str]]:
    """``(rounds_done, path)`` pairs present in ``directory``, newest
    (highest round) first.  Existence only — validity is checked lazily
    by ``load_latest_snapshot``."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    out = []
    for name in names:
        m = _FILE_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    out.sort(reverse=True)
    return out


def load_latest_snapshot(directory: str) \
        -> Optional[Tuple[str, Dict[str, Any]]]:
    """Newest snapshot in ``directory`` that passes validation, or None.
    Corrupt/partial files (a torn newest write, bit rot) are skipped
    with a warning so the run falls back to the previous good state."""
    for rounds, path in list_snapshots(directory):
        state = read_snapshot(path)
        if state is not None:
            return path, state
        from . import obs
        obs.inc("snapshot_corrupt_skipped_total")
        log.warning("snapshot %s is corrupt or truncated; falling back "
                    "to an older snapshot", path)
    return None


def prune_snapshots(directory: str, keep: int) -> None:
    """Delete all but the newest ``keep`` snapshot files (best-effort);
    ``keep <= 0`` disables that half.  ALWAYS sweeps orphaned
    ``snapshot_*.bin.tmp`` files: a write that died before its
    ``os.replace`` (hard crash mid-fsync) leaves one behind, and stale
    tmps would otherwise accumulate per retry on a full disk.  Safe
    because one rank owns the directory (``is_snapshot_writer``) and
    the sweep runs in the writer's own thread, never concurrently with
    a live write."""
    try:
        for name in os.listdir(directory):
            if _FILE_RE.match(name[:-4]) and name.endswith(".tmp"):
                try:
                    os.remove(os.path.join(directory, name))
                except OSError:
                    pass
    except OSError:
        pass
    if keep <= 0:
        return
    for _, path in list_snapshots(directory)[keep:]:
        try:
            os.remove(path)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# multihost discipline (docs/FAULT_TOLERANCE.md §Distributed)
# ---------------------------------------------------------------------------

def _rank_world() -> Tuple[int, int]:
    """(process_index, process_count); (0, 1) outside a distributed
    runtime, without initializing a jax backend."""
    try:
        from .parallel.multihost import process_rank_world
        return process_rank_world()
    except Exception:  # pragma: no cover - jax unavailable
        return 0, 1


def is_snapshot_writer() -> bool:
    """Under multihost the booster state is replicated, so ONE rank owns
    the snapshot directory: rank 0 writes, everyone reads.  Concurrent
    writers would race ``prune_snapshots`` (a file rank 1 is fsyncing
    can be unlinked by rank 0's prune) and each other's ``.tmp`` files
    for zero added durability."""
    return _rank_world()[0] == 0


def replicated_state_digest(gb) -> str:
    """Hex fingerprint of a booster's replicated training state, built
    from the SAME per-field digests the desync detector allgathers
    (``GBDT._consistency_digests``: iter/trees/score/rng) — cheap (no
    second full-state pickle) and directly comparable across ranks'
    logs when debugging a desync.  Recorded in each snapshot's ``world``
    block; the resume consensus verifies the stronger property (raw
    file bytes identical across ranks) separately."""
    fields = gb._consistency_digests()
    blob = b"".join(k.encode() + int(v).to_bytes(8, "little")
                    for k, v in sorted(fields.items()))
    return hashlib.sha256(blob).hexdigest()


# ---------------------------------------------------------------------------
# booster capture / restore glue
# ---------------------------------------------------------------------------

def capture_booster_state(booster, rounds_done: int,
                          evals_result: Optional[dict] = None) \
        -> Dict[str, Any]:
    """Full resumable state of a training ``Booster`` after
    ``rounds_done`` completed boosting rounds (flushes the pipelined
    iteration first — ``GBDT.snapshot_state`` does that)."""
    from . import obs
    gb = booster._booster
    obs_snap = obs.snapshot()
    rank, world = _rank_world()
    booster_state = gb.snapshot_state()
    return {
        "version": SNAPSHOT_VERSION,
        "rounds_done": int(rounds_done),
        # who wrote this, out of how many, over what state: resume
        # consensus refuses a snapshot from a differently-sized pod
        # (num_processes) and verifies byte-identical files across
        # ranks; the digest is the desync detector's field fingerprint,
        # for debugging which rank/field drifted (single-process
        # snapshots, which nothing compares, skip it)
        "world": {
            "num_processes": int(world),
            "rank": int(rank),
            "digest": (replicated_state_digest(gb) if world > 1 else ""),
        },
        "booster": booster_state,
        "evals_result": (copy.deepcopy(evals_result)
                         if evals_result else None),
        "best_iteration": int(booster.best_iteration),
        # legacy key kept so old readers of new snapshots still see the
        # counter account; obs_state is the full registry (counters +
        # gauges + histograms) restored bit-exactly on resume
        "obs_counters": obs_snap["counters"],
        "obs_state": {"counters": obs_snap["counters"],
                      "gauges": obs_snap["gauges"],
                      "histograms": obs_snap["histograms"]},
    }


def restore_booster_state(booster, state: Dict[str, Any]) -> int:
    """Restore a ``capture_booster_state`` snapshot onto a freshly
    constructed ``Booster`` (same params, same data).  Returns the
    number of completed rounds.  The obs counter account is restored so
    a fresh process continues the interrupted run's telemetry."""
    from . import obs
    booster._booster.restore_state(state["booster"])
    booster.best_iteration = int(state.get("best_iteration", -1))
    obs_state = state.get("obs_state")
    if obs_state:
        # full registry resume: counters, gauges, and histogram bucket
        # state come back bit-exactly (pickle round-trips the float sum)
        obs.REGISTRY.restore(obs_state)
    elif state.get("obs_counters"):
        obs.REGISTRY.restore({"counters": state["obs_counters"]})
    return int(state.get("rounds_done", 0))


def save_snapshot(directory: str, booster, rounds_done: int,
                  evals_result: Optional[dict] = None,
                  keep: int = 0) -> Optional[str]:
    """Capture + atomically write one snapshot; prune old files when
    ``keep > 0``.  Returns the written path — or None on non-zero ranks
    under multihost, where the replicated state is rank 0's to write
    (``is_snapshot_writer``)."""
    if not is_snapshot_writer():
        log.warn_once("snapshot_writer_rank",
                      "snapshots are written by rank 0 only (state is "
                      "replicated); this rank skips the write")
        return None
    state = capture_booster_state(booster, rounds_done, evals_result)
    try:
        path = write_snapshot(snapshot_path(directory, rounds_done), state)
    except OSError as exc:
        # resource exhaustion on the snapshot sink must not kill the
        # training run it protects: the last-good snapshot is intact
        # (write_file_atomic removed the torn .tmp), this interval's
        # write is skipped, and the NEXT snapshot_freq interval retries
        from .utils import diskguard
        diskguard.note_sink_error(
            "snapshot", snapshot_path(directory, rounds_done), exc,
            action="the last-good snapshot is kept; the write retries "
            "on the next snapshot_freq interval")
        prune_snapshots(directory, keep)   # sweep any stale .tmp now
        return None
    prune_snapshots(directory, keep)
    return path


def coordinated_resume(directory: str) \
        -> Optional[Tuple[str, Dict[str, Any]]]:
    """Multihost resume consensus: every rank reports its newest VALID
    snapshot iteration, the pod agrees on the minimum, and each rank
    verifies it loaded the byte-identical file — so a restarted pod can
    never resume desynced (one rank on round 40, the rest on 50, every
    later collective silently mixing different models).

    Returns the same ``(path, state)`` on every rank, or None everywhere
    when any rank has no usable snapshot (a fresh start is the only
    state all ranks can agree on).  Single-process: plain
    ``load_latest_snapshot``."""
    rank, world = _rank_world()
    if world <= 1:
        return load_latest_snapshot(directory)
    import contextlib

    from .parallel.watchdog import active_watchdog
    wd = active_watchdog()
    # same guard as Comm::grow: a rank dying during the consensus
    # allgathers must become a bounded named abort, not a silent hang
    with (wd.guard("Dist::resume") if wd is not None
          else contextlib.nullcontext()):
        return _coordinated_resume_body(directory, rank, world)


def _coordinated_resume_body(directory: str, rank: int, world: int) \
        -> Optional[Tuple[str, Dict[str, Any]]]:
    import numpy as np

    from .parallel.comm import allgather_host_array
    found = load_latest_snapshot(directory)
    newest = -1 if found is None else int(found[1].get("rounds_done", 0))
    got = allgather_host_array(np.int64(newest))
    agreed = int(got.min())
    if agreed < 0:
        if int(got.max()) >= 0:
            have = [i for i, v in enumerate(got) if int(v) >= 0]
            log.warning(
                "resume consensus: rank(s) %s hold snapshots but rank(s) "
                "%s hold none — snapshot_dir is not shared or was "
                "partially cleared; the pod starts FRESH (the only state "
                "every rank can agree on)", have,
                [i for i in range(len(got)) if i not in have])
        return None
    if agreed != newest:
        log.warning("resume consensus: this rank's newest snapshot holds "
                    "%d rounds but the pod agrees on %d; resuming from "
                    "the common iteration", newest, agreed)
    path = snapshot_path(directory, agreed)
    state = read_snapshot(path)
    if state is None:
        log.fatal("resume consensus agreed on %s but rank %d cannot read "
                  "it; clear snapshot_dir (or restore the file) and "
                  "restart the pod", path, rank)
    w = state.get("world") or {}
    if w and int(w.get("num_processes", world)) != world:
        log.fatal("snapshot %s was written by a %d-process run but this "
                  "pod has %d processes; the replicated state is only "
                  "meaningful at the same world size", path,
                  int(w["num_processes"]), world)
    # every rank must have loaded the byte-identical file (per-host disks
    # can hold diverged copies of the "same" snapshot)
    with open(path, "rb") as fh:
        blob = fh.read()
    mine = np.frombuffer(hashlib.sha256(blob).digest()[:8],
                         np.uint64)[0]
    digests = allgather_host_array(np.uint64(mine))
    if int((digests != digests[0]).sum()):
        bad = [i for i, d in enumerate(digests) if int(d) != int(digests[0])]
        log.fatal("resume consensus: snapshot %s differs across ranks "
                  "(rank(s) %s hold different bytes than rank 0); refusing "
                  "to resume desynced — re-replicate the snapshot "
                  "directory and restart", path, bad)
    log.info("resume consensus: %d ranks agreed on %s (%d rounds done)",
             world, path, agreed)
    return path, state
