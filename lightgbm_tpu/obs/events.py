"""Per-iteration structured JSONL event stream.

One line per boosting iteration (see docs/OBSERVABILITY.md for the field
table).  Fields for a given iteration arrive from several producers at
different times because training is PIPELINED (models/gbdt.py):

- ``GBDT.train_one_iter`` notes wall time, phase deltas, bag count and
  cumulative collective bytes as iteration *i* is dispatched;
- the eval callback (``callback.log_telemetry``) notes metric values for
  *i* after the engine evaluates it;
- the grown trees' shape for *i* only materializes when the NEXT call
  flushes the pipelined host transfer (``GBDT._flush_pending``).

The recorder therefore commits on ADVANCE: a record is written out the
first time any field for a *later* iteration is noted — by then every
producer of iteration *i* has run (the pipelined flush for *i* happens at
the start of the device work for *i+1*, and eval callbacks for *i* run
before ``update(i+1)``).  ``close()`` drains whatever is still pending
(the final iteration), so callers must flush the booster pipeline before
closing — ``engine.train`` does this for recorders it owns.

Schema 2 adds ONE record with no iteration number, ahead of the first
iteration's: ``{"schema": 2, "setup": {...}}``, the job's set-up account
(obs/setup.py), written when the first round returns.  ``read_events``
passes over it (it returns the per-iteration records, as before);
``read_setup`` returns it.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 2


def _json_default(o):
    """Producers hand over numpy scalars (tree depths, counts); coerce
    instead of burdening every call site."""
    if hasattr(o, "item"):
        return o.item()
    raise TypeError(f"Object of type {type(o).__name__} "
                    f"is not JSON serializable")


def _sanitize(v):
    """Non-finite metric values (nan auc on a one-class fold, inf loss)
    would serialize as bare NaN/Infinity tokens — valid for Python's
    json but rejected by strict consumers (jq, JSON.parse).  Map them to
    null; the record stays parseable everywhere."""
    if isinstance(v, float):
        return v if math.isfinite(v) else None
    if isinstance(v, dict):
        return {k: _sanitize(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_sanitize(x) for x in v]
    return v


class EventRecorder:
    """Append-only JSONL writer with per-iteration field merging.

    The sink is a ``diskguard.GuardedWriter`` (line-buffered, flushed
    every ``flush_every`` committed records — default every record), so
    (a) a crashed run keeps every record committed before the crash: the
    tail of exactly the iterations you need to debug the crash is on
    disk, not in a userspace buffer (pinned by
    tests/test_resource_chaos.py's kill-without-close test), and (b) a
    full disk mid-run disables the stream with one warning and a
    ``sink_write_errors_total`` count instead of crashing training from
    inside its own telemetry (docs/FAULT_TOLERANCE.md §Resource
    exhaustion)."""

    def __init__(self, path: str, flush_every: int = 1):
        self._path = str(path)
        self._flush_every = max(int(flush_every), 1)
        self._pending: Dict[int, Dict[str, Any]] = {}
        self._written = 0
        self._since_flush = 0
        # multihost: stamp every record with this process's rank so
        # obs-report over merged per-rank files can attribute stragglers
        # (single-process streams stay unchanged — no rank field).  The
        # path is suffixed per rank too: every rank receives the SAME
        # events_file from the one conf, and N ranks opening one shared
        # path with mode "w" would truncate each other's streams.
        self._rank: Any = None
        try:
            from ..parallel.multihost import process_rank_world
            rank, world = process_rank_world()
            if world > 1:
                self._rank = int(rank)
                import os
                root, ext = os.path.splitext(self._path)
                self._path = f"{root}.rank{rank}{ext or '.jsonl'}"
        except Exception:
            pass
        from ..utils.diskguard import GuardedWriter
        # policy=None: honor the run's sink_error_policy (disable by
        # default; fatal for runs where lost telemetry is unacceptable).
        # Line-buffered only at the every-record cadence — with a
        # flush_every batch the block buffer is the point (one syscall
        # per cadence, not per record).
        self._fh = GuardedWriter(self._path, sink="events", policy=None,
                                 buffering=1 if self._flush_every == 1
                                 else -1)
        # eager create: readers (obs-report, tests) expect the stream
        # file to exist from the moment the run starts
        self._fh.touch()

    # -- producers -------------------------------------------------------
    def note(self, iteration: int, **fields: Any) -> None:
        """Merge ``fields`` into iteration ``iteration``'s record.  Dict
        fields (``eval``, ``phases``) merge key-wise so multiple producers
        can contribute; scalars are last-write-wins.  Noting any field for
        an iteration commits every pending record of earlier iterations."""
        it = int(iteration)
        rec = self._pending.setdefault(it, {})
        for key, value in fields.items():
            if isinstance(value, dict) and isinstance(rec.get(key), dict):
                rec[key].update(value)
            else:
                rec[key] = value
        for old in sorted(k for k in self._pending if k < it):
            self._commit(old)

    def write_setup(self, account: Dict[str, Any]) -> None:
        """The job's set-up account (obs/setup.py), written at once: the
        first round has just returned and its own record commits only on
        advance, so this one lands ahead of it."""
        self._write({"schema": SCHEMA_VERSION, "setup": account})

    # -- sink ------------------------------------------------------------
    def _commit(self, it: int) -> None:
        rec = self._pending.pop(it)
        line = {"schema": SCHEMA_VERSION, "iter": it}
        line.update(rec)
        self._write(line)

    def _write(self, line: Dict[str, Any]) -> None:
        if self._rank is not None:
            line = {**line, "rank": self._rank}
        ok = self._fh.write(
            json.dumps(_sanitize(line), default=_json_default) + "\n")
        if not ok:
            return              # sink disabled (disk full): drop, run on
        self._written += 1
        self._since_flush += 1
        if self._since_flush >= self._flush_every:
            self._fh.flush()
            self._since_flush = 0

    def close(self) -> None:
        """Commit all pending records (ascending) and close the file."""
        if self._fh.closed:
            return
        for it in sorted(self._pending):
            self._commit(it)
        self._fh.close()

    # -- introspection ---------------------------------------------------
    @property
    def path(self) -> str:
        return self._path

    @property
    def events_written(self) -> int:
        return self._written

    def __enter__(self) -> "EventRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _read_lines(path: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def read_events(path: str) -> List[Dict[str, Any]]:
    """Parse an events file back into its per-iteration records (schema
    round-trip).  The one record without an iteration number, the
    set-up account, is passed over: ``read_setup`` returns it."""
    return [rec for rec in _read_lines(path) if "iter" in rec]


def read_setup(path: str) -> Optional[Dict[str, Any]]:
    """The set-up account of an events file (the newest, where several
    jobs appended to one file); None where the file holds none."""
    found = [rec["setup"] for rec in _read_lines(path) if "setup" in rec]
    return found[-1] if found else None
