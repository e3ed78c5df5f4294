"""Multi-host bring-up: the reference's machine-list discovery mapped to
``jax.distributed.initialize``.

Reference flow (src/network/linkers_socket.cpp Construct + config
machine_list_file): every machine reads the same ``ip port`` list, finds
its own entry, listens on its port, and connects to the others.  The JAX
runtime replaces the TCP linkers/Bruck topology wholesale (SURVEY §2.3):
all that remains is electing a coordinator and numbering the processes,
which this module derives from the SAME machine list file so reference
multi-machine confs run unmodified:

  * coordinator = first list entry (host:port),
  * process_id  = this machine's index in the list, located by matching
    local interface addresses/hostname (override:
    LIGHTGBM_TPU_PROCESS_ID=<idx> for containerized setups where the
    list names VIPs the host cannot see).

After ``jax.distributed.initialize`` the existing device-mesh learners
(parallel/comm.py) and the sharded ingestion (parallel/ingest.py) operate
per-process on the global device set with no further changes — the mesh
axis simply spans hosts, and the psum/all_gather collectives ride
ICI/DCN as laid out by XLA.
"""

from __future__ import annotations

import os
import socket
import time
from typing import List, Optional, Tuple

from ..utils import log


def parse_machine_list(path: str) -> List[Tuple[str, int]]:
    """``ip port`` per line (config.h machine_list_file format).

    Every diagnostic names the file and line number, and duplicate
    ``host port`` entries are fatal HERE — letting them through used to
    surface minutes later as find_process_id's confusing "matches this
    host N times" (a duplicated line is a broken list, not a
    several-processes-per-machine setup)."""
    out: List[Tuple[str, int]] = []
    seen: dict = {}
    with open(path) as fh:
        for line_no, line in enumerate(fh, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.replace(",", " ").split()
            if len(parts) < 2:
                log.fatal("machine_list_file %s line %d: malformed entry "
                          "%r (expected 'ip port')", path, line_no, line)
            try:
                port = int(parts[1])
            except ValueError:
                log.fatal("machine_list_file %s line %d: port %r is not "
                          "an integer", path, line_no, parts[1])
            entry = (parts[0], port)
            if entry in seen:
                log.fatal("machine_list_file %s line %d: duplicate entry "
                          "'%s %d' (first seen on line %d) — every "
                          "process needs a distinct host:port pair",
                          path, line_no, parts[0], port, seen[entry])
            seen[entry] = line_no
            out.append(entry)
    return out


def _local_addresses() -> set:
    names = {socket.gethostname()}
    try:
        names.add(socket.getfqdn())
        for info in socket.getaddrinfo(socket.gethostname(), None):
            names.add(info[4][0])
    except OSError:
        pass
    names.update({"127.0.0.1", "localhost"})
    return names

def find_process_id(machines: List[Tuple[str, int]]) -> Optional[int]:
    """This host's rank in the machine list (linkers_socket.cpp's
    own-entry search), or None when no entry matches."""
    override = os.environ.get("LIGHTGBM_TPU_PROCESS_ID")
    if override is not None:
        try:
            pid = int(override)
        except ValueError:
            log.fatal("LIGHTGBM_TPU_PROCESS_ID=%r is not an integer",
                      override)
        if not 0 <= pid < len(machines):
            # caught here, with a named cause — not as an opaque
            # jax.distributed.initialize failure minutes into bring-up
            log.fatal("LIGHTGBM_TPU_PROCESS_ID=%d is out of range: the "
                      "machine list has %d entr%s (valid ids 0..%d)",
                      pid, len(machines),
                      "y" if len(machines) == 1 else "ies",
                      len(machines) - 1)
        return pid
    local = _local_addresses()
    matches = [i for i, (host, _) in enumerate(machines) if host in local]
    if len(matches) > 1:
        # several processes per machine (same IP, different ports): the
        # reference disambiguates by binding the listed port, which the
        # jax runtime owns here — the launcher must number the processes
        log.fatal("machine_list_file matches this host %d times; set "
                  "LIGHTGBM_TPU_PROCESS_ID per process", len(matches))
    return matches[0] if matches else None


def process_rank_world() -> Tuple[int, int]:
    """``(process_index, process_count)`` WITHOUT initializing a backend
    in single-process runs: reads the distributed service state directly
    (a backend-initializing jax call before ``distributed.initialize``
    would make the later init illegal — see
    maybe_initialize_distributed).  Single-process: ``(0, 1)``."""
    try:
        from jax._src import distributed as _dist
        if getattr(_dist.global_state, "coordinator_address", None) is None:
            return 0, 1
    except Exception:  # pragma: no cover - private-API drift
        return 0, 1
    import jax
    try:
        return int(jax.process_index()), int(jax.process_count())
    except Exception:  # pragma: no cover - mid-init races
        return 0, 1


def globalize_grow_fn(grow_fn, mesh):
    """Bridge a mesh-jitted grow fn into a per-process training loop.

    Under a multi-controller runtime (jax.distributed) the GBDT iteration
    state (scores, gradients, bags) is PROCESS-LOCAL and replicated — every
    process computes identical values from identical seeds, exactly like
    the reference's per-machine GBDT state around its parallel tree
    learners (SURVEY §2.8).  Only tree growth spans processes.  This
    wrapper promotes the (replicated) host values to global arrays on the
    mesh, runs the distributed grow, and gathers the row-sharded outputs
    (leaf_id, score delta) back to every process so the local score update
    can proceed."""
    import numpy as np
    import jax
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    # The leading args (bins, num_bin, is_cat) are per-dataset constants:
    # promote them ONCE instead of pulling the full bin matrix through the
    # host every iteration (x num_class).  Keyed by identity — the caller
    # passes the same resident arrays each round.
    static_cache = {}

    def _promote(a):
        # Device-resident args (grad/hess/row_weight/lr: products of the
        # jitted objective/bagging chain) replicate device-to-device; a
        # host numpy round-trip here would sync the pipeline AND pay a
        # PCIe/DCN copy per array per class per iteration.
        if isinstance(a, jax.Array):
            try:
                return jax.device_put(a, replicated)
            except Exception:
                # runtimes without cross-process device_put: fall through
                # to the host path below
                pass
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(a), mesh, PartitionSpec())

    def wrapped(*args):
        import contextlib
        import time as _time
        from .. import obs
        from . import watchdog as _watchdog
        wd = _watchdog.active_watchdog()
        t0 = _time.perf_counter()
        # Comm::grow: the whole cross-process round — promote, grow,
        # gather.  An obs.span (not a raw perf_counter pair) so the
        # collective time lands in the phase_seconds histogram, the
        # causal trace export, and obs-report --traces; the watchdog
        # phase arms the deadline/peer-death guard around the same
        # region (a dead rank mid-psum trips DistributedAborted here
        # instead of hanging the pod).
        def grow_round():
            glob = []
            for i, a in enumerate(args):
                if i < 3:
                    hit = static_cache.get(i)
                    if hit is None or hit[0] is not a:
                        static_cache[i] = (a, _promote(a))
                    glob.append(static_cache[i][1])
                else:
                    glob.append(_promote(a))
            tree, leaf_id, delta = grow_fn(*glob)
            # tree is replicated: every process holds the full value as
            # its one addressable shard.  leaf_id and delta are
            # row-sharded over processes -> all-gather them back to
            # every process.
            tree = jax.tree.map(
                lambda x: jax.numpy.asarray(x.addressable_data(0)), tree)
            leaf_id = jax.numpy.asarray(
                multihost_utils.process_allgather(leaf_id, tiled=True))
            delta = jax.numpy.asarray(
                multihost_utils.process_allgather(delta, tiled=True))
            return tree, leaf_id, delta

        try:
            with obs.span("Comm::grow"):
                with (wd.phase("Comm::grow") if wd is not None
                      else contextlib.nullcontext()):
                    tree, leaf_id, delta = grow_round()
        except _watchdog.DistributedAborted:
            raise
        except Exception as e:
            # gloo surfaces a killed peer as a connection error instead
            # of a hang: let the watchdog wait for the heartbeats to
            # confirm the death (-> named abort with the distinct exit
            # code) before the raw error is allowed to unwind
            if wd is not None:
                wd.classify_collective_error(e, "Comm::grow")
            raise
        # per-tree wall time of the cross-process growth, including its
        # collectives — the process_allgather above synchronized, so this
        # is a real (not dispatch-only) duration.  Every rank records its
        # own comm_seconds histogram; scraped per rank (metrics_server's
        # rank label) or folded with registry.merge, the distribution is
        # the straggler detector.  The same sample feeds the watchdog's
        # EWMA, from which the auto collective timeout derives.
        dt = _time.perf_counter() - t0
        obs.observe("comm_seconds", dt)
        if wd is not None:
            wd.note_comm_seconds(dt)
        return tree, leaf_id, delta

    return wrapped


def _is_already_initialized(err: BaseException) -> bool:
    s = str(err)
    return "already" in s or "must be called before" in s


def initialize_with_retry(coordinator_address: str, num_processes: int,
                          process_id: int, *, retries: int = 3,
                          backoff_s: float = 2.0,
                          timeout_s: float = 0.0) -> bool:
    """``jax.distributed.initialize`` with exponential backoff.

    Pod bring-up is racy by nature: the coordinator process may start
    seconds (or a scheduler hiccup) after the workers, and one refused
    connection must not kill a run that would have succeeded on the next
    attempt.  Retries ``retries`` times with delays ``backoff_s * 2^k``,
    bounded by ``timeout_s`` overall (<= 0: no deadline).  Returns True
    on success (including launcher-already-initialized); exhausting the
    budget raises a fatal diagnostic naming the coordinator, attempts
    and last error instead of an opaque runtime traceback."""
    import jax

    deadline = (time.monotonic() + timeout_s) if timeout_s > 0 else None
    attempts = max(int(retries), 0) + 1
    delay = max(float(backoff_s), 0.0)
    last_err: Optional[BaseException] = None
    made = 0
    for attempt in range(attempts):
        if attempt > 0:
            if deadline is not None \
                    and time.monotonic() + delay > deadline:
                break
            log.warning("jax.distributed.initialize attempt %d/%d failed "
                        "(%s); retrying in %.1fs", attempt, attempts,
                        last_err, delay)
            time.sleep(delay)
            delay *= 2
        made += 1
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
            return True
        except Exception as e:  # noqa: BLE001 - runtime raises several types
            if isinstance(e, RuntimeError) and _is_already_initialized(e):
                log.warning("jax.distributed.initialize skipped: %s", e)
                return True
            last_err = e
    log.fatal(
        "jax.distributed could not connect to coordinator %s as process "
        "%d/%d after %d attempt(s): %s.  Check that the first "
        "machine_list_file entry names a host every worker can reach, "
        "that the coordinator process is running, and that the port is "
        "open; raise distributed_init_retries / distributed_init_backoff "
        "/ time_out for slow pod bring-up.",
        coordinator_address, process_id, num_processes, made, last_err)


def maybe_initialize_distributed(config) -> bool:
    """Bring up jax.distributed from reference multi-machine config keys.

    Returns True when a multi-host runtime was initialized (or already
    was); False for the single-process case.  Mirrors Network::Init
    being a no-op for num_machines <= 1."""
    num_machines = int(getattr(config, "num_machines", 1) or 1)
    mlist = getattr(config, "machine_list_file", "") or ""
    if num_machines <= 1 or not mlist:
        return False
    import jax
    # NOTE: must not touch jax.process_count()/jax.devices() here — any
    # backend-initializing call makes a later distributed.initialize()
    # illegal.  The launcher-already-initialized case is read from the
    # distributed service state directly.
    try:
        from jax._src import distributed as _dist
        already = bool(getattr(_dist.global_state,
                               "coordinator_address", None))
    except Exception:  # pragma: no cover - private-API drift
        already = False
    if already:
        # already initialized by the launcher: the machine list is only
        # needed to arm the watchdog, so a stale/bad file degrades to a
        # warning — it must not kill a healthy launcher-managed run
        # (and nothing here may fall through to a second initialize)
        try:
            machines = parse_machine_list(mlist)[:num_machines]
            _maybe_start_watchdog(config, machines,
                                  process_rank_world()[0])
        except Exception as e:
            log.warning("launcher-initialized run: machine_list_file %s "
                        "is unusable for the collective watchdog (%s); "
                        "watchdog disabled", mlist, e)
        return True
    machines = parse_machine_list(mlist)
    if len(machines) < num_machines:
        log.fatal("machine_list_file has %d entries but num_machines=%d",
                  len(machines), num_machines)
    machines = machines[:num_machines]
    pid = find_process_id(machines)
    if pid is None:
        log.fatal("Could not find the local machine in machine_list_file; "
                  "set LIGHTGBM_TPU_PROCESS_ID explicitly")
    host, port = machines[0]
    log.info("jax.distributed: coordinator %s:%d, process %d/%d",
             host, port, pid, num_machines)
    # reference time_out is minutes (config.h network section); it bounds
    # the whole retry schedule like it bounds the socket Construct loop
    timeout_s = 60.0 * float(getattr(config, "time_out", 0) or 0)
    initialize_with_retry(
        f"{host}:{port}", num_machines, pid,
        retries=int(getattr(config, "distributed_init_retries", 3) or 0),
        backoff_s=float(getattr(config, "distributed_init_backoff", 2.0)
                        or 0.0),
        timeout_s=timeout_s)
    _maybe_start_watchdog(config, machines, pid)
    return True


def _maybe_start_watchdog(config, machines: List[Tuple[str, int]],
                          pid: int):
    """Arm the collective watchdog (parallel/watchdog.py) for this rank
    once the distributed runtime is up.  ``distributed_heartbeat_ms=0``
    disables it; a mesh bind failure degrades to a warning."""
    hb_ms = float(getattr(config, "distributed_heartbeat_ms", 0.0) or 0.0)
    if hb_ms <= 0:
        return None
    from . import watchdog as wdmod
    return wdmod.start_watchdog(
        machines, int(pid), heartbeat_s=hb_ms / 1000.0,
        timeout_s=float(getattr(config, "collective_timeout_s", 0.0)
                        or 0.0))
