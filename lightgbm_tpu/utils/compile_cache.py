"""Persistent XLA compilation cache + shared training row buckets.

Identical code used to pay minutes of XLA compiles on every training run
(PERF.md, "Carried over") — the warmup tax the compile ledger
(obs/compile_ledger.py) made attributable in round 6.  Two levers kill
most of it, both owned here so every entry point (engine.train, the CLI,
bench.py, chip_smoke.py, the tools) configures them identically instead
of copy-pasting ``jax.config.update`` blocks:

- ``setup()`` points JAX's persistent compilation cache at a directory,
  so a repeated or resumed run loads compiled executables from disk
  instead of re-invoking XLA.  The directory is placed from OUTSIDE:
  where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is there and
  nothing in the code names another (a directory given through the
  ``compile_cache_dir`` param is ignored with one warning).  Where it is
  not set: the ``compile_cache_dir`` param if the user gave one, else one
  fixed directory inside the checkout (``DEFAULT_CACHE_DIR``, derived
  from this package's own path — the path is part of the cache key, so
  it never carries a temporary name, a pid or a time).  The cache is ON
  by default; ``compile_cache_dir`` or the ``LIGHTGBM_TPU_COMPILE_CACHE``
  env var set to ``off``/``none``/``0`` disables it (the env var is an
  off-switch only — it no longer names a directory).

- ``bucket_rows()`` maps a row count onto a small ladder of shared
  shapes, the training-side counterpart of ``serve/batcher.py``'s
  ``BucketLadder``: every jitted training program specializes on N, so
  without bucketing each dataset size is a fresh compile of the most
  expensive programs in the repo (``train_step``/``grow_tree``).
  Training pads rows up to the bucket with zero ``row_weight`` (exactly
  how bagging already excludes rows): histogram digit sums stay exact
  (int32, pad digits zero) so splits match the unpadded run, and only
  the f32 leaf-total reductions re-associate — the same last-bit wiggle
  any row-count change causes.  In exchange nearby row counts share one
  compiled program — in-process across boosters, and across processes
  via the persistent cache.  The serve ladder's pure powers of
  two would pad up to 2x; training rows are heavier than serve batches,
  so this ladder keeps ``ROW_BUCKET_BITS`` mantissa bits (bucket =
  next multiple of ``2^(bitlen(n-1) - bits)``), bounding pad overhead at
  ``2^(1-bits)`` (6.25% worst case, ~1.6% typical, for the default 5
  bits) while still collapsing the shape universe to ~32 buckets per
  octave.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_SWITCH = "LIGHTGBM_TPU_COMPILE_CACHE"   # off-switch only
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

# Below this compile time XLA skips the disk write; 1.0 s keeps every
# program that meaningfully contributes to the warmup tax (the default
# of jax's flag misses mid-size programs that add up across a run).
MIN_COMPILE_SECONDS = 1.0

_OFF_VALUES = {"off", "none", "0", "false", "disabled"}

# Last directory actually applied (None = disabled / never configured);
# setup() is idempotent and cheap, so every entry point just calls it.
_configured_dir: Optional[str] = None


def resolve_dir(cache_dir: Optional[str] = None) -> Optional[str]:
    """Effective cache directory for a run, or None when disabled.

    An off-value in ``LIGHTGBM_TPU_COMPILE_CACHE``, in ``cache_dir`` (the
    ``compile_cache_dir`` param) or in ``JAX_COMPILATION_CACHE_DIR``
    disables.  Otherwise ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``cache_dir`` when given, else ``DEFAULT_CACHE_DIR``."""
    from . import log
    switch = os.environ.get(ENV_SWITCH, "").strip()
    param = str(cache_dir or "").strip()
    placed = os.environ.get(JAX_ENV_DIR, "").strip()
    if any(v.lower() in _OFF_VALUES for v in (switch, param, placed) if v):
        return None
    if switch:
        log.warn_once(
            "compile_cache_env_dir",
            "%s=%s ignored: the variable only switches the cache off; "
            "place the cache with %s or compile_cache_dir",
            ENV_SWITCH, switch, JAX_ENV_DIR)
    if placed:
        if param and param != placed:
            log.warn_once(
                "compile_cache_param_dir",
                "compile_cache_dir=%s ignored: %s=%s places the cache",
                param, JAX_ENV_DIR, placed)
        return placed
    return param or DEFAULT_CACHE_DIR


def setup(cache_dir: Optional[str] = None,
          min_compile_seconds: float = MIN_COMPILE_SECONDS) -> Optional[str]:
    """Configure JAX's persistent compilation cache; returns the
    effective directory (None = disabled).  Idempotent — safe to call
    from every entry point; must run before the first compilation to
    cover it (later calls still cover later compiles)."""
    global _configured_dir
    path = resolve_dir(cache_dir)
    if path is not None:
        # pre-flight writability (utils/diskguard.py): a full/read-only
        # cache volume must degrade to "no persistent cache" with one
        # warning, not surface later as an opaque error from inside
        # XLA's own cache writer mid-compile
        from . import diskguard, log
        if not diskguard.probe_writable(path, sink="compile_cache"):
            log.warn_once(
                "compile_cache_unwritable",
                "compile cache dir %s is not writable; the persistent "
                "XLA cache is DISABLED for this run (every process pays "
                "full compiles)", path)
            path = None
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    if path is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_seconds))
    _configured_dir = path
    return path


def configured_dir() -> Optional[str]:
    """Directory applied by the last setup() call (None = disabled)."""
    return _configured_dir


ROW_BUCKET_BITS = 5


def bucket_rows(n: int, bits: int = ROW_BUCKET_BITS) -> int:
    """Smallest shared-shape bucket >= n: the next multiple of
    ``2^(bitlen(n-1) - bits)``.  Keeps ``bits`` mantissa bits, so pad
    overhead is bounded by ``2^(1-bits)`` (6.25% worst case at the
    default 5) and all row counts in an octave collapse onto at most
    ``2^bits`` shapes."""
    n = int(n)
    if n <= 1:
        return max(n, 0)
    step = 1 << max((n - 1).bit_length() - int(bits), 0)
    return -(-n // step) * step
