"""Test config: force an 8-virtual-device CPU platform so data/feature/voting
parallel paths are testable without a TPU pod (SURVEY.md §4).

The tests must never take the chip: the driver runs them with several
workers, and a chip belongs to one process at a time.  So the CPU is forced
whatever the environment presets — the env var AND the live config, before any
test imports jax.  What the chip's compiler accepts is checked without a chip
in tests/test_tpu_compile.py; what the chip computes, by chip_smoke.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import os  # noqa: E402

import pytest  # noqa: E402

_terminal_reporter = None


def pytest_configure(config):
    global _terminal_reporter
    _terminal_reporter = config.pluginmanager.getplugin("terminalreporter")


def pytest_runtest_logreport(report):
    """The tier-1 harness greps progress dots from a piped log; piped
    stdout is block-buffered, so a timeout kill silently drops every
    completed test still in the buffer.  Flush after each test so the
    log reflects what actually ran."""
    if report.when != "teardown" or _terminal_reporter is None:
        return
    try:
        _terminal_reporter._tw._file.flush()
    except Exception:
        pass


def pytest_collection_modifyitems(config, items):
    """Tests driving the reference's example data need the read-only
    /root/reference mount of the dev box; skip PER TEST elsewhere
    (container / CI runners) so self-contained tests in the same module
    still run."""
    if os.path.exists("/root/reference"):
        return
    import inspect
    import re

    skip = pytest.mark.skip(reason="/root/reference mount not available")
    for item in items:
        fn = getattr(item, "function", None)
        if fn is None:
            continue
        try:
            src = inspect.getsource(fn)
        except (OSError, TypeError):
            continue
        # direct literal use, or use of a module-level constant that
        # holds a reference path (REF, BINARY_TRAIN, CASES, ...)
        needs = "/root/reference" in src
        if not needs:
            for name, val in vars(item.module).items():
                if "/root/reference" in str(val) and \
                        re.search(rf"\b{re.escape(name)}\b", src):
                    needs = True
                    break
        if needs:
            item.add_marker(skip)
