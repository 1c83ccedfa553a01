"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of executing the entire suite under
multiple MPI world sizes (``Jenkinsfile:24-27``): here a single process
hosts 8 XLA CPU devices and every sharded op runs a real GSPMD program.
The true multi-process analogue is ``tools/mpirun.py`` (see
``docs/TESTING.md``), which re-runs this same suite inside real
``jax.distributed`` groups; it launches each worker with ``XLA_FLAGS``
pre-set, which the guard below respects.
"""
import faulthandler
import hashlib
import os
import re

import pytest

# world size of the virtual mesh; CI can run the matrix
#   HEAT_TPU_TEST_DEVICES={1,2,5,8} python -m pytest tests/
# (the analogue of the reference's mpirun -n {1,2,5,8} sweep)
_n = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
_flag = f"--xla_force_host_platform_device_count={_n}"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax

jax.config.update("jax_platforms", "cpu")

# set by the tools/mpirun.py coordinator for every pool worker: one
# directory shared by ALL processes of the worker group
_WS_SHARED_ROOT = os.environ.get("HEAT_TPU_WS_SHARED_ROOT")


# No single test may sit longer than this. A wedged CPU client (see
# heat_tpu/core/_dispatch.py) blocks the main thread inside the runtime,
# where no Python-level timeout can reach it: under xdist the worker then
# sat silent until the whole suite's clock ran out. faulthandler's
# watchdog thread needs no GIL: it dumps every thread's stack to the real
# stderr and exits the process, xdist reports the test as crashed, starts
# a new worker and the run reaches its end. Longer than the 600 s the
# multi-process tests give their own children. Under the driver's six
# workers (PR 29, four runs) the slowest test is test_fuzz_battery.py's
# one sweep, 242-256 s: 37-39 % of the bound, not the third that was
# the aim. The slowest chip compile takes 104-122 s (the join's program at
# one payload a side; at question 2's widths, 352-373 s alone and 463 s
# beside a full run, it is marked slow). docs/TESTING.md has the rule for
# a new slow test.
_TEST_BOUND_S = 660


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multihost: also executed inside the real 2-process jax.distributed "
        "runs (tests/test_multihost.py::test_multi_process_pytest_subset)",
    )
    # capture is suspended while hooks configure: fd 2 is the real stderr
    config._heat_tpu_stderr = os.fdopen(os.dup(2), "w")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if _WS_SHARED_ROOT:  # tools/mpirun.py workers have the runner's deadlines
        yield
        return
    faulthandler.dump_traceback_later(
        _TEST_BOUND_S, exit=True, file=item.config._heat_tpu_stderr
    )
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def _rendezvous_dir(root: str, nodeid: str):
    """The per-test rendezvous directory, identical on every process of
    the group: coordinator-chosen root (env) + a digest of the test id —
    every process derives the SAME path with no communication. Process 0
    creates it and the ``replicated_decision`` OR-collective doubles as
    the creation barrier: no rank proceeds before the directory exists,
    and the collective broadcasts that fact instead of each process
    probing the filesystem independently."""
    import pathlib

    from heat_tpu.core.communication import replicated_decision

    digest = hashlib.sha1(nodeid.encode("utf-8")).hexdigest()[:16]
    path = pathlib.Path(root) / f"t_{digest}"
    created = False
    if jax.process_index() == 0:
        path.mkdir(parents=True, exist_ok=True)
        created = True
    if not replicated_decision(created):
        raise RuntimeError(
            f"shared tmp rendezvous: no process created {path} — rank 0 missing?"
        )
    return path


@pytest.fixture
def shared_tmp_path(request, tmp_path):
    """One rendezvous path per test, shared by every process of the group.

    Single-process runs just get ``tmp_path`` (which, under
    ``tools/mpirun.py``, is itself already the shared rendezvous dir —
    see the override below). Inside other multi-process harnesses
    (``tests/test_multihost.py`` sets ``HEAT_TPU_MH_TMP``) the rendezvous
    root comes from that env instead."""
    root = _WS_SHARED_ROOT or os.environ.get("HEAT_TPU_MH_TMP")
    if not root or jax.process_count() == 1:
        return tmp_path
    return _rendezvous_dir(root, request.node.nodeid)


if _WS_SHARED_ROOT:
    # Under the multi-process runner, EVERY test's tmp_path becomes the
    # shared rendezvous directory: the dominant ws-2 failure class was
    # N processes writing/reading N different per-process tmpdirs while
    # the op under test assumes one filesystem path visible everywhere
    # (exactly how a real multi-host run with shared storage behaves).
    @pytest.fixture
    def tmp_path(request, tmp_path_factory):
        if jax.process_count() == 1:
            name = re.sub(r"[\W]", "_", request.node.name)[:30] or "tmp"
            return tmp_path_factory.mktemp(name, numbered=True)
        return _rendezvous_dir(_WS_SHARED_ROOT, request.node.nodeid)
