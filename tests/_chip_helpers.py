"""What the ``test_chip_compile*.py`` files share: the described chip and how to read a program.

Four files since PR 34 (the Lasso fit's); ``test_lasso_reference.py`` reads
a CPU program's loops with the same helpers.

Interpret mode discharges a pallas kernel to plain jax on the CPU, so it
passes what Mosaic refuses: a scalar store to VMEM, a 64-bit index-map
literal, a slice off the (8, 128) tiling, a copy the layout forces. The
TPU compiler is installed here and compiles for a chip that is described
and not attached: device 0 of a ``v5e:2x2`` topology, or a mesh of its
four devices. Nothing runs: a compile that passes is not a chip run.

The files are split by what is compiled (the Mosaic kernels, the
groupby's programs, the join's program) because pytest-xdist's
``--dist loadfile`` hands a worker a whole file: each is a chain of
compiles that nothing can shorten, so each has to be able to run beside
the others. xdist hands files out by their number of tests, most first
(``LoadScopeScheduling`` sorts its queue so; a file's name decides
nothing), so files of 20, 5 and 2 tests start late and one file of all
27 would end the run alone: ``docs/TESTING.md`` has the measured runs.

The topology is described inside the module-scoped fixture ``topo``,
which skips if it cannot be; nothing here or in those files touches
``topologies`` while a module is imported (xdist workers all import every
test file, and by default only one process may load the TPU's library).
Three workers describe it at once now. With ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``
in the environment, as the driver's command has it, each gets its
description whatever the others do (three processes at once, 3 s each:
PR 29's probe). Without it the first process to ask holds the library's
lock (``/tmp/libtpu_lockfile``) until it exits, and every other one is
refused ("ABORTED: Internal error when accessing libtpu multi-process
lockfile"), which ``topo`` turns into a skip: an xdist worker lives as
long as the run, so two of the three files skip whole, and which two is
a matter of timing. The count of passes is steady only with the variable.
The tests do not set it: ``docs/TESTING.md`` has it beside the command.
Compiles run in the test's own process with the persistent compilation
cache off around them: an entry written for a described device cannot
be read back without the chip.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

# The old bench's shapes (``bench.py``: N, F, K; MOM_N, MOM_F; CDIST_F), kept
# here by value so that retiring that file leaves these tests as they are.
BENCH_KMEANS = (1 << 19, 32, 8)  # samples, features, clusters
BENCH_MOMENTS = (1 << 22, 32)  # rows, features
BENCH_CDIST_FEATURES = 18  # SUSY's feature count


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh

    from heat_tpu.core.communication import SPLIT_AXIS

    return Mesh(np.array(topo.devices), (SPLIT_AXIS,))


def _one_chip(topo):
    """The mesh of one described chip: every benchmark cell's layout."""
    from jax.sharding import Mesh

    from heat_tpu.core.communication import SPLIT_AXIS

    return Mesh(np.array(topo.devices[:1]), (SPLIT_AXIS,))


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernel(lowered, max_temp_bytes: int):
    """Compile; the Mosaic kernel must be in the program, and XLA must not
    have had to copy the operand into another layout around it."""
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= max_temp_bytes, f"{temp} bytes of temporaries around the kernel"
    return compiled


def _frame_mesh(mesh):
    """(comm, sharding of a column, sharding of a replicated vector) over the described ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.core.communication import SPLIT_AXIS, MeshCommunication

    comm = MeshCommunication(devices=list(mesh.devices.flat))
    return comm, NamedSharding(comm.mesh, P(SPLIT_AXIS)), NamedSharding(comm.mesh, P())


def _indexed_ops(text: str, b: int):
    """The gather and scatter instructions of a compiled program whose result
    has ``b`` elements: a gather through a block-long index vector, a scatter
    into a block-long column."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= (\S+) (gather|scatter)\((.*)", line)
        if m and re.search(rf"\[(\d+,)*{b}(,\d+)*\]", m.group(1) + m.group(3)):
            found.append(line.strip()[:160])
    return found


def _hlo_computations(text: str):
    """name -> lines of each computation of a compiled module's text."""
    computations, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if m and not line.startswith(" "):
            name = m.group(1)
            computations[name] = []
        elif name is not None:
            computations[name].append(line)
    return computations


def _reached_from_loops(text: str):
    """The lines of every computation a ``while`` of the module runs: body, condition, what they call."""
    computations = _hlo_computations(text)
    joined = {n: "\n".join(lines) for n, lines in computations.items()}
    calls = {n: set(re.findall(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)", body))
             | {c for group in re.findall(r"branch_computations=\{([^}]*)\}", body) for c in re.findall(r"[\w.\-]+", group)}
             for n, body in joined.items()}
    todo = [c for body in joined.values() for line in body.splitlines() if " while(" in line
            for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", line)]
    assert todo, "no while in the module"
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in computations:
            continue
        seen.add(c)
        todo += calls[c]
    return [line for c in seen for line in computations[c]]


_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute", "reduce-scatter", "collective-broadcast")


def _collectives(lines):
    """Those of ``lines`` that are a collective instruction, plain or ``-start``."""
    return [line.strip()[:120] for line in lines if any(f" {c}(" in line or f" {c}-start(" in line for c in _COLLECTIVES)]
