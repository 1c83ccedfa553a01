"""The raw walk of a trace and the phases read from it: on the recorded chip traces, and on made-up operations.

``data/kmeans-fit30-3calls.xplane.pb`` and ``data/cdist-4calls.xplane.pb`` are PR 24's and PR 22's chip traces (TPU
v5 lite): their programs were written before any ``ht.phase:`` scope was, so they show what the walk reads of an
operation's metadata (``tf_op``, ``source``, ``bytes_accessed``) and that a trace without scopes reads as nothing.
``data/groupby-q5-2calls.xplane.pb`` and ``data/lasso-fit1-4calls.xplane.pb`` are PR 35's (seeds 3500001002 and
3500001001): the traced slices of ``groupby-q5-1e8`` (two calls of ``groupby("id6").agg`` on 1e8 rows) and of
``lasso-fit1-eurad-1e7`` (the first four of 46 calls of ``Lasso.fit`` on (1e7, 108) f32), their programs compiled with
the scopes from an empty compile cache. The device planes are as recorded; of the host planes only the ``bench.call``
annotations, the ``ht.*`` spans, the launches and the completion notices were kept (2.0 and 1.6 MB before, 0.3 and
0.1 MB now). The identity of phases, unphased rest and device time is pinned on them.
"""
import importlib.util
import os
import sysconfig

import pytest
from conftest import CHIP, ROOT

from harness import manifest, phases, report, xplane

DATA = os.path.join(CHIP, "tests", "data")
KMEANS, CDIST = os.path.join(DATA, "kmeans-fit30-3calls.xplane.pb"), os.path.join(DATA, "cdist-4calls.xplane.pb")
GROUPBY, LASSO = os.path.join(DATA, "groupby-q5-2calls.xplane.pb"), os.path.join(DATA, "lasso-fit1-4calls.xplane.pb")
NEW = ["sort_ms.call", "scan_ms.call", "compact_ms.call", "gram_ms.call", "unphased_ms.call", "compact_steps"]


def run_of(trace):
    return report.TracedRun(config={}, chips=1, work={}, peaks=None, calls=len(trace.calls) if trace else 0,
                            counters={}, trace=trace)


def named(ops, name):
    return [op for op in ops if xplane.op_name(op.name) == name]


# ---- the recorded traces
@pytest.mark.parametrize("path,annotation", [(KMEANS, xplane.ANNOTATION), (CDIST, "chipbench.call")])
def test_the_walk_gives_the_events_starts_and_ends_that_the_reduction_reads(path, annotation):
    trace = xplane.reduce(path, annotation=annotation)
    (plane, ops), = phases.walk(path)
    assert plane == trace.devices[0]["name"] == "/device:TPU:0" and trace.clock_shift_s == 0.0
    assert [(op.name, op.start, op.end) for op in ops] == trace.devices[0]["ops"]  # to the last digit
    assert {op.program for op in ops} == {name for name, _, _ in trace.devices[0]["modules"]}


def test_the_walk_reads_an_operations_name_stack_source_and_bytes():
    (_, ops), = phases.walk(KMEANS)
    kernel = named(ops, "_lloyd_call.7")
    assert len(kernel) == 90 and {op.program for op in kernel} == {"jit__lloyd_fit"}
    assert {op.tf_op for op in kernel} == {"jit(_lloyd_fit)/while/body/jit(_lloyd_call)/pallas_call:"}
    assert {op.source for op in kernel} == {"/root/repo/heat_tpu/core/kernels/lloyd.py:123"}
    loop, = {(op.tf_op, op.source) for op in named(ops, "while.5")}
    assert loop == (None, "/root/repo/heat_tpu/cluster/kmeans.py:141")  # a loop itself: a source, no name stack
    assert {op.tf_op for op in named(ops, "copy-start.1")} == {None}  # the compiler's own: neither
    (_, ops), = phases.walk(CDIST)
    assert {(op.bytes_accessed, op.tf_op) for op in named(ops, "fusion.2")} == {(6_406_079_488, "jit(_sqrt_quadratic_expand)/dot_general:")}


def test_a_trace_of_programs_without_a_scope_reads_as_nothing():
    trace = xplane.reduce(KMEANS)
    assert phases.of(run_of(trace), path=KMEANS) is None
    assert "_ht_phases" in trace.__dict__  # computed once a trace, as the host spans are
    for name in NEW[:-1]:
        assert manifest.load_module("layer_metrics", name).read(run_of(trace)) is None  # from the cache: no file is looked for


def _tsl_xplane_pb2():
    """TensorFlow's generated ``xplane_pb2`` loaded by its path (it imports protobuf alone), or a skip."""
    path = os.path.join(sysconfig.get_paths()["purelib"], "tensorflow", "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.isfile(path):
        pytest.skip("no tensorflow/tsl/profiler/protobuf/xplane_pb2.py on this machine")
    spec = importlib.util.spec_from_file_location("xplane_pb2_by_path", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("path", [KMEANS, CDIST])
def test_the_walk_agrees_field_for_field_with_the_generated_protocol_buffer(path):
    space = _tsl_xplane_pb2().XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    plane, = [p for p in space.planes if p.name.startswith("/device:TPU")]
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    programs = {}
    for line in plane.lines:
        for event in line.events if line.name == xplane.MODULES else ():
            text = plane.event_metadata[event.metadata_id].name
            programs[int(text[text.rindex("(") + 1:-1])] = text[:text.rindex("(")]
    line, = [ln for ln in plane.lines if ln.name == xplane.OPS]
    want = []
    for event in line.events:
        meta = plane.event_metadata[event.metadata_id]
        stats = {}
        for stat in meta.stats:
            kind = stat.WhichOneof("value")
            stats[names[stat.metadata_id]] = names[stat.ref_value] if kind == "ref_value" else getattr(stat, kind)
        start_ns = line.timestamp_ns + event.offset_ps // 1000
        want.append(phases.Op(program=programs[stats["program_id"]], name=meta.name, start=float(start_ns) * 1e-9,
                              end=(float(start_ns) + float(event.duration_ps // 1000)) * 1e-9, tf_op=stats.get("tf_op"),
                              source=stats.get("source"), bytes_accessed=stats.get("bytes_accessed")))
    (_, got), = phases.walk(path)
    assert got == want


def test_a_file_cut_short_is_an_error_and_not_a_shorter_trace(tmp_path):
    with open(CDIST, "rb") as fh:
        whole = fh.read()
    cut = tmp_path / "cut.xplane.pb"
    cut.write_bytes(whole[: len(whole) // 2])
    with pytest.raises(ValueError, match="cut short"):
        phases.walk(str(cut))


# ---- the recorded traces of programs with scopes
def read(name, run):
    return manifest.load_module("layer_metrics", name).read(run)


def self_ms_per_call(trace, name):
    """Self milliseconds a call of the first device's operations called ``name``."""
    return sum(secs for text, secs, _ in xplane.self_times(trace.devices[0]["ops"]) if xplane.op_name(text) == name) * 1e3 / len(trace.calls)


def test_recorded_frame_cell_phases_and_unphased_rest_add_up_to_the_device_time():
    trace = xplane.reduce(GROUPBY)
    run = run_of(trace)
    found = phases.of(run, path=GROUPBY)
    device_ms = trace.device_s_per_call() * 1e3
    assert device_ms == pytest.approx(1167.964591, rel=1e-9)
    everything = (sum(found.s_per_call.values()) + found.unphased_s_per_call) * 1e3
    assert everything == pytest.approx(device_ms, rel=1e-3) and everything <= device_ms  # what stands between operations is no phase's
    assert sorted(found.s_per_call) == ["compact", "move", "scan", "sort"]  # one chip elects nothing
    assert read("sort_ms.call", run) == pytest.approx(742.562369, rel=1e-9)
    assert read("scan_ms.call", run) == pytest.approx(91.1329875, rel=1e-9)
    assert read("compact_ms.call", run) == pytest.approx(301.8814355, rel=1e-9)
    assert read("unphased_ms.call", run) == pytest.approx(32.3549665, rel=1e-9) and read("unphased_ms.call", run) < 0.05 * device_ms
    assert read("gram_ms.call", run) is None  # no such phase in a frame program
    # the sort by key is one operation, and the phase is that operation and the merge's small sort
    plan, merge = found.by_program["jit_frame_plan"], found.by_program["jit_frame_merge"]
    label, seconds = trace.top_ops()[0]  # what the ledger's ``breakdown.device_ops`` shows of it
    assert label.startswith("sort.17 (s32[100000000], ") and plan["sort"] == pytest.approx(seconds, rel=1e-12) == pytest.approx(0.739961593, rel=1e-9)
    assert (plan["sort"] + merge["sort"]) * 1e3 == pytest.approx(read("sort_ms.call", run), rel=1e-12)
    programs = trace.module_s_per_call()
    for program in ("jit_frame_plan", "jit_frame_merge"):  # program by program too: its operations cover it, and no more
        assert sum(found.by_program[program].values()) == pytest.approx(programs[program], rel=1e-3), program
        assert sum(found.by_program[program].values()) <= programs[program]
    # the largest unphased operation is the compaction's cumulative sum, which JAX lowers under a name of its own
    label, source, seconds = found.unphased_ops[0]
    assert label.startswith("reduce-window.1 ") and not source and seconds * 1e3 == pytest.approx(18.9405045, rel=1e-9)


def test_recorded_fit_sweep_is_the_loops_and_the_three_phases_are_the_fit():
    trace = xplane.reduce(LASSO)
    run = run_of(trace)
    found = phases.of(run, path=LASSO)
    assert len(trace.calls) == 4 and sorted(found.s_per_call) == ["gram", "moments", "sweep"]
    assert read("gram_ms.call", run) == pytest.approx(12.0375105, rel=1e-9)
    assert read("unphased_ms.call", run) == pytest.approx(0.00079, rel=1e-6)  # the compiler's copies outside the loops
    columns, fit = read("cd_loop_ms.call", run), read("cd_fit_ms.call", run)
    sweeps_own = self_ms_per_call(trace, "while.16")  # the sweeps' loop around the columns' ``while.19``
    assert found.ms("sweep") == pytest.approx(columns + sweeps_own, rel=1e-2) and found.ms("sweep") >= columns + sweeps_own
    assert found.ms("gram") + found.ms("moments") + found.ms("sweep") == pytest.approx(fit, rel=1e-2)
    total = (sum(found.s_per_call.values()) + found.unphased_s_per_call) * 1e3
    assert total == pytest.approx(trace.device_s_per_call() * 1e3, rel=1e-3)
    assert read("sort_ms.call", run) is None and read("compact_ms.call", run) is None


# ---- made-up operations
def op(name, start, end, tf_op=None, program="jit_f", source=None):
    return phases.Op(program=program, name=f"%{name} = f32[8]{{0}} fusion()", start=start, end=end, tf_op=tf_op,
                     source=source, bytes_accessed=None)


def by_name(attributed):
    return {xplane.op_name(o.name): (pytest.approx(seconds), phase) for o, seconds, phase in attributed}


def test_the_innermost_scope_names_an_operation_and_one_without_any_is_unphased():
    assert phases.phase_of("jit(f)/ht.phase:outer/while/body/ht.phase:inner/add:") == "inner"
    assert phases.phase_of("jit(f)/shard_map/ht.phase:sort/sort:") == "sort"
    assert phases.phase_of("ht.phase:scan/while/body/select_n:") == "scan"
    assert phases.phase_of("jit(f)/not.ht.phase:x/add:") is None and phases.phase_of("jit(f)/add:") is None
    assert phases.phase_of(None) is None and phases.phase_of("") is None


def test_a_loop_takes_its_bodys_phase_and_what_the_compiler_put_in_it_the_loops():
    ops = [
        op("sort.1", 0.0, 5.0, "jit(f)/ht.phase:sort/sort:"),
        op("while.2", 5.0, 15.0),  # a TPU trace gives a loop no name stack
        op("fusion.3", 6.0, 9.0, "jit(f)/ht.phase:scan/while/body/select_n:"),
        op("copy.4", 9.0, 10.0),  # the compiler's copy into the carry: no name stack, inside the loop
        op("while.5", 10.0, 14.0),  # a loop in the loop
        op("fusion.6", 11.0, 13.0, "jit(f)/ht.phase:scan/while/body/while/body/add:"),
        op("concatenate.7", 15.0, 17.0, "jit(f)/concatenate:"),
        op("copy.8", 17.0, 18.0),  # the compiler's, outside every loop
    ]
    assert by_name(phases.attribute(ops)) == {
        "sort.1": (5.0, "sort"), "while.2": (2.0, "scan"), "fusion.3": (3.0, "scan"), "copy.4": (1.0, "scan"),
        "while.5": (2.0, "scan"), "fusion.6": (2.0, "scan"), "concatenate.7": (2.0, None), "copy.8": (1.0, None)}
    found = phases.reduce([("/device:TPU:0", ops)], (0.0, 18.0), 0.0, calls=2)
    assert found.s_per_call == {"sort": pytest.approx(2.5), "scan": pytest.approx(5.0)}
    assert found.unphased_s_per_call == pytest.approx(1.5)
    assert sum(found.s_per_call.values()) + found.unphased_s_per_call == pytest.approx(18.0 / 2)  # no moment twice, none lost
    assert found.ms("sort") == pytest.approx(2500.0) and found.ms("gram") is None
    assert [row[0] for row in found.unphased_ops] == ["concatenate.7 f32[8]", "copy.8 f32[8]"]


def test_a_loop_whose_body_disagrees_or_says_nothing_is_unphased():
    mixed = [op("while.1", 0.0, 10.0), op("a.2", 1.0, 2.0, "jit(f)/ht.phase:scan/while/body/add:"),
             op("b.3", 2.0, 3.0, "jit(f)/while/body/mul:")]
    assert by_name(phases.attribute(mixed)) == {"while.1": (8.0, None), "a.2": (1.0, "scan"), "b.3": (1.0, None)}
    silent = [op("while.1", 0.0, 10.0), op("copy.2", 1.0, 2.0)]
    assert by_name(phases.attribute(silent)) == {"while.1": (9.0, None), "copy.2": (1.0, None)}


def test_the_window_the_clock_shift_and_the_planes_are_the_reductions():
    one = [op("sort.1", 0.0, 4.0, "jit(f)/ht.phase:sort/sort:"), op("sort.1", 10.0, 14.0, "jit(f)/ht.phase:sort/sort:")]
    two = [op("sort.1", 0.0, 2.0, "jit(f)/ht.phase:sort/sort:"), op("add.2", 10.0, 12.0, "jit(f)/add:")]
    both = phases.reduce([("/device:TPU:0", one), ("/device:TPU:1", two)], (0.0, 20.0), 0.0, calls=2)
    assert both.s_per_call == {"sort": pytest.approx((8.0 + 2.0) / 2 / 2)} and both.unphased_s_per_call == pytest.approx(2.0 / 2 / 2)
    assert both.by_program == {"jit_f": {"sort": pytest.approx(2.5), None: pytest.approx(0.5)}}
    late = phases.reduce([("/device:TPU:0", one)], (0.0, 12.0), 3.0, calls=1)  # the second sort starts at 13: outside
    assert late.s_per_call == {"sort": pytest.approx(4.0)}
    assert phases.reduce([("/device:TPU:0", two[1:])], (0.0, 20.0), 0.0, calls=1) is None  # no scope at all


def test_the_table_for_people_names_programs_phases_and_the_unphased_by_source(capsys):
    ops = [op("sort.1", 0.0, 5.0, "jit(f)/ht.phase:sort/sort:"),
           op("concatenate.7", 5.0, 7.0, "jit(f)/concatenate:", source="/root/repo/heat_tpu/frame/_shuffle.py:640")]
    phases.table(phases.reduce([("/device:TPU:0", ops)], (0.0, 10.0), 0.0, calls=1), programs={"jit_f": 7.5})
    phases.table(None)
    out = capsys.readouterr().out
    assert "jit_f  7000.000" in out and "ht.phase:sort" in out and "(unphased)" in out
    between = [line.split() for line in out.splitlines() if "(between operations)" in line]
    assert between == [["(between", "operations)", "500.000"]] * 2  # the program's, and all programs'
    assert "concatenate.7 f32[8]  /root/repo/heat_tpu/frame/_shuffle.py:640" in out
    assert "no operation carries an ht.phase: scope" in out


# ---- the manifest
@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_declares_what_the_manifest_lists_and_reads_nothing_without_a_trace(name):
    entry, = [m for m in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"] if m["name"] == name]
    reader = manifest.load_module("layer_metrics", name)
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (entry["name"], entry["unit"], entry["layer"], entry["moves"])
    assert entry["layer"] == "compiled program (XLA)" and entry["moves"] == "call_ms.p50" and entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if name == "compact_steps" else "program_span")
    frame = ["groupby-q5-1e8", "join-q2-medium-inner", "join-q5-big-inner-4chip"]
    lasso = "lasso-fit1-eurad-1e7"
    assert entry["workloads"] == {"gram_ms.call": [lasso], "unphased_ms.call": [*frame, lasso]}.get(name, frame)
    if name != "compact_steps":  # the gauge needs no trace; the phases do
        assert reader.read(run_of(None)) is None  # the --rehearse path


def test_the_harness_file_imports_the_standard_library_and_the_harness_alone():
    import ast
    import sys

    with open(os.path.join(CHIP, "harness", "phases.py"), encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    roots = {alias.name.split(".")[0] for node in ast.walk(tree) if isinstance(node, ast.Import) for alias in node.names}
    roots |= {node.module.split(".")[0] for node in ast.walk(tree) if isinstance(node, ast.ImportFrom) and node.module and not node.level}
    assert roots - {"harness", "__future__"} <= set(sys.stdlib_module_names), roots
