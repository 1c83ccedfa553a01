"""graftlint unit tests: fixture corpus, waivers, scoping, exit codes.

The fixture corpus under ``tests/lint_fixtures/`` holds one minimal true
positive and one near-miss negative file per rule; each file's first
line declares its expected counts (``# graftlint-fixture: G001=4``) and
the parametrized test below asserts the checker produces EXACTLY those
counts — every unlisted rule must report zero, so a fixture that trips a
neighboring rule fails loudly instead of silently inflating coverage.
"""
import os
import re
import subprocess
import sys

import pytest

from heat_tpu.analysis import graftlint as gl

FIXTURE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lint_fixtures")
FIXTURES = sorted(f for f in os.listdir(FIXTURE_DIR) if f.endswith(".py"))

_HEADER_RE = re.compile(r"#\s*graftlint-fixture:\s*(.+)")


def _expected_counts(path):
    with open(path, encoding="utf-8") as fh:
        first = fh.readline()
    m = _HEADER_RE.search(first)
    assert m, f"{path}: missing '# graftlint-fixture: Gxxx=N' header"
    expected = {rid: 0 for rid in gl.RULES}
    for token in m.group(1).split():
        rid, _, n = token.partition("=")
        assert rid in gl.RULES and n.isdigit(), f"bad fixture token {token!r}"
        expected[rid] = int(n)
    return expected


def test_fixture_corpus_is_complete():
    """Every rule has at least one positive and one negative fixture."""
    assert len(FIXTURES) >= 14
    for rid in gl.RULES:
        stem = rid.lower()
        assert f"{stem}_pos.py" in FIXTURES, f"missing positive fixture for {rid}"
        assert f"{stem}_neg.py" in FIXTURES, f"missing negative fixture for {rid}"
        pos = _expected_counts(os.path.join(FIXTURE_DIR, f"{stem}_pos.py"))
        neg = _expected_counts(os.path.join(FIXTURE_DIR, f"{stem}_neg.py"))
        assert pos[rid] > 0, f"{rid} positive fixture expects no findings?"
        assert neg[rid] == 0, f"{rid} negative fixture expects findings?"


@pytest.mark.parametrize("name", FIXTURES)
def test_fixture(name):
    path = os.path.join(FIXTURE_DIR, name)
    expected = _expected_counts(path)
    findings = gl.lint_file(path)
    got = {rid: 0 for rid in gl.RULES}
    for f in findings:
        got[f.rule] += 1
    assert got == expected, "\n".join(
        [f"{name}: rule counts diverge (got vs expected above)"]
        + [f"  {f.path}:{f.line}: {f.rule} {f.message}" for f in findings]
    )


# ----------------------------------------------------------------- waivers
_SYNC_SNIPPET = "# graftlint: hot-path\ndef f(x):\n    return np.asarray(x){}\n"


def test_waiver_same_line():
    dirty = gl.lint_source(_SYNC_SNIPPET.format(""))
    assert [f.rule for f in dirty] == ["G004"]
    assert not gl.lint_source(_SYNC_SNIPPET.format("  # graftlint: host-sync"))
    # rule id spelling works too
    assert not gl.lint_source(_SYNC_SNIPPET.format("  # graftlint: G004"))
    # 'all' waives any rule
    assert not gl.lint_source(_SYNC_SNIPPET.format("  # graftlint: all"))


def test_waiver_comment_block_above():
    src = (
        "# graftlint: hot-path\n"
        "def f(x):\n"
        "    # this fetch is the op's documented contract,\n"
        "    # graftlint: host-sync - and stays small\n"
        "    # (O(world) metadata only)\n"
        "    return np.asarray(x)\n"
    )
    assert not gl.lint_source(src)


def test_waiver_wrong_rule_does_not_apply():
    assert gl.lint_source(_SYNC_SNIPPET.format("  # graftlint: retrace"))


def test_skip_file_pragma():
    src = "# graftlint: skip-file\n" + _SYNC_SNIPPET.format("")
    assert not gl.lint_source(src)


def test_hot_path_pragma_gates_g004():
    body = "def f(x):\n    return np.asarray(x)\n"
    assert not gl.lint_source(body)  # not hot: no finding
    assert gl.lint_source("# graftlint: hot-path\n" + body)


def test_hot_path_by_location():
    src = "def f(x):\n    return x.item()\n"
    assert gl.lint_source(src, path="heat_tpu/parallel/anything.py")
    for pkg in ("cluster", "spatial", "frame", "regression"):  # what the chip benchmark calls
        assert gl.lint_source(src, path=f"heat_tpu/{pkg}/anything.py")
    assert gl.lint_source(src, path="heat_tpu/core/_operations.py")
    assert not gl.lint_source(src, path="heat_tpu/core/io.py")  # cold module
    assert not gl.lint_source(src, path="heat_tpu/core/printing.py")


# ----------------------------------------------------------- rule details
def test_g001_module_scope_jit_is_fine():
    assert not gl.lint_source("import jax\nj = jax.jit(lambda v: v + 1)\n")


def test_g001_partial_flagged():
    src = (
        "from functools import partial\nimport jax\n"
        "def f(x, n):\n    return jax.jit(partial(step, n=n))(x)\n"
    )
    assert [f.rule for f in gl.lint_source(src)] == ["G001"]


def test_g003_not_fooled_by_nested_def():
    # a collective inside a nested function DEFINED under a rank branch
    # does not run there — defining is not dispatching
    src = (
        "def f(comm, x):\n"
        "    if comm.rank == 0:\n"
        "        def later():\n"
        "            return psum(x)\n"
        "        return later\n"
        "    return None\n"
    )
    assert not gl.lint_source(src)


def test_g006_resilience_first_then_broad_ok():
    src = (
        "def f(fn):\n"
        "    try:\n        return fn()\n"
        "    except CollectiveTimeout:\n        raise\n"
        "    except Exception:\n        return None\n"
    )
    assert not gl.lint_source(src)


def test_g007_scoped_by_location():
    src = 'def f(p, b):\n    with open(p, "wb") as fh:\n        fh.write(b)\n'
    assert [f.rule for f in gl.lint_source(src, path="heat_tpu/resilience/journal.py")] == ["G007"]
    assert [f.rule for f in gl.lint_source(src, path="heat_tpu/core/io.py")] == ["G007"]
    # out of scope: the rest of the tree, and the atomic layer itself
    assert not gl.lint_source(src, path="heat_tpu/cluster/kmeans.py")
    assert not gl.lint_source(src, path="heat_tpu/core/_atomic.py")


def test_g007_atomic_write_staging_exempt():
    src = (
        "def f(p, b):\n"
        "    with atomic_write(p) as tmp:\n"
        '        with open(tmp, "wb") as fh:\n'
        "            fh.write(b)\n"
    )
    assert not gl.lint_source(src, path="heat_tpu/resilience/journal.py")


def test_syntax_error_reported_not_raised():
    findings = gl.lint_source("def f(:\n")
    assert [f.rule for f in findings] == ["SYNTAX"]
    assert gl.exit_code_for(findings) == 128


# ------------------------------------------------------------- exit codes
def test_exit_code_bitmask():
    mk = lambda rule: gl.Finding(rule, "x.py", 1, 0, "m")
    assert gl.exit_code_for([]) == 0
    assert gl.exit_code_for([mk("G001")]) == 1
    assert gl.exit_code_for([mk("G004"), mk("G004")]) == 8
    assert gl.exit_code_for([mk("G001"), mk("G006")]) == 33
    assert gl.exit_code_for([mk("G007")]) == 64
    assert gl.exit_code_for([mk(r) for r in gl.RULES]) == 127


def test_select_subset():
    path = os.path.join(FIXTURE_DIR, "g001_pos.py")
    assert not gl.lint_file(path, select={"G006"})
    assert gl.lint_file(path, select={"G001"})


# ------------------------------------------------------------------- CLI
def test_cli_on_fixture_corpus():
    """The CLI over the whole corpus reports exactly the expected counts
    and encodes every rule in its exit bitmask."""
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "graftlint.py"), FIXTURE_DIR,
         "--format", "json"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    import json

    report = json.loads(proc.stdout.strip().splitlines()[-1])
    want = {rid: 0 for rid in gl.RULES}
    for name in FIXTURES:
        for rid, n in _expected_counts(os.path.join(FIXTURE_DIR, name)).items():
            want[rid] += n
    assert report["counts"] == want
    assert proc.returncode == 127  # every rule bit set by its positive fixture
    assert report["exit_code"] == 127
