"""What ``Makefile`` and ``.github/workflows/ci.yml`` run must exist.

A gate left pointing at a script, a test file or a ``make`` target that a
clean-up removed fails only in CI, after the merge. Every path the two
files run (comments do not count) is a case here: it exists and, where it
is Python, it compiles. Every ``make <target>`` the workflow calls is a
case too: the ``Makefile`` defines it.
"""

import os
import py_compile
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIRING = ("Makefile", os.path.join(".github", "workflows", "ci.yml"))

_PATH = re.compile(
    r"(?<![\w./-])"
    r"((?:tests|exp|tools|examples|mqtt_tpu|benchmark)/[\w./-]+\.(?:py|sh)"
    r"|[\w-]+\.py)(?!\w)"
)
_MAKE_CALL = re.compile(r"\bmake\s+([\w-]+)")
_MAKE_TARGET = re.compile(r"^([\w-]+):", re.M)


def _code(name):
    """The file's lines with ``#`` comments cut (neither file quotes one)."""
    with open(os.path.join(ROOT, name), encoding="utf-8") as f:
        return "\n".join(line.split("#", 1)[0] for line in f)


def _run_paths():
    return sorted({p for name in WIRING for p in _PATH.findall(_code(name))})


def _make_calls():
    return sorted(set(_MAKE_CALL.findall(_code(WIRING[1]))))


def test_the_extraction_sees_the_wiring():
    """A regex that stopped matching would pass every case below by
    having none: these are on both lists as long as the repo has them."""
    assert "chip_smoke.py" in _run_paths()
    assert "tests/test_tree_mesh.py" in _run_paths()
    assert "verify" in _make_calls()


@pytest.mark.parametrize("path", _run_paths())
def test_a_path_the_gates_run_exists_and_compiles(path, tmp_path):
    full = os.path.join(ROOT, path)
    assert os.path.isfile(full), f"{path} is run by {WIRING} and is not in the tree"
    if path.endswith(".py"):
        py_compile.compile(full, cfile=str(tmp_path / "out.pyc"), doraise=True)


@pytest.mark.parametrize("target", _make_calls())
def test_a_make_target_the_workflow_calls_is_defined(target):
    assert target in _MAKE_TARGET.findall(_code("Makefile")), (
        f"ci.yml calls `make {target}`; the Makefile has no such target"
    )
