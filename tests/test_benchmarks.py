"""Tier-1 guards the one benchmark: tests of ``benchmarks/tests`` are
collected here, from their own files.

``python -m pytest benchmarks/tests`` runs them under the benchmark's own
``conftest.py``. Tier-1 collects ``tests/`` only, where ``conftest`` names
another module, so each file is loaded by path with the benchmark's conftest
under that name for the length of the import. Left out are two tests of
``test_harness.py``: one builds the ``train_smallthinker`` cell from a config
that the tiny root cut as a dense one, the other reads every copied file as
text, a ``__pycache__`` that an earlier import left among them too, so it
passes or fails by what ran before it (PERF.md §7)."""

import os
import sys

from _pytest.fixtures import FixtureFunctionDefinition

from conftest import benchmarks_conftest, load_by_path

HOME = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "tests")
# file -> the tests taken from it (None: every one)
TAKEN = {
    "test_flops.py": None,
    "test_traffic.py": None,
    "test_trace_reduce.py": None,
    "test_reference.py": None,
    "test_control.py": None,
    "test_harness.py": ("test_command_fails_without_a_tpu",
                        "test_unknown_workload_and_too_few_chips_give_no_result"),
}


def _take():
    mine = sys.modules["conftest"]
    sys.modules["conftest"] = conf = benchmarks_conftest()
    try:
        mods = [(conf, ())] + [
            (load_by_path("benchmarks_tests_" + file[:-3], os.path.join(HOME, file)),
             names) for file, names in TAKEN.items()]
    finally:
        sys.modules["conftest"] = mine
    for mod, names in mods:
        for attr, obj in vars(mod).items():
            test = attr.startswith("test_") and (names is None or attr in names)
            if test or isinstance(obj, FixtureFunctionDefinition):
                assert attr not in globals(), f"{mod.__name__}: a second {attr}"
                globals()[attr] = obj


_take()
