"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Multi-chip hardware is not available in CI; sharding/collective tests run on
a virtual 8-device CPU mesh exactly as the driver's dryrun does.
"""

import functools
import importlib.util
import json
import os
import sys

# Hard-set (not setdefault): a machine with an accelerator would
# otherwise hand the tests its real backend; they run on the CPU's
# virtual devices only.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"


def wait_for(predicate, timeout=30.0, interval=0.05):
    """Poll until predicate() is true; one final check after the deadline so
    a slow scheduler can't produce a spurious timeout."""
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()

# Make the repo root importable regardless of pytest invocation dir.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def load_by_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def benchmarks_conftest():
    """``benchmarks/tests/conftest.py``, loaded by path (here ``conftest``
    names this file). Its ``make_tiny_root`` knows the tiny form of the mixes
    of two runners; a mix of a runner that extends one of them
    (``train_smallthinker``, PR 26) is cut as its family's are — tier-1 loads
    no cell of such a mix from a tiny root."""
    home = os.path.join(_ROOT, "benchmarks")
    conf = load_by_path("benchmarks_tests_conftest",
                        os.path.join(home, "tests", "conftest.py"))
    for name in os.listdir(os.path.join(home, "traffic")):
        with open(os.path.join(home, "traffic", name)) as f:
            runner = json.load(f)["runner"]
        conf.TINY_MIXES.setdefault(runner, conf.TINY_MIXES[runner.split("_")[0]])
    return conf
