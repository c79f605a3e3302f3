"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Multi-chip hardware is not available in CI; sharding/collective tests run on
a virtual 8-device CPU mesh exactly as the driver's dryrun does.
"""

import faulthandler
import functools
import importlib.util
import json
import os
import signal
import sys
import tempfile

import pytest

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


# Seconds a case may take, fixtures included, before it is FAILED where it
# stands (``case_limit`` below) instead of sitting in a wait until the run's
# own clock cuts the whole run. Every case has the default; a file whose cases
# wait on a wall clock for processes, threads or servers has a tighter one,
# and a case that works longer than its file's limit has its own. Sized at
# ~ 3 x the case's seconds on the driver's machine, which reads 1.3 - 1.5 x
# the builder's: ~ 4 x the seconds beside each entry (the file's longest
# case, the largest of five of the builder's six-worker runs, PR 32), in
# steps of 30, 60 at least; the waits inside those cases are 30 s under it.
CASE_LIMIT_S = 300  # 2.5 x the 120 s no case may take (ROADMAP C1)
CASE_LIMITS_S = {
    "test_e2e_local.py": 180,                                            # 38
    "test_e2e_local.py::test_jobs_survive_chaos_kills": 270,             # 44, two waits
    "test_e2e_accuracy.py": 150,                                         # 23
    "test_e2e_accuracy.py::test_real_image_resnet_gang_reaches_accuracy": 390,  # 97
    "test_e2e_evaluator.py": 210,                                        # 45
    "test_multihost.py": 180,                                            # 43
    "test_tools.py": 120,                                                # 26
    "test_process_backend.py": 60,                                       # 12
    # the limit's own case: a child that never ends
    "test_process_backend.py::test_a_hung_child_costs_its_case_its_own_limit": 3,
    "test_warmpool.py": 60,                                              # 9
    "test_hang.py": 60,                                                  # 1
    "test_controller_loop.py": 60,                                       # 1
    "test_dashboard.py": 60,                                             # 3
}


@pytest.fixture(autouse=True)
def case_limit(request):
    """A timer of the case's own: when it fires, every thread's stack goes
    into the failure's message and the case fails by name, there. The alarm
    interrupts the main thread's ``sleep`` / ``wait`` / ``join``, so a hung
    child costs its case's limit, not the rest of the run. (pytest, and
    xdist's workers, run the cases on the main thread, where signals land.)"""
    name = os.path.basename(str(request.node.path))
    limit = CASE_LIMITS_S.get(f"{name}::{request.node.originalname}",
                              CASE_LIMITS_S.get(name, CASE_LIMIT_S))

    def expired(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"{request.node.nodeid} ran into its limit of {limit} s "
                    f"(tests/conftest.py CASE_LIMITS_S); it was here:\n{stacks}",
                    pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield limit
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


def jit_value_and_grad(loss, *args, argnums=0, has_aux=False):
    """``loss(*args)`` and its gradients from ONE compiled call, the way the
    trainer's step runs. A ``shard_map`` body called eagerly is executed
    primitive by primitive on all 8 virtual devices, every call anew (a
    Pallas kernel in it by the interpreter): 10 - 25 x the jitted call's
    trace + compile + run (PR 32). Built and called inside the test: what a
    trace reads from the environment (``TPUJOB_GMM_BLOCK_ROWS``) is the
    test's own."""
    import jax

    return jax.jit(jax.value_and_grad(loss, argnums=argnums, has_aux=has_aux))(*args)


def jit_out_and_grads(fn, *args, argnums=0):
    """``out = fn(*args)`` and the gradients of ``sum(out ** 2)`` (of
    ``out[0]`` where ``fn`` returns a tuple, e.g. ``(y, stats)``) from one
    compiled call: a mesh test's forward value and every gradient."""
    import jax.numpy as jnp

    def loss(*a):
        out = fn(*a)
        return jnp.sum((out[0] if isinstance(out, tuple) else out) ** 2), out

    (_, out), grads = jit_value_and_grad(loss, *args, argnums=argnums, has_aux=True)
    return out, grads


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
