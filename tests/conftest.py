"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Multi-chip hardware is not available in CI; sharding/collective tests run on
a virtual 8-device CPU mesh exactly as the driver's dryrun does.
"""

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
