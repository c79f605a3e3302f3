"""Process backend tests: fake records intents; the real backends (pure
Python and native C++ supervisor) launch OS processes and report
phase/exit codes into the store. Lifecycle tests run against BOTH real
backends — behavioral parity between them is itself the contract."""

import os
import subprocess
import sys
import time

import pytest

from tf_operator_tpu.api.types import ObjectMeta
from conftest import wait_for
from tf_operator_tpu.runtime import (
    FakeProcessControl,
    LocalProcessControl,
    NativeProcessControl,
    Process,
    ProcessPhase,
    ProcessSpec,
    Store,
)

BACKENDS = [LocalProcessControl, NativeProcessControl]


def proc(name, env=None):
    return Process(
        metadata=ObjectMeta(name=name),
        spec=ProcessSpec(job_name="j", replica_type="Worker", env=env or {}),
    )


def test_fake_records_actions():
    fake = FakeProcessControl()
    fake.create_process(proc("a"))
    fake.delete_process("default", "a")
    assert [p.metadata.name for p in fake.created] == ["a"]
    assert fake.deleted == ["default/a"]


def test_fake_error_injection():
    fake = FakeProcessControl()
    fake.create_error = RuntimeError("boom")
    with pytest.raises(RuntimeError):
        fake.create_process(proc("a"))


def script_builder(code):
    """Run a tiny inline script instead of the rendezvous harness."""

    def build(process):
        return [sys.executable, "-c", code]

    return build


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_success_cycle(backend):
    store = Store()
    ctl = backend(store, command_builder=script_builder("import sys; sys.exit(0)"))
    ctl.create_process(proc("ok"))
    assert wait_for(
        lambda: store.get("Process", "default", "ok").status.phase is ProcessPhase.SUCCEEDED
    )
    st = store.get("Process", "default", "ok").status
    assert st.exit_code == 0 and st.pid is not None


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_failure_exit_code(backend):
    store = Store()
    ctl = backend(store, command_builder=script_builder("import sys; sys.exit(7)"))
    ctl.create_process(proc("bad"))
    assert wait_for(
        lambda: store.get("Process", "default", "bad").status.phase is ProcessPhase.FAILED
    )
    assert store.get("Process", "default", "bad").status.exit_code == 7


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_env_injection(backend):
    store = Store()
    code = "import os, sys; sys.exit(3 if os.environ.get('TPUJOB_X') == 'y' else 1)"
    ctl = backend(store, command_builder=script_builder(code))
    ctl.create_process(proc("envy", env={"TPUJOB_X": "y"}))
    assert wait_for(lambda: store.get("Process", "default", "envy").is_finished())
    assert store.get("Process", "default", "envy").status.exit_code == 3


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_delete_terminates_running_child(backend):
    store = Store()
    ctl = backend(store, command_builder=script_builder("import time; time.sleep(60)"))
    ctl.create_process(proc("sleeper"))
    assert wait_for(
        lambda: store.get("Process", "default", "sleeper").status.phase is ProcessPhase.RUNNING
    )
    ctl.delete_process("default", "sleeper")
    # object gone from the store; child reaped
    from tf_operator_tpu.runtime import NotFoundError

    with pytest.raises(NotFoundError):
        store.get("Process", "default", "sleeper")
    assert not ctl._children


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_bad_command_reports_failed(backend):
    store = Store()

    def build(process):
        return ["/nonexistent/binary"]

    ctl = backend(store, command_builder=build)
    ctl.create_process(proc("ghost"))
    assert wait_for(
        lambda: store.get("Process", "default", "ghost").status.phase is ProcessPhase.FAILED
    )
    assert store.get("Process", "default", "ghost").status.exit_code == 127


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_log_capture(backend, tmp_path):
    store = Store()
    ctl = backend(
        store,
        command_builder=script_builder("print('hello from child', flush=True)"),
        log_dir=str(tmp_path),
    )
    ctl.create_process(proc("logged"))
    assert wait_for(lambda: store.get("Process", "default", "logged").is_finished())
    log = tmp_path / "default_logged.log"
    assert wait_for(lambda: log.exists() and b"hello from child" in log.read_bytes())


# ---- native-supervisor specifics -----------------------------------------


def test_delete_while_launching_does_not_doom_recreated_incarnation():
    """A tombstone from delete-during-launch is keyed by uid: a same-name
    recreate (gang restart) must launch normally, not be killed at birth by
    the OLD incarnation's tombstone (which would wedge the job Pending)."""
    import threading

    store = Store()
    gate = threading.Event()
    ctl = LocalProcessControl(
        store, command_builder=script_builder("import time; time.sleep(30)")
    )
    real_spawn = ctl._spawn
    blocked_uids = set()

    def gated_spawn(process, env, log_path):
        if process.metadata.uid in blocked_uids:
            gate.wait(10)  # hold the FIRST incarnation's launch in flight
        return real_spawn(process, env, log_path)

    ctl._spawn = gated_spawn
    first = proc("w0")
    stored_first = store.create(first)
    blocked_uids.add(stored_first.metadata.uid)
    ctl.launch_existing(stored_first)
    # delete while its launch is blocked: tombstones the first uid
    ctl.delete_process("default", "w0")
    # same-name recreate (fresh uid) — must not consume the tombstone
    ctl.create_process(proc("w0"))
    gate.set()  # old launch now returns; its child must be reaped silently

    def second_running():
        p = store.get("Process", "default", "w0")
        return p.status.phase is ProcessPhase.RUNNING

    assert wait_for(second_running, timeout=10)
    # old incarnation's monitor must not have clobbered the new entry
    assert ctl.tracks("default", "w0")
    ctl.shutdown()


def test_native_normalizes_signal_exit_codes():
    """A SIGTERM death must surface as 143 (128+15) — the convention the
    exit-code taxonomy (train_util.go:18-53) classifies as retryable — not
    Python's -15."""
    store = Store()
    code = "import os, signal; os.kill(os.getpid(), signal.SIGTERM)"
    ctl = NativeProcessControl(store, command_builder=script_builder(code))
    ctl.create_process(proc("sig"))
    assert wait_for(lambda: store.get("Process", "default", "sig").is_finished())
    assert store.get("Process", "default", "sig").status.exit_code == 143

    from tf_operator_tpu.utils.exit_codes import is_retryable

    assert is_retryable(143)


def test_native_group_kill_reaps_grandchildren():
    """Deleting a process must take down children IT forked (the C++
    supervisor signals the whole setsid process group)."""
    store = Store()
    marker = "tpujob-native-grandchild-marker"
    # Child forks a grandchild (identifiable via argv marker) then sleeps.
    code = (
        "import subprocess, sys, time; "
        f"subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(300)', '{marker}']); "
        "time.sleep(300)"
    )
    ctl = NativeProcessControl(store, command_builder=script_builder(code))
    ctl.create_process(proc("forker"))
    assert wait_for(
        lambda: store.get("Process", "default", "forker").status.phase is ProcessPhase.RUNNING
    )

    def grandchild_alive():
        out = subprocess.run(["pgrep", "-f", marker], capture_output=True, text=True)
        return out.returncode == 0

    assert wait_for(grandchild_alive)
    ctl.delete_process("default", "forker")
    assert wait_for(lambda: not grandchild_alive(), timeout=10)


def test_native_group_reaped_when_leader_dies_on_its_own():
    """Pod semantics: the leader exiting by itself (crash, chaos kill) must
    still take its forked children down — not only explicit deletes."""
    store = Store()
    marker = "tpujob-native-selfdeath-marker"
    # Child forks a long-lived grandchild then EXITS on its own.
    code = (
        "import subprocess, sys; "
        f"subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(300)', '{marker}']); "
        "sys.exit(0)"
    )
    ctl = NativeProcessControl(store, command_builder=script_builder(code))
    ctl.create_process(proc("selfdeath"))
    assert wait_for(lambda: store.get("Process", "default", "selfdeath").is_finished())

    def grandchild_alive():
        out = subprocess.run(["pgrep", "-f", marker], capture_output=True, text=True)
        return out.returncode == 0

    assert wait_for(lambda: not grandchild_alive(), timeout=10)


def test_native_exec_failure_carries_errno():
    """Exec failures surface synchronously with the child-side errno."""
    from tf_operator_tpu.runtime.native import NativeSupervisor

    sup = NativeSupervisor()
    with pytest.raises(OSError) as exc_info:
        sup.spawn(["/nonexistent/binary"], {"PATH": "/usr/bin"})
    assert exc_info.value.errno == 2  # ENOENT


def test_native_registry_does_not_leak():
    """Consumed children are forgotten (pids recycle; stale done-entries
    would lie about future children)."""
    from tf_operator_tpu.runtime.native import NativeSupervisor

    sup = NativeSupervisor()
    before = sup.tracked_count()
    children = [sup.spawn([sys.executable, "-c", "pass"], dict(os.environ)) for _ in range(5)]
    for c in children:
        assert c.wait() == 0
    assert sup.tracked_count() == before


# ---------------------------------------------------------------------------
# OOM oracle (r8): SIGKILL exits promote to oom_killed only when the
# supervising cgroup's oom_kill counter advanced across the child's life
# ---------------------------------------------------------------------------


def test_sigkill_with_oom_counter_delta_reports_oom_killed():
    import itertools

    store = Store()
    ctl = LocalProcessControl(
        store,
        command_builder=script_builder("import os, signal; os.kill(os.getpid(), signal.SIGKILL)"),
    )
    # Oracle stub: the cgroup counter ticks once between spawn and exit.
    ctl._oom_kills_reader = itertools.count().__next__
    ctl.create_process(proc("oomer"))
    assert wait_for(
        lambda: store.get("Process", "default", "oomer").status.phase
        is ProcessPhase.FAILED
    )
    st = store.get("Process", "default", "oomer").status
    assert st.exit_code in (137, -9)
    assert st.oom_killed is True


def test_sigkill_without_oracle_stays_plain_retryable():
    store = Store()
    ctl = LocalProcessControl(
        store,
        command_builder=script_builder("import os, signal; os.kill(os.getpid(), signal.SIGKILL)"),
    )
    ctl._oom_kills_reader = lambda: None  # no cgroup oracle available
    ctl.create_process(proc("killed"))
    assert wait_for(
        lambda: store.get("Process", "default", "killed").status.phase
        is ProcessPhase.FAILED
    )
    st = store.get("Process", "default", "killed").status
    assert st.oom_killed is False  # conservative: never a guessed OOM


def test_clean_exit_ignores_oom_counter_noise():
    # A sibling's OOM (counter delta) must not taint a clean exit.
    import itertools

    store = Store()
    ctl = LocalProcessControl(
        store, command_builder=script_builder("import sys; sys.exit(0)")
    )
    ctl._oom_kills_reader = itertools.count().__next__
    ctl.create_process(proc("clean"))
    assert wait_for(
        lambda: store.get("Process", "default", "clean").status.phase
        is ProcessPhase.SUCCEEDED
    )
    assert store.get("Process", "default", "clean").status.oom_killed is False


def test_a_hung_child_costs_its_case_its_own_limit(case_limit):
    """The tests' own bound (``conftest.case_limit``): a case that waits on a
    child that never ends is failed at ITS limit — 3 s for this one — with
    every thread's stack in the message, while the run's clock is minutes
    away. The alarm interrupts the un-timed ``wait``."""
    assert case_limit == 3
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
    t0 = time.monotonic()
    try:
        with pytest.raises(pytest.fail.Exception, match="ran into its limit of 3 s") as hit:
            child.wait()
        assert time.monotonic() - t0 < 3.5
        # where it stood: this test's frame, waiting on the child
        assert "test_a_hung_child_costs_its_case_its_own_limit" in str(hit.value)
        assert "subprocess.py" in str(hit.value)
    finally:
        child.kill()
        child.wait()
