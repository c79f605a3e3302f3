"""Dashboard REST API + Python client tests (reference parity:
dashboard/backend handler routes + py/tf_job_client.py), driven through a
live daemon stack: store + controller + real processes + HTTP server."""

import json
import sys
import urllib.request

import pytest

from conftest import wait_for
from tf_operator_tpu.api.types import (
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    TPUJob,
    TPUJobSpec,
)
from tf_operator_tpu.controller import TPUJobController
from tf_operator_tpu.dashboard import DashboardServer, TPUJobClient
from tf_operator_tpu.dashboard.client import TPUJobApiError
from tf_operator_tpu.runtime import LocalProcessControl, Store


@pytest.fixture
def stack(tmp_path):
    store = Store()
    pc = LocalProcessControl(
        store,
        command_builder=lambda p: [
            sys.executable, "-c", "import time; print('hello from', 'worker'); time.sleep(1)",
        ],
        log_dir=str(tmp_path / "logs"),
    )
    ctl = TPUJobController(store, pc, resync_period=0.2)
    ctl.run(workers=1)
    server = DashboardServer(store, port=0, metrics=ctl.metrics)  # ephemeral port
    server.start()
    client = TPUJobClient(server.url)
    yield store, client, server
    server.stop()
    ctl.stop()
    pc.shutdown()


def make_job(name="webjob", workers=1):
    return TPUJob(
        metadata=ObjectMeta(name=name),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=workers, template=ProcessTemplate(entrypoint="x.y:z")
                )
            }
        ),
    )


def test_create_list_get_delete_roundtrip(stack):
    store, client, _ = stack
    created = client.create(make_job())
    assert created.metadata.uid

    names = [j.metadata.name for j in client.list()]
    assert "webjob" in names

    detail = client.get("default", "webjob")
    assert detail["job"]["metadata"]["name"] == "webjob"
    # controller created the worker process
    assert wait_for(lambda: len(client.get("default", "webjob")["processes"]) == 1)

    client.delete("default", "webjob")
    client.wait_for_delete("default", "webjob", timeout=10)


def test_wait_for_job_reaches_done(stack):
    store, client, _ = stack
    client.create(make_job("quick"))
    job = client.wait_for_job("default", "quick", timeout=60)
    assert job.status.phase().value == "Done"


def test_invalid_job_rejected_400(stack):
    _, client, _ = stack
    bad = make_job("bad")
    bad.spec.replica_specs[ReplicaType.WORKER].template.entrypoint = "nocolon"
    with pytest.raises(TPUJobApiError) as err:
        client.create(bad)
    assert err.value.code == 400


def test_duplicate_job_conflict_409(stack):
    _, client, _ = stack
    client.create(make_job("dup"))
    with pytest.raises(TPUJobApiError) as err:
        client.create(make_job("dup"))
    assert err.value.code == 409


def test_missing_job_404(stack):
    _, client, _ = stack
    with pytest.raises(TPUJobApiError) as err:
        client.get("default", "ghost")
    assert err.value.code == 404


def test_process_logs_served(stack):
    store, client, _ = stack
    client.create(make_job("loggy"))
    assert wait_for(lambda: len(client.get("default", "loggy")["processes"]) == 1)
    assert wait_for(
        lambda: "hello from worker" in client.logs("default", "loggy-worker-0"),
        timeout=20,
    )


def test_logs_of_a_process_not_yet_spawned_are_empty(stack, tmp_path):
    """The log path is annotated when the process is created and the file is
    opened when its child is spawned: a read between the two is an empty log,
    not a 500 (``test_process_logs_served`` polled into that window under
    load and failed on the error, CHANGES.md PRs 23-25 and 32)."""
    from tf_operator_tpu.runtime import Process, ProcessSpec

    store, client, _ = stack
    store.create(Process(
        metadata=ObjectMeta(name="unborn", annotations={
            LocalProcessControl.LOG_ANNOTATION: str(tmp_path / "logs" / "unborn.log")}),
        spec=ProcessSpec(job_name="j", replica_type="Worker")))
    assert client.logs("default", "unborn") == ""


def test_events_surface(stack):
    _, client, _ = stack
    client.create(make_job("eventful"))
    assert wait_for(
        lambda: any(
            e["reason"] == "SuccessfulCreateProcess" for e in client.events("default")
        )
    )


def test_ui_page_served(stack):
    _, client, server = stack
    with urllib.request.urlopen(server.url + "/ui") as resp:
        html = resp.read().decode()
    assert "TPUJob dashboard" in html


def test_healthz(stack):
    _, _, server = stack
    with urllib.request.urlopen(server.url + "/healthz") as resp:
        assert json.loads(resp.read())["ok"] is True


def test_metrics_endpoint_counts_real_work(stack):
    """Prometheus /metrics (SURVEY.md §5: reference has no metrics endpoint
    at all): counters move with actual reconciles/creates, gauges reflect
    the store, and the output parses as text exposition format."""
    store, client, server = stack
    client.create(make_job("metered"))

    def scrape():
        with urllib.request.urlopen(server.url + "/metrics") as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            return resp.read().decode()

    def parse(text):
        vals = {}
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            name, _, val = line.rpartition(" ")
            vals[name] = float(val)
        return vals

    assert wait_for(
        lambda: parse(scrape()).get("tpujob_processes_created_total", 0) >= 1,
        timeout=30,
    )
    vals = parse(scrape())
    assert vals["tpujob_syncs_total"] >= 1
    assert vals["tpujob_sync_duration_seconds_count"] >= 1
    assert "tpujob_workqueue_depth" in vals
    # store gauge: the job we created shows up under some phase
    assert any(k.startswith('tpujob_jobs{phase="') for k in vals)


def test_job_routes_reject_encoded_slash_in_name(stack):
    """Job ns/name pairs circulate as "ns/name" string keys (workqueue,
    expectations), so a %2F-smuggled slash in a job route must 400 —
    while the generic tuple-keyed /api/v1 object routes stay permissive
    (test_names_with_reserved_characters_round_trip)."""
    _, _, server = stack
    for path in ("/api/tpujob/default/a%2Fb", "/api/process/default/a%2Fb/logs"):
        try:
            urllib.request.urlopen(server.url + path)
            raise AssertionError(f"{path} should have been rejected")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400, path
