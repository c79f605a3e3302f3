"""Local end-to-end, the Evaluator role: the evaluator section of
tests/test_e2e_local.py as a file of its own, so that ``--dist loadfile``
gives it a worker (the file was one worker's 306 s in a run whose other
files had come down to 240 s and under; PR 32). Cases moved verbatim; the
rig fixtures and the data-plane environment are that file's."""

import pytest

from tf_operator_tpu.api.types import (
    ConditionType,
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    TPUJob,
    TPUJobSpec,
)
from tf_operator_tpu.controller.status import has_condition
from conftest import wait_for
from test_e2e_local import DATAPLANE_ENV, job_status, rig, rig_api  # noqa: F401 (fixtures)

pytestmark = pytest.mark.e2e


def test_evaluator_scores_checkpoints_alongside_training(rig, tmp_path):
    """The Evaluator role doing real work (the reference defines the role
    but no behavior): one job runs a 2-process LM training gang that
    checkpoints, plus an Evaluator replica — outside the gang — polling
    the same checkpoint_dir and scoring each checkpoint. Job success is
    chief-driven (reference semantics: worker-0), so the evaluator's work
    is asserted through its report artifact, which also catches
    reader-staleness bugs — the evaluator here starts BEFORE any
    checkpoint exists."""
    store = rig
    ckpt_dir = str(tmp_path / "ckpt")
    report = str(tmp_path / "eval_report.json")
    job = TPUJob(
        metadata=ObjectMeta(name="train-eval"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=2,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.lm:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                ),
                ReplicaType.EVALUATOR: ReplicaSpec(
                    replicas=1,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.eval:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                ),
            },
        ),
    )
    job.spec.workload = {
        "preset": "tiny",
        "steps": 6,
        "batch_size": 4,
        "seq_len": 32,
        "checkpoint_dir": ckpt_dir,
        "checkpoint_every": 2,
        # evaluator keys (same shared workload dict). train_steps=2 so the
        # evaluator finishes BEFORE the trainers: job success is
        # chief-driven and cleanup kills whatever is still running, so an
        # evaluator that needed the final checkpoint would race it.
        "train_steps": 2,
        "eval_batch_size": 4,
        "eval_seq_len": 32,
        "eval_batches": 1,
        "poll_interval_s": 0.2,
        "max_wait_s": 120,
        "eval_report": report,
    }
    store.create(job)
    ok = wait_for(
        lambda: has_condition(job_status(store, "train-eval"), ConditionType.SUCCEEDED),
        timeout=180,
    )
    st = job_status(store, "train-eval")
    assert ok, f"conditions: {[(c.type.value, c.reason, c.message) for c in st.conditions]}"

    # Whether the evaluator got a score in before success-cleanup killed it
    # is a timing race at this toy scale (compile time >> train time), so
    # the report is not asserted here — evaluator liveness against a live
    # writer is covered deterministically by
    # tests/test_eval_workload.py::test_eval_concurrent_with_live_writer,
    # and the operator-launched scoring path by
    # test_eval_scoring_job_over_existing_checkpoints below.


def test_eval_scoring_job_over_existing_checkpoints(rig, tmp_path):
    """The scoring workload through the full operator path: a one-shot
    eval job (worker-0 is the chief — Evaluator-ONLY jobs are rejected at
    admission since nothing would drive job state) over a pre-existing
    checkpoint directory; Succeeded requires the report artifact, so the
    launched process really scored."""
    import json

    from tests.test_eval_workload import _save_checkpoints

    store = rig
    ckpt_dir = tmp_path / "ckpt"
    _save_checkpoints(ckpt_dir, steps={2})
    report = str(tmp_path / "report.json")
    job = TPUJob(
        metadata=ObjectMeta(name="eval-only"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=1,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.eval:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                ),
            },
        ),
    )
    job.spec.workload = {
        "preset": "tiny",
        "checkpoint_dir": str(ckpt_dir),
        "eval_batch_size": 4,
        "eval_seq_len": 32,
        "eval_batches": 1,
        "poll_interval_s": 0.1,
        "max_wait_s": 60,
        "eval_report": report,
    }
    store.create(job)
    ok = wait_for(
        lambda: has_condition(job_status(store, "eval-only"), ConditionType.SUCCEEDED),
        timeout=180,
    )
    st = job_status(store, "eval-only")
    assert ok, f"conditions: {[(c.type.value, c.reason, c.message) for c in st.conditions]}"
    with open(report) as f:
        assert "2" in json.load(f)


def test_resnet_evaluator_reports_accuracy(rig_api, tmp_path):
    """VERDICT r3 #7b done-bar: a resnet_real_idx-class job with an
    EVALUATOR replica reporting accuracy into eval_metrics. The trainer
    gang checkpoints (params + BN stats); the evaluator — model="resnet",
    outside the gang — restores both subtrees per checkpoint and scores
    test-split accuracy through the same idx reader."""
    import numpy as np

    sklearn_datasets = pytest.importorskip(
        "sklearn.datasets", reason="real-digits fixture needs scikit-learn"
    )
    from tf_operator_tpu.train.data import write_idx

    digits = sklearn_datasets.load_digits()
    order = np.random.default_rng(0).permutation(len(digits.target))
    images = (digits.images * (255.0 / 16.0)).astype(np.uint8)[order]
    labels = digits.target.astype(np.uint8)[order]
    data_dir = tmp_path / "digits"
    data_dir.mkdir()
    write_idx(str(data_dir / "train-images-idx3-ubyte.gz"), images[:1500])
    write_idx(str(data_dir / "train-labels-idx1-ubyte.gz"), labels[:1500])
    write_idx(str(data_dir / "t10k-images-idx3-ubyte"), images[1500:])
    write_idx(str(data_dir / "t10k-labels-idx1-ubyte"), labels[1500:])

    store = rig_api
    ckpt_dir = str(tmp_path / "ckpt")
    report = str(tmp_path / "eval_report.json")
    job = TPUJob(
        metadata=ObjectMeta(name="resnet-eval"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=1,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.resnet:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                ),
                ReplicaType.EVALUATOR: ReplicaSpec(
                    replicas=1,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.eval:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                ),
            },
        ),
    )
    job.spec.workload = {
        "data": "idx",
        "data_dir": str(data_dir),
        "variant": "tiny",
        "num_classes": 10,
        "image_size": 32,
        "epochs": 4,
        "batch_size": 256,
        "lr": 0.02,
        "augment": True,
        "flip": False,
        "checkpoint_dir": ckpt_dir,
        "checkpoint_every": 2,
        # evaluator keys: model selects the resnet scorer; train_steps=2
        # so the evaluator finishes BEFORE the trainer (job success is
        # chief-driven; cleanup kills stragglers — same protocol as the
        # LM evaluator e2e above)
        "model": "resnet",
        "train_steps": 2,
        "eval_batch_size": 64,
        "poll_interval_s": 0.2,
        "max_wait_s": 180,
        "eval_report": report,
    }
    store.create(job)
    ok = wait_for(
        lambda: has_condition(job_status(store, "resnet-eval"), ConditionType.SUCCEEDED),
        timeout=180,
    )
    st = job_status(store, "resnet-eval")
    assert ok, f"conditions: {[(c.type.value, c.reason, c.message) for c in st.conditions]}"
    # the trainer's own end-of-run gate also reports accuracy; the
    # EVALUATOR's per-checkpoint scoring is asserted via its report
    # artifact — written before job cleanup because train_steps=2 ends
    # the evaluator while the trainer still has epochs to run, so its
    # absence means the scoring path is broken, not a timing race
    import json as _json

    scored = _json.loads(open(report).read())
    assert scored and all(0.0 <= v <= 1.0 for v in scored.values()), scored
    assert "metrics" in st.eval_metrics, st.eval_metrics
