"""Local end-to-end, real data to a stated accuracy: the two
``..._reaches_accuracy`` gangs of tests/test_e2e_local.py as a file of their
own, so that ``--dist loadfile`` gives them a worker (with them the file was
285 - 315 s of one worker, at the 300 s no file may take; the ResNet gang
alone trains for 73 - 97 s; PR 32). Cases moved verbatim; the rig fixtures
and the data-plane environment are that file's."""

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
from test_e2e_local import DATAPLANE_ENV, job_status, rig_api  # noqa: F401 (fixture)

pytestmark = pytest.mark.e2e


def test_real_data_mnist_gang_reaches_accuracy(rig_api, tmp_path):
    """VERDICT #2 done-bar: REAL data end to end. Real scanned-digit
    images (sklearn's UCI digits — this environment has no egress to
    download MNIST itself) are written in the exact MNIST idx wire format;
    a 2-process gang reads disjoint shards through the DeviceLoader,
    trains SPMD, and must reach >95% test accuracy — the same proof
    dist_mnist.py gives the reference (test/e2e/dist-mnist). The accuracy
    flows back through the API into TPUJobStatus.eval_metrics."""
    import numpy as np

    sklearn_datasets = pytest.importorskip(
        "sklearn.datasets", reason="real-digits fixture needs scikit-learn"
    )
    load_digits = sklearn_datasets.load_digits

    from tf_operator_tpu.train.data import write_idx

    digits = load_digits()
    order = np.random.default_rng(0).permutation(len(digits.target))
    images = (digits.images * (255.0 / 16.0)).astype(np.uint8)[order]  # [1797,8,8]
    labels = digits.target.astype(np.uint8)[order]
    n_train = 1500
    data_dir = tmp_path / "digits"
    data_dir.mkdir()
    write_idx(str(data_dir / "train-images-idx3-ubyte.gz"), images[:n_train])
    write_idx(str(data_dir / "train-labels-idx1-ubyte.gz"), labels[:n_train])
    write_idx(str(data_dir / "t10k-images-idx3-ubyte"), images[n_train:])
    write_idx(str(data_dir / "t10k-labels-idx1-ubyte"), labels[n_train:])

    store = rig_api
    job = TPUJob(
        metadata=ObjectMeta(name="mnist-real"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=2,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.mnist:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                )
            },
        ),
    )
    job.spec.workload = {
        "data_dir": str(data_dir),
        "epochs": 30,
        "batch_size": 128,
        "hidden": 128,
        "lr": 0.1,
        "target_accuracy": 0.95,  # the workload itself fails below this
    }
    store.create(job)
    ok = wait_for(
        lambda: has_condition(job_status(store, "mnist-real"), ConditionType.SUCCEEDED),
        timeout=120,
    )
    st = job_status(store, "mnist-real")
    assert ok, f"conditions: {[(c.type.value, c.reason, c.message) for c in st.conditions]}"
    # accuracy surfaced through the API into eval_metrics
    assert st.eval_metrics.get("metrics", {}).get("accuracy", 0) > 0.95, st.eval_metrics


def test_real_image_resnet_gang_reaches_accuracy(rig_api, tmp_path):
    """VERDICT r2 #7 done-bar: the ResNet path trains REAL images end to
    end — idx files -> 3-channel/32px prepare -> random-crop augmentation
    -> DeviceLoader shards across a 2-process gang -> sharded Trainer ->
    eval-mode (running BN stats) test accuracy, gated and reported into
    eval_metrics. The ResNet counterpart of the dist_mnist proof
    (test-scale `tiny` variant: same stem/BN/residual machinery at CPU-CI
    cost; calibrated single-process accuracy 0.99)."""
    import numpy as np

    sklearn_datasets = pytest.importorskip(
        "sklearn.datasets", reason="real-digits fixture needs scikit-learn"
    )
    from tf_operator_tpu.train.data import write_idx

    digits = sklearn_datasets.load_digits()
    order = np.random.default_rng(0).permutation(len(digits.target))
    images = (digits.images * (255.0 / 16.0)).astype(np.uint8)[order]
    labels = digits.target.astype(np.uint8)[order]
    n_train = 1500
    data_dir = tmp_path / "digits"
    data_dir.mkdir()
    write_idx(str(data_dir / "train-images-idx3-ubyte.gz"), images[:n_train])
    write_idx(str(data_dir / "train-labels-idx1-ubyte.gz"), labels[:n_train])
    write_idx(str(data_dir / "t10k-images-idx3-ubyte"), images[n_train:])
    write_idx(str(data_dir / "t10k-labels-idx1-ubyte"), labels[n_train:])

    store = rig_api
    job = TPUJob(
        metadata=ObjectMeta(name="resnet-real"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.WORKER: ReplicaSpec(
                    replicas=2,
                    template=ProcessTemplate(
                        entrypoint="tf_operator_tpu.workloads.resnet:main",
                        env=dict(DATAPLANE_ENV),
                    ),
                )
            },
        ),
    )
    job.spec.workload = {
        "data": "idx",
        "data_dir": str(data_dir),
        "variant": "tiny",
        "num_classes": 10,
        "image_size": 32,
        "epochs": 20,
        "batch_size": 256,
        "lr": 0.02,
        "augment": True,
        "flip": False,  # digits are orientation-sensitive
        "target_accuracy": 0.95,  # the workload itself fails below this
    }
    store.create(job)
    ok = wait_for(
        lambda: has_condition(job_status(store, "resnet-real"), ConditionType.SUCCEEDED),
        timeout=360,
    )
    st = job_status(store, "resnet-real")
    assert ok, f"conditions: {[(c.type.value, c.reason, c.message) for c in st.conditions]}"
    assert st.eval_metrics.get("metrics", {}).get("accuracy", 0) > 0.95, st.eval_metrics
