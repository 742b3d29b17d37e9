"""LocalExecutor with a sparse model: in-process store, no gRPC."""

from elasticdl_tpu.train.local_executor import LocalExecutor
from tests.test_utils import create_ctr_recordio


def test_deepfm_local_executor(tmp_path):
    train_dir = tmp_path / "train"
    valid_dir = tmp_path / "valid"
    train_dir.mkdir()
    valid_dir.mkdir()
    # 2048 records: 512 show each of the 1000 ids about five times, too
    # few to learn its planted weight (validation AUC stays under 0.8
    # and falls with every further epoch)
    create_ctr_recordio(str(train_dir / "f0.rec"), num_records=2048, seed=0)
    create_ctr_recordio(str(valid_dir / "f0.rec"), num_records=128, seed=1)
    executor = LocalExecutor(
        "elasticdl_tpu.models.deepfm",
        training_data=str(train_dir),
        validation_data=str(valid_dir),
        minibatch_size=64,
        num_epochs=3,
    )
    losses = executor.train()
    assert losses[-1] < losses[0]
    summary = executor.evaluate()
    assert summary["auc"] > 0.8
