"""Train library tests: JaxTrainer end-to-end on a local cluster —
worker group, sessions/report, checkpointing, failure restart
(reference: python/ray/train/tests/test_data_parallel_trainer.py shape)."""

import os
import time

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (Checkpoint, CheckpointConfig, FailureConfig,
                           JaxTrainer, RunConfig, ScalingConfig)


RAY_START = dict(num_cpus=6, object_store_memory=128 * 1024 * 1024)


def test_trainer_basic(ray_start, tmp_path):
    def loop(config):
        ctx = train.get_context()
        for step in range(3):
            train.report({"step": step, "rank": ctx.get_world_rank(),
                          "world": ctx.get_world_size()})

    trainer = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=str(tmp_path), name="basic"))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["world"] == 2
    assert len(result.metrics_history) == 3


def test_trainer_checkpointing(ray_start, tmp_path):
    def loop(config):
        ctx = train.get_context()
        for step in range(4):
            ckpt = None
            if ctx.get_world_rank() == 0:
                ckpt = Checkpoint.from_dict({"model": step * 10})
            train.report({"step": step, "loss": 10.0 - step},
                         checkpoint=ckpt)

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            storage_path=str(tmp_path), name="ckpt",
            checkpoint_config=CheckpointConfig(
                num_to_keep=2, checkpoint_score_attribute="loss",
                checkpoint_score_order="min")))
    result = trainer.fit()
    assert result.error is None
    assert result.checkpoint is not None
    data = result.checkpoint.to_dict()
    assert data["model"] == 30   # best (lowest loss) = last step
    ckpt_dir = os.path.join(str(tmp_path), "ckpt", "checkpoints")
    assert len(os.listdir(ckpt_dir)) == 2   # top-k retention


def test_trainer_failure_restart(ray_start, tmp_path):
    marker = str(tmp_path / "crashed_once")

    def loop(config):
        ctx = train.get_context()
        start = 0
        if train.session.get_checkpoint() is not None:
            start = train.session.get_checkpoint().to_dict()["step"] + 1
        for step in range(start, 4):
            ckpt = None
            if ctx.get_world_rank() == 0:
                ckpt = Checkpoint.from_dict({"step": step})
            train.report({"step": step}, checkpoint=ckpt)
            if step == 1 and ctx.get_world_rank() == 0 and \
                    not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                time.sleep(0.3)   # let the report drain
                os._exit(1)       # simulate worker crash

    trainer = JaxTrainer(
        loop, train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            storage_path=str(tmp_path), name="restart",
            failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 3
    assert os.path.exists(marker)
