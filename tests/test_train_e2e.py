"""The minimum end-to-end slice (SURVEY.md §7.2 milestone): driver →
JaxTrainer → worker actor → sharded train step on a device mesh, with
Data ingest and checkpointing — loss must drop."""

import numpy as np

import ray_tpu
from ray_tpu import train
from ray_tpu.train import Checkpoint, JaxTrainer, RunConfig, ScalingConfig


RAY_START = dict(num_cpus=4, object_store_memory=256 * 1024 * 1024)


def _loop(config):
    import jax
    import optax
    from ray_tpu import train
    from ray_tpu.models import MODEL_REGISTRY, TransformerLM
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_fns

    jax.config.update("jax_platforms", "cpu")
    cfg = MODEL_REGISTRY["llama-debug"]
    model = TransformerLM(cfg)
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, seq=1, tensor=1),
                     devices=jax.devices()[:1])
    B, L = 4, 32
    init_fn, step_fn, _ = make_train_fns(model, optax.adamw(3e-3), mesh,
                                         batch_shape=(B, L + 1))
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L + 1), 0,
                                cfg.vocab_size)
    first = last = None
    for step in range(6):
        state, metrics = step_fn(state, tokens)
        loss = float(metrics["loss"])
        first = first if first is not None else loss
        last = loss
        ckpt = None
        if train.get_context().get_world_rank() == 0 and step == 5:
            ckpt = Checkpoint.from_dict({"final_loss": loss})
        train.report({"loss": loss, "step": step}, checkpoint=ckpt)
    assert last < first


def test_jax_trainer_transformer(ray_start, tmp_path):
    trainer = JaxTrainer(
        _loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path), name="e2e"))
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 5
    losses = [m["loss"] for m in result.metrics_history]
    assert losses[-1] < losses[0]
    assert result.checkpoint is not None
    assert "final_loss" in result.checkpoint.to_dict()
