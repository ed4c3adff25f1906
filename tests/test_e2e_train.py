"""The minimum end-to-end slice (SURVEY.md §7): a transformer trained
through the full stack — TpuTrainer worker actor, jax mesh + compiled
sharded step, Dataset input pipeline, orbax checkpointing, failure
resume.  This is the integration contract benchmarks/run.py's training
cells scale up on TPU.
"""

import json
import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd
from ray_tpu.train import (Checkpoint, FailureConfig, RunConfig,
                           ScalingConfig, TpuTrainer)


def _train_loop(config):
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from ray_tpu.train import session
    from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu import data as rd

    ctx = session.get_context()
    cfg = tfm.PRESETS["tiny"]
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    step = CompiledTrainStep(
        cfg, mesh, optimizer=make_optimizer(learning_rate=1e-2,
                                            warmup_steps=1,
                                            total_steps=100),
        donate_state=False)

    start_step = 0
    ckpt = ctx.get_checkpoint()
    if ckpt is not None:
        state = step.init_state(seed=0)
        state = ckpt.load_pytree(jax.tree.map(lambda x: x, state))
        start_step = int(state.step)
    else:
        state = step.init_state(seed=0)

    # Data pipeline: token blocks through the dataset layer.
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, size=(32, 65)).astype(np.int32)
    ds = rd.from_numpy({"tokens": tokens}, block_rows=8)

    total_steps = config["total_steps"]
    step_i = start_step
    while step_i < total_steps:
        for batch in ds.iter_batches(batch_size=8, drop_last=True):
            if step_i >= total_steps:
                break
            state, metrics = step(state, batch["tokens"])
            step_i = int(state.step)
            ckpt_path = os.path.join(ctx.get_trial_dir(),
                                     f"step_{step_i}")
            saved = Checkpoint.save_pytree(ckpt_path, state,
                                           metadata={"step": step_i})
            session.report({"step": step_i,
                            "loss": float(metrics["loss"]),
                            "resumed_from": start_step},
                           checkpoint=saved)
            if (config.get("crash_at") == step_i
                    and not os.path.exists(config["marker"])):
                open(config["marker"], "w").close()
                os._exit(1)


def test_e2e_train_slice(ray_start, tmp_path):
    trainer = TpuTrainer(
        _train_loop,
        train_loop_config={"total_steps": 6},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="e2e", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 6
    losses = [m["loss"] for m in result.metrics_dataframe]
    assert losses[-1] < losses[0], "loss should drop while overfitting"
    assert result.checkpoint is not None


def test_e2e_train_crash_resume(ray_start, tmp_path):
    marker = str(tmp_path / "crashed")
    trainer = TpuTrainer(
        _train_loop,
        train_loop_config={"total_steps": 5, "crash_at": 3,
                           "marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="e2e_ft", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None
    assert os.path.exists(marker), "crash must have happened"
    assert result.metrics["step"] == 5
    # The second attempt resumed from the step-3 checkpoint, not step 0.
    assert result.metrics["resumed_from"] >= 2
