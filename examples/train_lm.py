"""End-to-end LM training on one TPU chip, through the runtime.

Run: python examples/train_lm.py        (fails without a chip)
Wires together: TpuTrainer (a worker that leases the chip — this driver
never touches jax), models/transformer presets, and the compiled pjit
train step (forward+backward+optimizer in ONE XLA program).
"""
from ray_tpu.train import ScalingConfig, TpuTrainer


def train_loop(config):
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.train import session
    from ray_tpu.train.train_step import CompiledTrainStep, make_optimizer

    cfg = dataclasses.replace(tfm.PRESETS["gpt2-small"], remat=True,
                              remat_policy="dots", xent_chunk=4096)
    batch, seq = 16, 1024
    mesh = make_mesh(MeshSpec(), devices=jax.devices())
    step = CompiledTrainStep(cfg, mesh,
                             optimizer=make_optimizer(total_steps=100))
    state = step.init_state(seed=0)
    rng = np.random.RandomState(0)
    for i in range(config["steps"]):
        tokens = rng.randint(0, cfg.vocab_size,
                             size=(batch, seq + 1)).astype(np.int32)
        state, metrics = step(state, step.shard_batch(tokens))
        session.report({"step": i, "loss": float(metrics["loss"]),
                        "device": jax.devices()[0].device_kind})


def main():
    result = TpuTrainer(
        train_loop, train_loop_config={"steps": 5},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
    ).fit()
    if result.error is not None:
        raise SystemExit(f"training failed: {result.error}")
    for m in result.metrics_dataframe:
        print(f"step {m['step']}: loss={m['loss']:.4f} ({m['device']})")


if __name__ == "__main__":
    main()
