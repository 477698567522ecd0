"""End-to-end driver: train a MoE LM with FLASH expert dispatch on a
multi-device mesh (8 fake CPU devices stand in for 2 pods x 2 x 2).

    PYTHONPATH=src python examples/moe_train_flash.py --steps 60

Demonstrates the full stack: synthetic data pipeline -> MoE model with the
FLASH hierarchical All-to-All (EP over pod x data) -> AdamW -> fault-
tolerant Trainer (checkpoint/resume). Loss decreases; swap --a2a to compare
schedules (outputs are bit-identical -- only the collective schedule
changes).
"""

import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the fake devices are CPUs
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.configs.registry import MoESpec
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainOptions, make_train_step
from repro.models import build_model
from repro.optim import init_opt_state
from repro.runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--a2a", default="flash",
                    choices=["flash", "direct", "hierarchical"])
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_moe_flash")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        smoke_config("megatron-moe-32e"),
        moe=MoESpec(num_experts=4, top_k=2),  # 4 experts == pod*data shards
        a2a_impl=args.a2a)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"a2a_impl={args.a2a}")

    opts = TrainOptions(peak_lr=3e-3, warmup_steps=5,
                        total_steps=args.steps)
    step_fn, state_shape, state_sh, batch_sh_fn = make_train_step(
        cfg, mesh, opts)

    model = build_model(cfg)

    def init_state(key):
        params = model.init(key)
        return {"params": params, "opt": init_opt_state(params),
                "step": jnp.zeros((), jnp.int32)}

    # Each device materialises only its own shards of the state.
    state = jax.jit(init_state, out_shardings=state_sh)(
        jax.random.PRNGKey(0))

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch), cfg)

    def batches(step):
        host = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        sh = batch_sh_fn(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), host))
        return jax.device_put(host, sh)

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=max(args.steps // 3, 1), log_every=5),
        train_step=step_fn,
        init_state=lambda: state,
        batches=batches,
        state_shardings=state_sh,
    )
    result = trainer.run()
    print(f"done at step {result['stopped_at']}: "
          f"loss={result['metrics']['loss']:.4f} "
          f"(preempted={result['preempted']}, "
          f"stragglers={len(result['stragglers'])})")


if __name__ == "__main__":
    main()
