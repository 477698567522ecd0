"""Train-step construction + end-to-end training driver.

``make_train_step`` builds the jit'd (state, batch) -> (state, metrics)
function with full sharding annotations; it is consumed by the dry-run
(lowering only), the examples, and the fault-tolerant Trainer runtime.

Run directly for a real (CPU-scale) training session:
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --smoke --steps 100 --batch 8 --seq 64
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..configs import ModelConfig, get_config, smoke_config
from ..models import DistContext, MeshRules, build_model, choose_ep_axes, \
    use_mesh_rules
from ..optim import AdamWConfig, adamw_update, cosine_schedule, \
    init_opt_state
from .compile_cache import enable_compile_cache
from .mesh import dp_axes, slow_axis
from .shardings import batch_shardings, state_shardings

__all__ = ["make_dist_context", "make_rules", "make_train_step",
           "make_train_state_shapes", "TrainOptions"]


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    adamw: AdamWConfig = AdamWConfig()
    # beyond-paper distributed-optimization knobs
    grad_compression: bool = False   # int8 EF all-gather over the pod axis
    microbatches: int = 1            # grad accumulation: divides live
                                     # activation memory, same math


def make_dist_context(cfg: ModelConfig, mesh: Mesh,
                      a2a_impl: Optional[str] = None,
                      plan=None) -> DistContext:
    """Build the DistContext; ``a2a_impl`` overrides the config's choice.

    ``plan`` (a core.plan.Plan or simulator.ExecutableSchedule) backs
    ``a2a_impl="plan"`` and is preferred by ``"auto"``; it rides along in
    the context so model code never threads it explicitly.

    The implementation name is validated against the one comm-layer
    registry (comm.all_to_all) so every entry point -- training, serving,
    dry-run sweeps -- fails fast on a typo instead of inside shard_map.
    """
    from ..comm.all_to_all import all_to_all_by_name

    impl = a2a_impl or cfg.a2a_impl
    if impl != "auto":
        all_to_all_by_name(impl)  # raises ValueError on unknown impls
    if impl == "plan" and plan is None:
        raise ValueError('a2a_impl="plan" needs a synthesized plan; pass '
                         "plan= (e.g. from serving.client.PlanClient)")
    return DistContext(
        mesh=mesh,
        dp_axes=dp_axes(mesh),
        slow_axis=slow_axis(mesh),
        ep_axes=choose_ep_axes(cfg, mesh),
        a2a_impl=impl,
        plan=plan,
    )


def make_rules(cfg: ModelConfig, mesh: Mesh) -> MeshRules:
    act_seq = "model" if cfg.seq_shard_activations else None
    if cfg.pure_dp:
        # no TP: weights replicated (or FSDP-stored); batch over every axis
        # unless FSDP needs the model axis for parameter storage
        batch = dp_axes(mesh) if cfg.fsdp else tuple(mesh.axis_names)
        return MeshRules(mesh=mesh, batch=batch,
                         act_seq=None, heads=None, kv_heads=None,
                         head_dim=None, ff=None, vocab=None,
                         expert_ff=None, model_dim=None, kv_feature=None)
    return MeshRules(mesh=mesh, batch=dp_axes(mesh), act_seq=act_seq)


def make_train_state_shapes(cfg: ModelConfig, mesh: Optional[Mesh]):
    """abstract state tree (no allocation) + shardings."""
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(init_opt_state, params_shape)
    state_shape = {"params": params_shape, "opt": opt_shape,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
    if mesh is None:
        return state_shape, None
    return state_shape, state_shardings(cfg, mesh, state_shape)


def make_train_step(cfg: ModelConfig, mesh: Optional[Mesh],
                    options: TrainOptions = TrainOptions()):
    """Returns (train_step, state_shape, state_shardings, batch_fn).

    train_step is already jit'd with in/out shardings when a mesh is given.
    """
    model = build_model(cfg)
    dist = make_dist_context(cfg, mesh) if mesh is not None else None
    rules = make_rules(cfg, mesh) if mesh is not None else None
    lr_fn = cosine_schedule(options.peak_lr, options.warmup_steps,
                            options.total_steps)

    def train_step(state, batch):
        with use_mesh_rules(rules):
            def loss_fn(params, mb):
                loss, metrics = model.loss(params, mb, dist)
                return loss, metrics

            n_mb = options.microbatches
            if n_mb > 1:
                # grad accumulation over sequential microbatches: live
                # activations shrink n_mb-fold; grads accumulate in f32
                mbs = jax.tree.map(
                    lambda a: a.reshape((n_mb, a.shape[0] // n_mb)
                                        + a.shape[1:])
                    if a.ndim else a, batch)

                def mb_body(acc, mb):
                    (l, m), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(state["params"], mb)
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32) / n_mb,
                        acc, g)
                    return acc, (l, m)

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])
                grads, (losses, metricses) = jax.lax.scan(
                    mb_body, zero, mbs)
                metrics = jax.tree.map(lambda x: x.mean(0), metricses)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state["params"], batch)
            if options.grad_compression and dist is not None \
                    and dist.slow_axis is not None:
                grads = _compress_pod_grads(grads, dist)
            lr = lr_fn(state["step"])
            new_params, new_opt, gnorm = adamw_update(
                grads, state["opt"], state["params"], lr, options.adamw)
            new_state = {"params": new_params, "opt": new_opt,
                         "step": state["step"] + 1}
            metrics = dict(metrics)
            metrics["grad_norm"] = gnorm
            metrics["lr"] = lr
            return new_state, metrics

    state_shape, state_sh = make_train_state_shapes(cfg, mesh)
    if mesh is None:
        return jax.jit(train_step), state_shape, None, None

    def batch_sharding_fn(batch_shape):
        return batch_shardings(mesh, batch_shape,
                               pure_dp=cfg.pure_dp and not cfg.fsdp)

    metrics_shape = {"loss": 0., "nll": 0., "aux": 0., "ppl_proxy": 0.,
                     "grad_norm": 0., "lr": 0.}
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = jax.tree.map(lambda _: NamedSharding(mesh, P()), metrics_shape)
    step = jax.jit(
        train_step,
        donate_argnums=(0,),
        out_shardings=(state_sh, repl),
    )
    return step, state_shape, state_sh, batch_sharding_fn


def _compress_pod_grads(grads, dist: DistContext):
    """int8 error-feedback grad sync over the DCN axis (stateless form:
    the quantization residual is re-derived per step inside the island;
    see repro.comm.collectives for the stateful carry variant used in the
    examples)."""
    from ..comm.collectives import ef_compressed_psum

    def island(g):
        total, _err = ef_compressed_psum(g, dist.slow_axis)
        return total / jax.lax.psum(1, dist.slow_axis)

    def one(g):
        # check_vma off: the dequantized sum over the gathered pod axis is
        # pod-invariant by construction, which the checker cannot prove.
        return jax.shard_map(
            island, mesh=dist.mesh, in_specs=P(), out_specs=P(),
            axis_names={dist.slow_axis}, check_vma=False)(g)

    return jax.tree.map(one, grads)


# -- CLI driver (real run, CPU-scale) ----------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opts = TrainOptions(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                        total_steps=args.steps)
    step_fn, state_shape, _, _ = make_train_step(cfg, mesh=None,
                                                 options=opts)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": init_opt_state(params),
             "step": jnp.zeros((), jnp.int32)}

    from ..data import DataConfig, SyntheticLM
    from ..runtime import Trainer, TrainerConfig

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch), cfg)

    def batches(step: int) -> Dict[str, Any]:
        return {k: jnp.asarray(v) for k, v in data.batch(step).items()}

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=max(args.steps // 4, 1)),
        train_step=step_fn,
        init_state=lambda: state,
        batches=batches,
    )
    result = trainer.run()
    print(f"finished at step {result['stopped_at']} "
          f"loss={result['metrics'].get('loss'):.4f} "
          f"preempted={result['preempted']}")


if __name__ == "__main__":
    main()
