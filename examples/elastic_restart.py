"""Elastic restart: train on one mesh, checkpoint, resume on a DIFFERENT
mesh (the 1000+-node failure/resize story at demo scale).

    PYTHONPATH=src python examples/elastic_restart.py
    PYTHONPATH=src python examples/elastic_restart.py --fault-only

Phase 1 trains on a (2,2,2) pod x data x model mesh and checkpoints.
Phase 2 restores the same (host-gathered, mesh-independent) checkpoint onto
a (4,2) data x model single-pod mesh -- as after losing a pod -- and
continues; the loss trajectory continues from where phase 1 stopped.
Also demonstrates int8 error-feedback gradient compression over the pod
axis (--compress).

Phase 3 (``--fault-only`` runs it alone, without jax) is the scheduler
side of the same elasticity story: a plan-serving daemon survives an
injected mid-job NIC failure.  A FabricMonitor feeds the fail/recover
events into the PlanServer, which re-repairs its warm plan families
against the degraded fabric instead of evicting them; every request in
the event window is answered (zero rejections), with completion bounded
by a small factor of what cold synthesis on the degraded fabric would
give.
"""

import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the fake devices are CPUs
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse


def run_fault_phase():
    """Phase 3: the plan server rides out a NIC failure (no jax needed)."""
    import numpy as np

    from repro.core import ClusterSpec, Topology, execute_plan, get_scheduler
    from repro.core.traffic import Workload, moe_workload
    from repro.serving import FabricMonitor, PlanClient, PlanServer

    spec = ClusterSpec(n_servers=4, m_gpus=2)
    topo = Topology.homogeneous(4, 2)
    mon = FabricMonitor(topo)

    def drifting(step, scale=0.02):
        base = moe_workload(spec, 512, 64, top_k=2, seed=0)
        rng = np.random.default_rng(step)
        m = base.matrix * (1.0 + scale * rng.standard_normal(
            base.matrix.shape))
        m = np.maximum(m, 0.0)
        np.fill_diagonal(m, 0.0)
        return Workload(spec, m, topo)  # clients keep the ORIGINAL fabric

    print("phase 3: plan server vs mid-job NIC failure")
    worst_ratio = 0.0
    with PlanServer(workers=2) as srv:
        srv.attach_monitor(mon)
        cli = PlanClient(srv, algorithm="flash_ca", timeout=30.0)
        for step in range(4):                      # healthy warmup
            cli.get_plan(drifting(step))
        srv.drain()

        ev = mon.inject("fail", server=0, nic=0)   # the fault
        degraded = mon.current()
        print(f"  injected: {ev.describe()}")
        cold = get_scheduler("flash_ca")
        for step in range(4, 8):                   # event window
            w = drifting(step)
            answer = cli.get_plan(w)               # stale topo: re-homed
            w_deg = Workload(spec, w.matrix, degraded)
            t_served = execute_plan(answer.plan, w_deg).completion_time
            t_cold = execute_plan(cold.synthesize(w_deg),
                                  w_deg).completion_time
            worst_ratio = max(worst_ratio, t_served / t_cold)
        srv.drain()

        mon.inject("recover", server=0, nic=0)     # the heal
        assert mon.current() == topo, "recovery must restore the fabric"
        for step in range(8, 10):
            cli.get_plan(drifting(step))
        srv.drain()

        c = srv.telemetry_snapshot()["counters"]
        print(f"  event-window worst served/cold ratio: {worst_ratio:.3f}")
        print(f"  counters: rerepaired={c.get('rerepaired', 0)} "
              f"stale_topology={c.get('stale_topology', 0)} "
              f"rejected={c.get('rejected', 0)} shed={c.get('shed', 0)} "
              f"errors={c.get('errors', 0)}")
        assert c.get("rejected", 0) == 0 and c.get("shed", 0) == 0
        assert c.get("errors", 0) == 0
        assert cli.counters["inline"] == 0, "daemon must answer everything"
        assert worst_ratio <= 2.0, "slowdown must stay bounded"
    print("fault survival OK: degraded, never stalled")
    return worst_ratio


def run_phase(cfg, mesh, steps, ckpt_dir, data, grad_compression=False):
    # jax and the training stack are imported lazily so --fault-only
    # exercises the scheduler path on boxes without an accelerator stack.
    import jax
    import jax.numpy as jnp

    from repro.launch.train import TrainOptions, make_train_step
    from repro.models import build_model
    from repro.optim import init_opt_state
    from repro.runtime import Trainer, TrainerConfig

    opts = TrainOptions(peak_lr=3e-3, warmup_steps=4, total_steps=steps,
                        grad_compression=grad_compression)
    step_fn, _, state_sh, batch_sh_fn = make_train_step(cfg, mesh, opts)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    state = jax.device_put(
        {"params": params, "opt": init_opt_state(params),
         "step": jnp.zeros((), jnp.int32)}, state_sh)

    def batches(step):
        host = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        sh = batch_sh_fn(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), host))
        return jax.device_put(host, sh)

    trainer = Trainer(
        TrainerConfig(total_steps=steps, ckpt_dir=ckpt_dir, ckpt_every=10,
                      log_every=5),
        step_fn, lambda: state, batches, state_shardings=state_sh)
    return trainer.run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default="/tmp/repro_elastic")
    ap.add_argument("--compress", action="store_true",
                    help="int8 EF gradient sync over the pod axis (phase 1)")
    ap.add_argument("--fault-only", action="store_true",
                    help="run only phase 3 (plan-server fault survival; "
                         "no jax required)")
    args = ap.parse_args()
    if args.fault_only:
        run_fault_phase()
        return

    import shutil
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    from repro.checkpoint import latest_step
    from repro.configs import smoke_config
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh

    cfg = smoke_config("qwen3-0.6b")
    data = SyntheticLM(
        DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8), cfg)

    mesh1 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    print("phase 1: multi-pod mesh", mesh1.devices.shape,
          "compress:", args.compress)
    r1 = run_phase(cfg, mesh1, 20, args.ckpt_dir, data,
                   grad_compression=args.compress)
    print(f"  stopped at {r1['stopped_at']}, "
          f"loss={r1['metrics']['loss']:.4f}")
    assert latest_step(args.ckpt_dir) == 20

    mesh2 = make_mesh((4, 2), ("data", "model"))
    print("phase 2: resumed on single-pod mesh", mesh2.devices.shape,
          "(elastic reshard)")
    r2 = run_phase(cfg, mesh2, 40, args.ckpt_dir, data)
    print(f"  stopped at {r2['stopped_at']}, "
          f"loss={r2['metrics']['loss']:.4f}")
    assert r2["stopped_at"] == 40
    assert r2["metrics"]["loss"] < r1["metrics"]["loss"] * 1.2
    print("elastic restart OK: training continued across mesh resize")

    run_fault_phase()


if __name__ == "__main__":
    main()
