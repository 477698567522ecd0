"""Paper Fig 14: MoE end-to-end training speedup, FLASH vs RCCL-fanout.

Step-time model: per-iteration All-to-All times come from the alpha-beta
simulator on MoE-gating traffic (2 dispatch + 2 combine per MoE layer, fwd
+ bwd); compute time per layer is modeled at 40% MFU on MI300X bf16
(1.3 PFLOP/s peak).  Varies (a) expert/server count at fixed top-k, (b)
top-k at fixed 4 servers -- the two sweeps of the figure.

Measured-vs-simulated column (the plan-exec loop): a subprocess with fake
CPU devices runs the *device* exchange both ways -- ``impl="plan"``
(comm.plan_exec, the synthesized schedule lowered into shard_map) against
``direct_all_to_all`` -- on the same MoE matrix, checks bit parity, and
emits

  * ``e2e.plan_vs_direct``: measured wall-clock ratio plan/direct (with
    ``parity=ok`` as the correctness gate), and
  * ``e2e.sim_pred_err``: |measured - predicted| / predicted, where the
    prediction is the simulator's flash/fanout completion ratio on the
    identical workload -- the tracked simulator-prediction-error number.

Both are CPU-interpret proxies (XLA:CPU emulates the collectives; there
is no real DCN), so the CI ceilings in check_synth_budget.py are generous
regression backstops, not fidelity claims.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core import ClusterSpec, moe_workload, simulate

from .common import TESTBED, Csv

D_MODEL, D_FF, N_MOE_LAYERS = 4096, 28672, 12
TOKENS_PER_GPU = 8192
BYTES_PER_TOKEN = D_MODEL * 2
MI300X_FLOPS = 1.3e15 * 0.4

# Device-probe scale: small enough for CI smoke (fake CPU devices,
# interpret-free jnp path), big enough that the exchange dominates noise.
PROBE_PODS, PROBE_GPUS = 2, 2
PROBE_ROWS, PROBE_D = 64, 128

_PROBE_CODE = """
import json, time
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.comm import direct_all_to_all, plan_all_to_all, lower_plan
from repro.core.schedulers import get_scheduler
from repro.core.traffic import ClusterSpec, moe_workload
from repro.launch.mesh import make_mesh

pods, gpp, rows, dmodel = {pods}, {gpp}, {rows}, {d}
mesh = make_mesh((pods, gpp), ("pod", "data"))
n = pods * gpp
w = moe_workload(ClusterSpec(pods, gpp), tokens_per_gpu=2048,
                 bytes_per_token=2, seed=0)
plan = get_scheduler("flash").synthesize(w)
sched = lower_plan(plan)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(n * n, rows, dmodel)).astype(np.float32))
spec = P(("pod", "data"))

# use_kernel=False: the jnp gather/scatter path is bit-identical to the
# pallas pair but stable to time on CPU (interpret-mode pallas would
# measure the emulator, not the schedule).
f_plan = jax.jit(jax.shard_map(
    partial(plan_all_to_all, slow_axis="pod", fast_axes=("data",),
            plan=plan, use_kernel=False),
    mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
f_dir = jax.jit(jax.shard_map(
    partial(direct_all_to_all, slow_axis="pod", fast_axes=("data",)),
    mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))

parity = bool(jnp.array_equal(f_plan(x), f_dir(x)))

def best_of(f, repeats=30):
    f(x).block_until_ready()  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    return min(times)

print(json.dumps({{
    "plan_s": best_of(f_plan),
    "direct_s": best_of(f_dir),
    "parity": parity,
    "n_stages": sched.n_stages,
    "n_plan_stages": sched.n_plan_stages,
}}))
"""


def _measure_device_probe():
    """Run the plan-vs-direct device exchange in a fresh fake-device
    process; returns the probe's measurement dict."""
    env = dict(os.environ)
    # Fake host devices live on the CPU backend; pinned so the child never
    # claims an accelerator the parent (or another process) owns.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{PROBE_PODS * PROBE_GPUS}")
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    code = _PROBE_CODE.format(pods=PROBE_PODS, gpp=PROBE_GPUS,
                              rows=PROBE_ROWS, d=PROBE_D)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device probe failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _step_time(cluster, algo: str, top_k: int, seed=0) -> float:
    w = moe_workload(cluster, TOKENS_PER_GPU, BYTES_PER_TOKEN,
                     top_k=top_k, seed=seed)
    a2a = simulate(w, algo).completion_time
    # expert FFN flops per GPU per layer (fwd 2x matmul, bwd 2x fwd)
    tokens = TOKENS_PER_GPU * top_k
    flops = 2 * tokens * D_MODEL * D_FF * 3 * 3
    compute = flops / MI300X_FLOPS
    # attention + the dense transformer layers interleaved with MoE layers
    # (paper Fig 2: half the stack is dense) -- roughly 2x the expert flops
    dense = 2 * compute
    # 4 All-to-Alls per MoE layer (dispatch+combine, fwd+bwd)
    return N_MOE_LAYERS * (compute + dense + 4 * a2a)


def run(csv: Csv):
    base = dict(TESTBED)
    for n_servers in (1, 2, 4):
        cluster = ClusterSpec(**{**base, "n_servers": n_servers})
        flash = _step_time(cluster, "flash", top_k=2)
        fanout = _step_time(cluster, "fanout", top_k=2)
        plan_t = _step_time(cluster, "flash", top_k=2)  # plan == flash sim
        csv.emit(f"fig14.experts{n_servers * 8}", flash * 1e6,
                 f"speedup_vs_fanout={fanout / flash:.2f}x"
                 f"|plan_us={plan_t * 1e6:.1f}"
                 f"|tokens_per_s={TOKENS_PER_GPU / flash:.0f}")
    cluster = ClusterSpec(**base)
    for k in (1, 2, 4):
        flash = _step_time(cluster, "flash", top_k=k)
        fanout = _step_time(cluster, "fanout", top_k=k)
        csv.emit(f"fig14.top{k}", flash * 1e6,
                 f"speedup_vs_fanout={fanout / flash:.2f}x")

    # -- measured vs simulated: the plan-exec device loop ------------------
    probe = _measure_device_probe()
    measured = probe["plan_s"] / probe["direct_s"]
    w = moe_workload(ClusterSpec(PROBE_PODS, PROBE_GPUS),
                     tokens_per_gpu=2048, bytes_per_token=2, seed=0)
    predicted = (simulate(w, "flash").completion_time
                 / simulate(w, "fanout").completion_time)
    pred_err = abs(measured - predicted) / predicted
    csv.emit("e2e.plan_vs_direct", measured,
             f"parity={'ok' if probe['parity'] else 'MISMATCH'}"
             f"|stages={probe['n_stages']}"
             f"|plan_stages={probe['n_plan_stages']}"
             f"|plan_us={probe['plan_s'] * 1e6:.1f}"
             f"|direct_us={probe['direct_s'] * 1e6:.1f}")
    csv.emit("e2e.sim_pred_err", pred_err,
             f"measured={measured:.3f}|predicted={predicted:.3f}")
