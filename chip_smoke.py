#!/usr/bin/env python3
"""Run the served MoE path and the plan-driven exchange once on a TPU.

    python chip_smoke.py              # one chip: serve + kernel phases
    python chip_smoke.py --chips 4    # 2x2 chips: plan vs direct exchange

Model: megatron-moe-32e, the paper's own workload, at its published widths
(d_model 2048, 32 query / 8 KV heads, d_ff 8192, 32 experts top-2, vocab
50304), cut to 2 of its 24 layers (the stack is scanned, so every layer
runs the same program) with bf16 parameters, so that it fits one 16 GB
v5e.  Weights and prompts are random, drawn from ``--seed``.

One chip (default):
  serve   the parameters are initialised under jit; 4 requests (512-token
          prompts, 16 generated tokens each) are served through
          ``make_prefill_step`` / ``make_serve_step``, twice, and must give
          the same tokens.  The plan-serving daemon answers with the
          device schedule of the 2x2 exchange.  The first MoE layer must
          match the same function run on the host CPU backend.
  kernel  ``a2a_pack`` / ``a2a_unpack`` compiled natively (interpret=False)
          at the block shape the four-chip exchange moves, indexed by the
          daemon's schedule; the compiled program must hold a
          ``tpu_custom_call`` and the results must equal ``ref.py`` bit
          for bit.
Four chips (``--chips 4``), and nothing else:
  exchange  a (pod=2, data=2) mesh with experts over pod x data (8 per
          chip), parameters initialised sharded under jit; the requests
          are served with ``a2a_impl="plan"`` (the daemon's schedule) and
          with ``"direct"``, and every logit must be bit-identical.

Every phase runs in this one process, which starts no other.  Without a
TPU the script exits non-zero before any phase.  The last line of a
passing run is ``{"ok": true, "device": {...}}``; a failing phase raises
and the line is never printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.comm.plan_exec import slot_indices
from repro.configs import get_config
from repro.core.traffic import ClusterSpec, moe_workload
from repro.kernels.a2a_pack.a2a_pack import a2a_pack, a2a_unpack
from repro.kernels.a2a_pack.ref import a2a_pack_ref, a2a_unpack_ref
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.serve import generate, make_prefill_step, make_serve_step
from repro.launch.shardings import batch_shardings, param_shardings
from repro.models import build_model, choose_ep_axes
from repro.models.moe import _capacity, moe_apply
from repro.serving import PlanClient, PlanServer

ARCH = "megatron-moe-32e"
N_LAYERS = 2                       # of 24: 2 bf16 layers take 6.9 GB
N_REQUESTS, PROMPT_LEN, GEN_LEN = 4, 512, 16
PODS, FAST = 2, 2                  # the four-chip mesh: (pod, data)
CPU_REF_TOKENS = 256               # tokens through the first MoE layer
# TPU vs host CPU, first MoE layer: both sides compute in bf16 with f32
# accumulation, the f32 router at "highest" matmul precision so that no
# token changes expert.  Only accumulation order and bf16 rounding differ,
# a few ulps (one bf16 ulp is 2**-8 relative):
MAX_ABS_ERR = 2.0 ** -5            # max |tpu - cpu| / max |cpu|
REL_L2_ERR = 2.0 ** -7             # ||tpu - cpu|| / ||cpu||


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache events, from JAX's own
    monitoring hooks (a cache hit skips the backend compile)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def line(self) -> str:
        return (f"compile: {self.seconds:.3f}s backend compile, persistent "
                f"cache {self.hits} hits, {self.writes} entries written")


def model_config():
    full = get_config(ARCH)
    cfg = get_config(ARCH, n_layers=N_LAYERS, param_dtype="bfloat16")
    log(f"reduced: {ARCH} n_layers {full.n_layers}->{cfg.n_layers}, "
        f"param_dtype {full.param_dtype}->{cfg.param_dtype}; widths as "
        f"published: d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} d_ff={cfg.d_ff} experts={cfg.moe.num_experts} "
        f"top_k={cfg.moe.top_k} vocab={cfg.vocab}")
    return cfg


def daemon_schedule(cfg, seed: int, n_tokens: int):
    """The 2x2 exchange's plan and device schedule from the plan daemon."""
    w = moe_workload(ClusterSpec(PODS, FAST),
                     tokens_per_gpu=n_tokens // (PODS * FAST),
                     bytes_per_token=cfg.d_model * 2, top_k=cfg.moe.top_k,
                     n_experts=cfg.moe.num_experts, seed=seed)
    with PlanServer(workers=1) as srv:
        # No inline fallback: the answer must come from the daemon.
        client = PlanClient(srv, algorithm="flash", inline_fallback=False)
        answer, sched = client.get_device_schedule(w)
    log(f"plan: daemon answered ({answer.source}) for ClusterSpec({PODS}, "
        f"{FAST}); lowered to {sched.n_stages} ppermute stages "
        f"({sched.n_plan_stages} from the plan, {sched.n_fallback_stages} "
        f"coverage) pairs={list(sched.pairs)}")
    check(sched.n_pods == PODS, f"schedule has {sched.n_pods} pods")
    return answer.plan, sched


def make_prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (N_REQUESTS, PROMPT_LEN)
                        ).astype(np.int32)


def serve(cfg, mesh, params, tokens, *, a2a_impl=None, plan=None,
          runs: int = 1):
    """Serve ``tokens`` [B, S] ``runs`` times: prefill + greedy decode.

    Returns ``(prefill_hlo, [(generated [B, GEN_LEN], [logits...]), ...])``
    with every step's logits as host arrays, for bit comparisons.
    """
    batch = {"tokens": tokens}
    prefill = make_prefill_step(cfg, mesh, a2a_impl, plan=plan,
                                cache_len=tokens.shape[1] + GEN_LEN
                                ).lower(params, batch).compile()
    step = make_serve_step(cfg, mesh, a2a_impl, plan=plan)
    out = []
    for run in range(runs):
        t0 = time.perf_counter()
        logits, gen = generate(prefill, step, params, tokens, GEN_LEN)
        jax.block_until_ready(gen)
        dt = time.perf_counter() - t0
        logits = [np.asarray(x.astype(jnp.float32)) for x in logits]
        check(all(np.isfinite(x).all() for x in logits), "non-finite logits")
        gen = np.stack([np.asarray(t) for t in gen], axis=1)
        check(gen.shape == (tokens.shape[0], GEN_LEN),
              f"generated shape {gen.shape}")
        log(f"serve[{a2a_impl or 'local'} run {run + 1}]: "
            f"{gen.shape[0]} requests served, {tokens.shape[1]}-token "
            f"prompts, {gen.shape[1]} tokens each, {dt:.3f}s host wall "
            f"clock{' incl. the decode-step compile' if run == 0 else ''}; "
            f"request 0 tokens {gen[0][:8].tolist()}...")
        out.append((gen, logits))
    return prefill.as_text(), out


def op_counts(hlo: str) -> dict:
    """Exchange-related instructions in a compiled HLO text."""
    ops = {"tpu_custom_call": 0, "collective-permute": 0, "all-to-all": 0}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            ops["tpu_custom_call"] += 1
        for op in ("collective-permute", "all-to-all"):
            if f" {op}(" in line or f" {op}-start(" in line:
                ops[op] += 1
    return ops


def param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


def moe_matches_cpu(cfg, params, seed: int) -> None:
    """First MoE layer on the chip vs the same function on the host CPU."""
    rng = np.random.default_rng(seed + 1)
    x = rng.standard_normal((1, CPU_REF_TOKENS, cfg.d_model)
                            ).astype(jnp.bfloat16)
    layer0 = jax.jit(lambda blocks: jax.tree.map(lambda a: a[0], blocks))(
        params["blocks"]["moe"])
    fn = jax.jit(lambda p, h: moe_apply(cfg, p, h)[0].astype(jnp.float32))
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        on_chip = np.asarray(fn(layer0, jnp.asarray(x)))
        t1 = time.perf_counter()
        on_cpu = np.asarray(fn(jax.device_put(layer0, cpu),
                               jax.device_put(x, cpu)))
        t2 = time.perf_counter()
    del layer0
    peak = float(np.abs(on_cpu).max())
    max_err = float(np.abs(on_chip - on_cpu).max()) / peak
    rel_l2 = float(np.linalg.norm(on_chip - on_cpu) / np.linalg.norm(on_cpu))
    log(f"cpu reference: first MoE layer, {CPU_REF_TOKENS} tokens, "
        f"{jax.devices()[0].platform} vs cpu: max|err|/max|ref|={max_err:.6g} "
        f"(limit {MAX_ABS_ERR:.6g}), rel L2={rel_l2:.6g} "
        f"(limit {REL_L2_ERR:.6g}); chip {t1 - t0:.3f}s, "
        f"cpu {t2 - t1:.3f}s incl. compile")
    check(np.isfinite(on_chip).all(), "non-finite MoE output on the chip")
    check(max_err <= MAX_ABS_ERR and rel_l2 <= REL_L2_ERR,
          "first MoE layer differs from the host CPU beyond bf16 tolerance")


def exchange_block_rows(cfg, n_tokens: int) -> int:
    """Rows per pod block of the four-chip prefill's MoE exchange:
    fast x experts-per-chip x capacity (``plan_all_to_all``'s block)."""
    n_shards = PODS * FAST
    e_loc = cfg.moe.num_experts // n_shards
    return FAST * e_loc * _capacity(cfg, n_tokens // n_shards,
                                    cfg.moe.num_experts)


def kernel_phase(cfg, sched, seed: int) -> None:
    """a2a_pack / a2a_unpack compiled for the chip vs ref.py, bit for bit."""
    block = exchange_block_rows(cfg, N_REQUESTS * PROMPT_LEN)
    p, s = sched.n_pods, sched.n_stages
    rng = np.random.default_rng(seed + 2)
    x = jnp.asarray(rng.standard_normal((p * block, cfg.d_model)),
                    jnp.bfloat16)
    pack = jax.jit(partial(a2a_pack, block_rows=block, interpret=False))
    unpack = jax.jit(partial(a2a_unpack, n_out_blocks=p + 1,
                             block_rows=block, interpret=False))
    for pod in range(p):
        dst_idx, src_idx = slot_indices(sched, pod)
        pack_c = pack.lower(x, dst_idx).compile()
        packed = pack_c(x, dst_idx)
        unpack_c = unpack.lower(packed, src_idx).compile()
        out = unpack_c(packed, src_idx)
        for name, c in (("a2a_pack", pack_c), ("a2a_unpack", unpack_c)):
            check("tpu_custom_call" in c.as_text(),
                  f"{name} compiled without a tpu_custom_call")
        pack_eq = bool(jnp.array_equal(
            packed, a2a_pack_ref(x, dst_idx, block_rows=block)))
        # Rows past p * block are the trash block, unspecified by contract.
        unpack_eq = bool(jnp.array_equal(
            out[:p * block],
            a2a_unpack_ref(packed, src_idx, n_out_blocks=p + 1,
                           block_rows=block)[:p * block]))
        log(f"kernel[pod {pod}]: bf16 block {block}x{cfg.d_model} rows, "
            f"{s + 1} slots, dst={np.asarray(dst_idx).tolist()} "
            f"src={np.asarray(src_idx).tolist()}: tpu_custom_call present; "
            f"pack==ref {pack_eq}, unpack==ref {unpack_eq}")
        check(pack_eq and unpack_eq, "a2a kernels differ from ref.py")


def one_chip(seed: int) -> None:
    cfg = model_config()
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    log(f"params: {param_bytes(params) / 1e9:.3f} GB on "
        f"{jax.devices()[0].device_kind}, initialised under jit")
    _, sched = daemon_schedule(cfg, seed, N_REQUESTS * PROMPT_LEN)
    prompts = make_prompts(cfg, seed)
    _, runs = serve(cfg, None, params, prompts, runs=2)
    (gen_a, _), (gen_b, _) = runs
    same = bool(np.array_equal(gen_a, gen_b))
    log(f"serve: tokens identical across two runs: {same}")
    check(same, "two identical serving runs produced different tokens")
    moe_matches_cpu(cfg, params, seed)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    del params
    kernel_phase(cfg, sched, seed)


def four_chips(seed: int) -> None:
    cfg = model_config()
    # A size-1 "model" axis keeps the repo's tensor-parallel rules valid.
    mesh = make_mesh((PODS, FAST, 1), ("pod", "data", "model"))
    ep = choose_ep_axes(cfg, mesh)
    check(ep == ("pod", "data"), f"experts over {ep}, not pod x data")
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    shardings = param_shardings(cfg, mesh, jax.eval_shape(model.init, key))
    params = jax.jit(model.init, out_shardings=shardings)(key)
    experts = [params["blocks"]["moe"][k] for k in ("w_gate", "w_up",
                                                    "w_down")]
    for dev in mesh.devices.flat:
        held = sum(s.data.nbytes for w in experts
                   for s in w.addressable_shards if s.device == dev)
        log(f"experts on {dev}: {held / 1e9:.3f} GB "
            f"({cfg.moe.num_experts // (PODS * FAST)} experts per layer)")
    plan, _ = daemon_schedule(cfg, seed, N_REQUESTS * PROMPT_LEN)
    prompts = make_prompts(cfg, seed)
    tokens = jax.device_put(prompts, batch_shardings(
        mesh, {"tokens": jax.ShapeDtypeStruct(prompts.shape, prompts.dtype)}
    )["tokens"])
    results = {}
    for impl in ("plan", "direct"):
        hlo, [(gen, logits)] = serve(cfg, mesh, params, tokens,
                                     a2a_impl=impl,
                                     plan=plan if impl == "plan" else None)
        ops = op_counts(hlo)
        log(f"prefill[{impl}] program ops: {ops}")
        check(ops["tpu_custom_call"] > 0 if impl == "plan"
              else ops["all-to-all"] > 0,
              f"prefill[{impl}] lacks its exchange ops: {ops}")
        results[impl] = (gen, logits)
    (gen_p, log_p), (gen_d, log_d) = results["plan"], results["direct"]
    same = [bool(np.array_equal(a, b)) for a, b in zip(log_p, log_d)]
    log(f"exchange: plan vs direct logits bit-identical at "
        f"{sum(same)}/{len(same)} steps; tokens identical "
        f"{bool(np.array_equal(gen_p, gen_d))}")
    check(all(same), "plan and direct exchanges gave different logits")
    for dev in mesh.devices.flat:
        stats = dev.memory_stats() or {}
        log(f"memory {dev}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + kernel phases; 4: the 2x2 plan vs "
                         "direct exchange only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2
    compiles = CompileLog()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args.seed)
    else:
        four_chips(args.seed)
    log(compiles.line())
    log(f"total: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
