"""Serving-step construction + a batched-request demo server.

``make_serve_step`` builds the jit'd one-token decode step against a KV
cache / recurrent state for a shape cell; ``make_prefill_step`` builds the
prompt pass.  Run directly for a CPU-scale batched-serving demo:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from .. import scopes
from ..configs import ModelConfig, get_config, smoke_config
from ..models import build_model, use_mesh_rules
from .compile_cache import enable_compile_cache
from .shardings import cache_shardings, param_shardings
from .train import make_dist_context, make_rules

__all__ = ["make_serve_step", "make_prefill_step", "generate",
           "serve_state_shapes"]


def serve_state_shapes(cfg: ModelConfig, mesh: Optional[Mesh],
                       batch: int, seq_len: int):
    """(params_shape, params_sh, cache_shape, cache_sh) -- no allocation."""
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_shape = jax.eval_shape(
        lambda: model.init_cache(batch, seq_len))
    if mesh is None:
        return params_shape, None, cache_shape, None
    return (params_shape, param_shardings(cfg, mesh, params_shape),
            cache_shape, cache_shardings(cfg, mesh, cache_shape))


class _Spanned:
    """A jitted step whose every call is a host span around its dispatch
    (a no-op unless a profiler runs).  Every attribute -- ``lower``,
    ``trace``, ... -- is the jitted function's."""

    def __init__(self, jitted, span: str):
        self._jitted, self._span = jitted, span

    def __call__(self, *args, **kwargs):
        with TraceAnnotation(self._span):
            return self._jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def make_serve_step(cfg: ModelConfig, mesh: Optional[Mesh],
                    a2a_impl: Optional[str] = None, plan=None):
    """jit'd (params, cache, tokens [B], pos) -> (logits [B, V], cache).

    ``a2a_impl`` selects the MoE dispatch schedule through the comm-layer
    registry (flash | direct | hierarchical | plan), defaulting to the
    config's.  ``plan`` is the synthesized Plan/ExecutableSchedule that
    backs ``"plan"`` (and that ``"auto"`` prefers); pair with
    ``serving.PlanClient.get_device_schedule`` for the daemon handoff.
    """
    model = build_model(cfg)
    dist = make_dist_context(cfg, mesh, a2a_impl, plan=plan) \
        if mesh is not None else None
    rules = make_rules(cfg, mesh) if mesh is not None else None

    def serve_step(params, cache, tokens, pos):
        with use_mesh_rules(rules):
            return model.decode_step(params, cache, tokens, pos, dist)

    if mesh is None:
        return _Spanned(jax.jit(serve_step), scopes.SERVE_DECODE_STEP)
    return _Spanned(jax.jit(serve_step, donate_argnums=(1,)),
                    scopes.SERVE_DECODE_STEP)


def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh],
                      a2a_impl: Optional[str] = None, plan=None,
                      cache_len: Optional[int] = None):
    """jit'd (params, batch) -> (logits, cache | aux).

    ``cache_len`` sizes the decode cache for prompt + generation budget
    (decoder-only LMs; default = prompt length).
    """
    model = build_model(cfg)
    dist = make_dist_context(cfg, mesh, a2a_impl, plan=plan) \
        if mesh is not None else None
    rules = make_rules(cfg, mesh) if mesh is not None else None
    extra = {} if cache_len is None else {"cache_len": cache_len}

    def prefill_step(params, batch):
        with use_mesh_rules(rules):
            return model.prefill(params, batch, dist, **extra)

    return _Spanned(jax.jit(prefill_step), scopes.SERVE_PREFILL)


def generate(prefill, step, params, tokens, gen_len: int):
    """Greedy decoding of a batch: prefill ``tokens`` [B, S], then
    ``gen_len - 1`` decode steps from position S.

    ``prefill`` / ``step`` are the callables ``make_prefill_step`` (with
    ``cache_len >= S + gen_len``) and ``make_serve_step`` return.  Returns
    ``(logits, tokens)``: ``gen_len`` device arrays each, [B, V] and [B].
    """
    logits, cache = prefill(params, {"tokens": tokens})
    with TraceAnnotation(scopes.SERVE_ARGMAX):
        toks = jnp.argmax(logits, -1)
    all_logits, out = [logits], [toks]
    start = tokens.shape[1]
    for t in range(start, start + gen_len - 1):
        logits, cache = step(params, cache, toks, jnp.int32(t))
        with TraceAnnotation(scopes.SERVE_ARGMAX):
            toks = jnp.argmax(logits, -1)
        all_logits.append(logits)
        out.append(toks)
    return all_logits, out


# -- CPU-scale batched-serving demo ------------------------------------------

def _plan_dispatch_schedules(gen_len: int, use_plan_server: bool) -> None:
    """Plan the MoE dispatch schedule each decode step would issue.

    Models the testbed fabric (4 servers x 8 GPUs) and one drifting MoE
    dispatch matrix per generated token.  With ``use_plan_server`` the
    plan requests route through the serving daemon (``repro.serving``);
    the default stays on the inline path -- ``simulate_many`` over a
    process-local PlanCache -- so the two paths print side by side
    comparable hit rates.
    """
    from ..core.plan import PlanCache
    from ..core.simulator import simulate_many
    from ..core.traffic import ClusterSpec, moe_workload

    cluster = ClusterSpec(n_servers=4, m_gpus=8)
    # Each decode step re-draws gating for the same token budget; every
    # 4th step repeats a seed (hot signatures), the rest drift.
    traj = [moe_workload(cluster, tokens_per_gpu=2048, bytes_per_token=2,
                         seed=(step // 4 if step % 4 == 0 else step))
            for step in range(gen_len)]
    t0 = time.perf_counter()
    if use_plan_server:
        from ..serving import PlanClient, PlanServer

        with PlanServer(workers=2) as srv:
            client = PlanClient(srv, algorithm="flash")
            results = client.simulate_many(traj)
            # Device handoff: each distinct signature's plan comes back
            # with its lowered stage tables; repeats reuse the memoized
            # lowering (counters["lowered"] counts only the cache misses).
            scheds = [client.get_device_schedule(w)[1] for w in traj]
            srv.drain(10.0)
            stats = srv.telemetry_snapshot()
        counters = stats["counters"]
        route = (f"plan-server: hits={counters.get('hits', 0)} "
                 f"warm={counters.get('warm', 0)} "
                 f"cold={counters.get('cold', 0)} "
                 f"upgrades={counters.get('upgrades', 0)}")
        n_stages = sorted({s.n_stages for s in scheds})
        print(f"device handoff: {len(scheds)} schedules, "
              f"{client.counters['lowered']} lowered "
              f"({len(scheds) - client.counters['lowered']} memoized); "
              f"stage counts {n_stages}")
    else:
        cache = PlanCache(capacity=256, warm_start=True)
        results = simulate_many(traj, "flash", cache=cache)
        route = (f"inline: hits={cache.hits} misses={cache.misses} "
                 f"warm={cache.warm_hits}")
    dt = time.perf_counter() - t0
    mean_us = float(np.mean([r.completion_time for r in results])) * 1e6
    print(f"dispatch planning [{route}] {len(traj)} steps in {dt:.3f}s; "
          f"mean schedule completion {mean_us:.1f}us")


def main():
    from ..comm.all_to_all import available_all_to_all_impls

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--a2a", default=None,
                    choices=available_all_to_all_impls() + ["auto"],
                    help="MoE All-to-All schedule (registry name, or "
                         "'auto' to resolve from the fabric topology); "
                         "defaults to the arch config's a2a_impl")
    ap.add_argument("--plan-server", action="store_true",
                    help="route dispatch-schedule planning through the "
                         "plan-serving daemon (repro.serving) instead of "
                         "the inline PlanCache path")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.a2a:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, a2a_impl=args.a2a)
    model = build_model(cfg)
    # Under jit the parameters are generated on the device in their own
    # dtype, with no eager per-leaf f32 temporaries.
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab,
                           (args.batch, args.prompt_len)).astype(np.int32)
    total = args.prompt_len + args.gen_len
    prefill = make_prefill_step(cfg, mesh=None, cache_len=total)
    step = make_serve_step(cfg, mesh=None)
    t0 = time.perf_counter()
    _, out = generate(prefill, step, params, jnp.asarray(prompts),
                      args.gen_len)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    gen = np.stack([np.asarray(t) for t in out], axis=1)
    tput = args.batch * gen.shape[1] / dt
    print(f"arch={cfg.name} batch={args.batch} generated={gen.shape[1]} "
          f"tokens/req; {tput:.1f} tok/s total")
    print("sample:", gen[0][:16])
    _plan_dispatch_schedules(args.gen_len, args.plan_server)


if __name__ == "__main__":
    main()
