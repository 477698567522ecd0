import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")  # mute SPMD copy warnings

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any jax import -- jax locks the device
count at first init, and the dry-run needs 512 placeholder CPU devices to
build the production meshes (on the CPU backend, so the process never
claims an attached accelerator):

    single pod : (16, 16)        ("data", "model")       256 chips
    multi-pod  : (2, 16, 16)     ("pod", "data", "model") 512 chips

For each cell this driver:
  1. builds abstract state/batch trees (ShapeDtypeStruct, no allocation),
  2. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...)``,
  3. ``.compile()``  -- sharding mismatches / OOM / unsupported collectives
     fail HERE and are bugs in the system,
  4. records memory_analysis(), cost_analysis(), and the parsed collective
     schedule (repro.launch.roofline) as JSON for EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k \
        --mesh multi --out results/
    python -m repro.launch.dryrun --all --mesh single --out results/
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from ..configs import SHAPES, get_config, list_archs, skip_reason
from ..models.model import input_specs
from .mesh import make_production_mesh
from .roofline import CollectiveStats, parse_collectives, roofline_terms
from .serve import make_prefill_step, make_serve_step, serve_state_shapes
from .shardings import batch_shardings
from .train import TrainOptions, make_train_step


def _lower_cell(cfg, shape, mesh, a2a_impl: Optional[str] = None,
                extra_overrides: Optional[dict] = None):
    """Returns (lowered, compiled) for one cell."""
    import dataclasses as dc
    overrides = dict(extra_overrides or {})
    if a2a_impl:
        overrides["a2a_impl"] = a2a_impl
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    batch_shape = jax.tree.map(
        lambda s: s,
        input_specs(cfg, shape.kind, shape.seq_len, shape.global_batch))

    if shape.kind == "train":
        step, state_shape, state_sh, batch_sh_fn = make_train_step(
            cfg, mesh, TrainOptions(microbatches=cfg.microbatches))
        batch_sh = batch_sh_fn(batch_shape)
        lowered = step.lower(
            _with_sh(state_shape, state_sh), _with_sh(batch_shape, batch_sh))
    elif shape.kind == "prefill":
        params_shape, params_sh, _, _ = serve_state_shapes(
            cfg, mesh, shape.global_batch, shape.seq_len)
        step = make_prefill_step(cfg, mesh)
        batch_sh = batch_shardings(mesh, batch_shape)
        lowered = step.lower(
            _with_sh(params_shape, params_sh),
            _with_sh(batch_shape, batch_sh))
    elif shape.kind == "decode":
        params_shape, params_sh, cache_shape, cache_sh = serve_state_shapes(
            cfg, mesh, shape.global_batch, shape.seq_len)
        step = make_serve_step(cfg, mesh)
        batch_sh = batch_shardings(mesh, batch_shape)
        lowered = step.lower(
            _with_sh(params_shape, params_sh),
            _with_sh(cache_shape, cache_sh),
            _with_sh({"t": batch_shape["tokens"]},
                     {"t": batch_sh["tokens"]})["t"],
            _with_sh({"p": batch_shape["pos"]},
                     {"p": batch_sh["pos"]})["p"])
    else:
        raise ValueError(shape.kind)
    compiled = lowered.compile()
    return lowered, compiled


def _with_sh(shape_tree, sh_tree):
    """Attach shardings to ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape_tree, sh_tree)


def _cell_costs(compiled) -> tuple:
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax: one dict per program
        cost = cost[0] if cost else {}
    coll = parse_collectives(compiled.as_text(), pod_size=256)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _extrapolated_costs(cfg, shape, mesh, a2a_impl, overrides):
    """XLA cost analysis counts a while-loop (scan-over-layers) body ONCE.

    For scanned archs we therefore lower unrolled 2- and 3-layer variants
    and extrapolate linearly in layer count: cost(L) = c2 + (L-2)*(c3-c2).
    Memory analysis / compile proof still come from the true scanned module.
    """
    import dataclasses as dc
    vals = {}
    for l in (2, 3):
        c = dc.replace(cfg, n_layers=l, scan_layers=False)
        _, compiled = _lower_cell(c, shape, mesh, a2a_impl, overrides)
        vals[l] = _cell_costs(compiled)
    big = cfg.n_layers

    def lin(a, b):
        return a + (big - 2) * (b - a)

    f = lin(vals[2][0], vals[3][0])
    by = lin(vals[2][1], vals[3][1])
    c2, c3 = vals[2][2], vals[3][2]
    coll = CollectiveStats(
        simple_bytes=lin(c2.simple_bytes, c3.simple_bytes),
        wire_bytes=lin(c2.wire_bytes, c3.wire_bytes),
        ici_bytes=lin(c2.ici_bytes, c3.ici_bytes),
        dcn_bytes=lin(c2.dcn_bytes, c3.dcn_bytes),
        by_op={k: lin(c2.by_op.get(k, 0.0), c3.by_op.get(k, 0.0))
               for k in set(c2.by_op) | set(c3.by_op)},
        count=int(lin(c2.count, c3.count)),
    )
    return f, by, coll


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             a2a_impl: Optional[str] = None,
             overrides: Optional[dict] = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        import dataclasses as dc
        overrides = dict(overrides)
        capf = overrides.pop("capacity_factor", None)
        if capf is not None and cfg.moe is not None:
            cfg = dc.replace(cfg, moe=dc.replace(cfg.moe,
                                                 capacity_factor=capf))
        cfg_over = {k: v for k, v in overrides.items()
                    if k in {f.name for f in dc.fields(cfg)}}
        cfg = dc.replace(cfg, **cfg_over)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    t0 = time.perf_counter()
    try:
        lowered, compiled = _lower_cell(cfg, shape, mesh, a2a_impl)
    except Exception as e:  # noqa: BLE001 - reported as cell failure
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "failed", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:]}
    compile_s = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    flops_raw, bytes_raw, coll_raw = _cell_costs(compiled)
    if cfg.scan_layers and cfg.n_layers > 3:
        try:
            flops, bytes_accessed, coll = _extrapolated_costs(
                cfg, shape, mesh, a2a_impl, None)
            cost_source = "unrolled-2/3-extrapolation"
        except Exception as e:  # noqa: BLE001
            flops, bytes_accessed, coll = flops_raw, bytes_raw, coll_raw
            cost_source = f"scan-body-once (extrapolation failed: {e})"
    else:
        flops, bytes_accessed, coll = flops_raw, bytes_raw, coll_raw
        cost_source = "direct"
    if shape.kind == "train" and cfg.microbatches > 1:
        # the grad-accumulation scan body is also counted once by cost
        # analysis; scale to the per-step total (peak memory is NOT scaled:
        # one microbatch lives at a time -- that is the point)
        n_mb = cfg.microbatches
        flops *= n_mb
        bytes_accessed *= n_mb
        coll = CollectiveStats(
            simple_bytes=coll.simple_bytes * n_mb,
            wire_bytes=coll.wire_bytes * n_mb,
            ici_bytes=coll.ici_bytes * n_mb,
            dcn_bytes=coll.dcn_bytes * n_mb,
            by_op={k: v * n_mb for k, v in coll.by_op.items()},
            count=coll.count * n_mb)
        cost_source += f" x{n_mb}-microbatches"
    terms = roofline_terms(flops, bytes_accessed, coll)

    n = cfg.n_params()
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_active * tokens

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "a2a_impl": a2a_impl or cfg.a2a_impl,
        "overrides": overrides or {},
        "status": "ok",
        "n_chips": n_chips,
        "compile_s": round(compile_s, 2),
        "cost_source": cost_source,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "flops_per_chip": flops,
        "bytes_per_chip": bytes_accessed,
        "collectives": {
            "count": coll.count,
            "simple_bytes": coll.simple_bytes,
            "wire_bytes": coll.wire_bytes,
            "ici_bytes": coll.ici_bytes,
            "dcn_bytes": coll.dcn_bytes,
            "by_op": coll.by_op,
        },
        "roofline": terms,
        "model_flops_total": model_flops,
        "model_flops_per_chip": model_flops / n_chips,
        "useful_flop_ratio": (model_flops / n_chips) / flops
        if flops else None,
        "params_total": n,
        "params_active": n_active,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--a2a", choices=["flash", "direct", "hierarchical"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field overrides key=value (python literals)")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            import ast
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    cells = []
    if args.all:
        for arch in list_archs():
            for shape_name in SHAPES:
                cells.append((arch, shape_name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch/--shape required unless --all")
        cells.append((args.arch, args.shape))

    for arch, shape_name in cells:
        res = run_cell(arch, shape_name, args.mesh, args.a2a,
                       overrides or None)
        tag = f"{arch}.{shape_name}.{args.mesh}"
        if args.a2a:
            tag += f".{args.a2a}"
        if overrides:
            tag += "." + "_".join(f"{k}-{v}" for k, v in overrides.items())
        line = {k: v for k, v in res.items()
                if k in ("arch", "shape", "mesh", "status", "compile_s",
                         "flops_per_chip", "reason", "error")}
        print(json.dumps(line))
        if res["status"] == "ok":
            mem = res["memory"]
            print(f"  memory/chip: args={_gb(mem['argument_bytes'])} "
                  f"temp={_gb(mem['temp_bytes'])} "
                  f"peak={_gb(mem['peak_bytes'])}")
            r = res["roofline"]
            print(f"  roofline: compute={r['compute_s']:.4f}s "
                  f"memory={r['memory_s']:.4f}s "
                  f"collective={r['collective_s']:.4f}s "
                  f"dominant={r['dominant']}")
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f, indent=1)


def _gb(x):
    return f"{x / (1 << 30):.2f}GB" if x is not None else "?"


if __name__ == "__main__":
    main()
