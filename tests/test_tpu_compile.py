"""Compiles for a described TPU v5e, no chip attached: the exchange kernels,
the plan-driven exchange and the served model, at ``chip_smoke.py``'s real
shapes.

Nothing runs, so these say nothing about results or times; they catch what
the TPU compiler refuses (block tiling, kernels it cannot partition, a
program that does not fit the device).  The topology is described in a
module fixture, never on import: with several pytest workers only the one
given this file loads the TPU library, and it compiles in its own process.
"""

import importlib.util
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from conftest import REPO

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg(smoke):
    return smoke.model_config()


def _plan(smoke, cfg):
    from repro.core.schedulers import get_scheduler
    from repro.core.traffic import ClusterSpec, moe_workload

    w = moe_workload(ClusterSpec(smoke.PODS, smoke.FAST),
                     tokens_per_gpu=smoke.PROMPT_LEN, bytes_per_token=2,
                     n_experts=cfg.moe.num_experts, seed=0)
    return get_scheduler("flash").synthesize(w)


@pytest.mark.parametrize("kernel", ["pack", "unpack"])
def test_a2a_kernels_compile_at_exchange_block(one_chip, smoke, cfg, kernel):
    from repro.kernels.a2a_pack.a2a_pack import a2a_pack, a2a_unpack

    block = smoke.exchange_block_rows(
        cfg, smoke.N_REQUESTS * smoke.PROMPT_LEN)
    n_slots = 3
    rows = (smoke.PODS if kernel == "pack" else n_slots) * block
    x = jax.ShapeDtypeStruct((rows, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    idx = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=one_chip)
    fn = partial(a2a_pack, block_rows=block, interpret=False) \
        if kernel == "pack" else partial(
            a2a_unpack, n_out_blocks=smoke.PODS + 1, block_rows=block,
            interpret=False)
    text = jax.jit(fn).lower(x, idx).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("axes", [("pod", "data"),
                                  ("pod", "data", "model")])
def test_plan_all_to_all_compiles_on_2x2(topo, smoke, cfg, axes):
    """All axes manual, and the MoE island's form: ``model`` (size 1)
    left automatic, where the kernels need a shard_map of their own."""
    from repro.comm import plan_all_to_all

    n = smoke.PODS * smoke.FAST
    mesh = Mesh(np.array(topo.devices).reshape(
        (smoke.PODS, smoke.FAST) + (1,) * (len(axes) - 2)), axes)
    rows = smoke.exchange_block_rows(
        cfg, smoke.N_REQUESTS * smoke.PROMPT_LEN) // smoke.FAST
    spec = P(("pod", "data"))
    x = jax.ShapeDtypeStruct((n * n, rows, cfg.d_model), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    f = jax.shard_map(
        partial(plan_all_to_all, slow_axis="pod", fast_axes=("data",),
                plan=_plan(smoke, cfg), interpret=False),
        mesh=mesh, in_specs=spec, out_specs=spec,
        axis_names={"pod", "data"}, check_vma=False)
    text = jax.jit(f).lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_megatron_prefill_fits_one_chip(one_chip, smoke, cfg):
    from repro.launch.serve import make_prefill_step
    from repro.models import build_model

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (smoke.N_REQUESTS, smoke.PROMPT_LEN), jnp.int32, sharding=one_chip)}
    step = make_prefill_step(cfg, None,
                             cache_len=smoke.PROMPT_LEN + smoke.GEN_LEN)
    mem = step.lower(params, batch).compile().memory_analysis()
    assert mem.argument_size_in_bytes < V5E_HBM_BYTES
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM_BYTES


# mixtral-8x7b-2l, the decode cell's configuration, and its traffic: 64
# streams of 128 prompt and 512 generated tokens
MIXTRAL_2L = dict(n_layers=2, d_model=4096, n_heads=32, n_kv_heads=8,
                  head_dim=128, d_ff=14336, vocab=32000, swa_window=4096,
                  param_dtype="bfloat16", compute_dtype="bfloat16")
DECODE_BATCH, DECODE_LEN = 64, 128 + 512


def test_decode_step_updates_the_cache_in_place(one_chip):
    """The served decode step at the decode cell's shapes aliases the
    donated cache, copies none of it (neither a layer nor the stack, nor
    the layer's slice into a temporary): only the new rows are written."""
    from repro.configs import get_config
    from repro.launch.serve import make_serve_step
    from repro.models import build_model

    cfg = get_config("mixtral-8x7b", **MIXTRAL_2L)
    model = build_model(cfg)
    on_chip = partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(DECODE_BATCH, DECODE_LEN)))
    layer = (DECODE_BATCH, cfg.n_kv_heads, DECODE_LEN, cfg.resolved_head_dim)
    assert cache["k"].shape == (cfg.n_layers,) + layer
    toks = jax.ShapeDtypeStruct((DECODE_BATCH,), jnp.int32,
                                sharding=one_chip)
    compiled = make_serve_step(cfg, None).lower(
        params, cache, toks, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    layer_k_bytes = cache_bytes // (2 * cfg.n_layers)
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < layer_k_bytes
    dims = ",".join(map(str, layer))
    cache_shape = re.compile(rf"\[(\d+,)?{dims}\]")
    copies = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w\-]+)\(", line)
        if m and cache_shape.search(m.group(2)) and (
                m.group(3) in ("copy", "copy-start")
                or m.group(1).startswith("copy")):
            copies.append(line)
    assert not copies, copies
