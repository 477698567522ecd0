"""Architectures are found by the configuration's ``arch``: the moved
code makes the very numbers it made before it moved, and a second
architecture runs through the harness with new files alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import cells, weights
from bench.reference import model

DATA = os.path.join(os.path.dirname(__file__), "data")

# The registry's smoke widths of megatron-moe-32e (capacity factor cut to
# 0.25, so that experts overflow and the drop path runs) and of
# mixtral-8x7b (window 16, bf16 weights).
MEGATRON = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
            "head_dim": 16, "d_ff": 128, "vocab": 256, "num_experts": 4,
            "top_k": 2, "capacity_factor": 0.25, "rope_theta": 1e4,
            "swa_window": None, "norm": "rmsnorm", "act": "silu",
            "tie_embeddings": False, "param_dtype": "float32",
            "compute_dtype": "float32"}
MIXTRAL = dict(MEGATRON, d_ff=96, swa_window=16, rope_theta=1e6,
               capacity_factor=2.0, param_dtype="bfloat16",
               compute_dtype="bfloat16")
SEED = 2 ** 33 + 7

# Made by the benchmark's code before the architecture moved into
# ``bench/arch/moe_gqa.py`` (weights.make_params / LayerMaker /
# make_globals, Reference.hidden at each precision with its dropped
# assignments, flops.prefill_flops(dims, 4, 2048) and
# flops.decode_step_flops(dims, 64, 300)), on this CPU.
BEFORE = {
    "megatron": {
        "params": "8a8ff1b5a55af9d1", "layer1": "88fc1d3b5b6f3d0a",
        "globals": "1c25d518ba21e269",
        "hidden": {None: ["8954f6240c6eead3", 130],
                   "e4m3": ["16beee08ae53c4da", 131],
                   "bf16": ["fa3b37cca0b76c97", 130]},
        "prefill_flops": 6318850048, "decode_step_flops": 27754496},
    "mixtral": {
        "params": "17cd31f74737c48b", "layer1": "5c29990d47d1d83f",
        "globals": "c10b67ac662af05f",
        "hidden": {None: ["83d84020cfec59e1", 0],
                   "e4m3": ["2c88599bde0be127", 0],
                   "bf16": ["e76ca2f0d4dbbfd8", 0]},
        "prefill_flops": 1685995520, "decode_step_flops": 15269888},
}


def digest(tree) -> str:
    """Every leaf's path, dtype, shape and bytes, hashed in path order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(weights._path(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,dims", [("megatron", MEGATRON),
                                       ("mixtral", MIXTRAL)])
def test_the_moved_code_makes_the_same_numbers(name, dims):
    want = BEFORE[name]
    arch = cells.load_arch("moe_gqa")
    tree = arch.param_tree(dims)
    assert digest(weights.make_params(tree, SEED)) == want["params"]
    assert digest(weights.LayerMaker(tree, "blocks")(SEED, 1)) \
        == want["layer1"]
    g = weights.make_globals(tree, ["blocks"], SEED)
    assert digest(g) == want["globals"]
    tokens = np.random.default_rng(3).integers(
        0, dims["vocab"], (4, 24)).astype(np.int32)
    job = {"device": jax.devices()[0], "embed": g["embed"],
           "tokens": tokens, "prompt_len": 16, "n_shards": 2}
    ref = model.Reference(arch, dims)
    for low, (hidden, dropped) in want["hidden"].items():
        r = model.Reference(arch, dims, low=low, makers=ref.makers)
        (h, drops), = r.hidden(SEED, [job])
        assert [digest(h), drops] == [hidden, dropped], low
    assert arch.prefill_flops(dims, 4, 2048) == want["prefill_flops"]
    assert arch.decode_step_flops(dims, 64, 300) == \
        want["decode_step_flops"]


# The granite-3-2b registry entry's smoke widths, served in float32 (the
# program and the reference then compute the same numbers), its layers
# scanned like the served configurations'.
DENSE_CONFIG = {
    "name": "granite-3-2b-smoke",
    "source": "https://huggingface.co/ibm-granite/granite-3.0-2b-base",
    "registry": "granite-3-2b", "smoke": True, "arch": "dense_gqa",
    "program": {"scan_layers": True},
    "model": {"family": "dense", "n_layers": 2, "d_model": 48,
              "n_heads": 4, "n_kv_heads": 2, "head_dim": 12, "d_ff": 96,
              "vocab": 128, "rope_theta": 1e6, "swa_window": None,
              "norm": "rmsnorm", "act": "silu", "tie_embeddings": True,
              "param_dtype": "float32", "compute_dtype": "float32"},
    "reduced": {}}
DENSE_TRAFFIC = {"name": "dense_smoke", "loop": "closed", "batch": 4,
                 "prompt_len": 16, "gen_len": 6,
                 "prompt_tokens": {"dist": "zipf", "s": 1.0},
                 "plan_stream": None}
DENSE_CELL = {"name": "granite2b.smoke", "config": "granite-3-2b-smoke",
              "traffic": "dense_smoke", "chips": 1, "mesh": None,
              "a2a_impl": None, "plan_cluster": None,
              "correct": {"sample_batches": 2, "limits": {
                  "logit_gap_max": 1e-3, "off_best_share": 0.0}}}

SCRIPT = r"""
import json, sys
import jax
from bench import cells, harness
from bench.reference.check import CONTROL
from bench.tests import smoke

harness.RequestPool.run_window = smoke.run_batches(3)
cell = cells.load_cell("granite2b.smoke")
out = harness.run(cell, int(sys.argv[1]), 1.5, False, jax.devices(),
                  lows=(CONTROL,))
print(json.dumps({"line": out["line"], "info": out["info"]}))
"""


def test_a_second_architecture_is_added_by_new_files_alone(tmp_path):
    shutil.copytree(cells.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = sorted(p.relative_to(tmp_path)
                    for p in (tmp_path / "bench").rglob("*") if p.is_file())
    new = {"bench/arch/dense_gqa.py": open(
               os.path.join(DATA, "dense_gqa.py")).read(),
           "bench/configs/granite-3-2b-smoke.json": json.dumps(DENSE_CONFIG),
           "bench/traffic/dense_smoke.json": json.dumps(DENSE_TRAFFIC),
           "bench/workloads/granite2b.smoke.json": json.dumps(DENSE_CELL)}
    for rel, text in new.items():
        assert not (tmp_path / rel).exists()
        (tmp_path / rel).write_text(text)
    bench = cells.load_benchmark()
    bench["configs"].append({"name": "granite-3-2b-smoke",
                             "source": DENSE_CONFIG["source"],
                             "file": "bench/configs/granite-3-2b-smoke.json",
                             "reduced": [], "why": "a test fixture"})
    bench["workloads"].append({k: DENSE_CELL[k] for k in (
        "name", "config", "traffic", "chips")} | {"why": "a test fixture"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    after = sorted(p.relative_to(tmp_path)
                   for p in (tmp_path / "bench").rglob("*") if p.is_file())
    assert sorted(set(after) - set(before)) == sorted(
        type(after[0])(p) for p in new)

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(tmp_path), os.path.join(cells.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(2 ** 35 + 9)],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    line, info = out["line"], out["info"]
    assert line["correct"], line["checks"]
    assert info["compared_tokens"] == 2 * 4 * 6       # 2 batches, 6 tokens
    assert line["metrics"]["tokens_per_s"]["value"] > 0
    assert not info["control_correct"], info["control_checks"]


def test_layers_of_two_stacks_regenerate_exactly():
    """A dense stack and an expert stack whose layers hold a router wider
    than the experts held and a shared expert stacked by layer only."""
    from bench.weights import Leaf, stack

    d, f = 16, 8
    ffn = {"w_up": Leaf((d, f), "float32"), "w_down": Leaf((f, d), "float32")}
    tree = {"embed": Leaf((32, d), "bfloat16", init="embed"),
            "dense": stack({"norm": {"scale": Leaf((d,), "float32",
                                                   init="ones")},
                            "mlp": ffn}, 1),
            "moe": stack({"router": Leaf((d, 64), "float32"),
                          "shared": ffn, **stack(ffn, 4)}, 3)}
    seed = 2 ** 40 + 1
    full = weights.make_params(tree, seed)
    assert full["moe"]["w_up"].shape == (3, 4, d, f)
    assert full["moe"]["shared"]["w_up"].shape == (3, d, f)
    for name, n in (("dense", 1), ("moe", 3)):
        maker = weights.LayerMaker(tree, name)
        for layer in range(n):
            one = maker(seed, layer)
            for a, b in zip(jax.tree.leaves(full[name]),
                            jax.tree.leaves(one)):
                assert np.array_equal(a[layer], b)
    assert set(weights.make_globals(tree, ["dense", "moe"], seed)) \
        == {"embed"}
    # the shared expert and each routed expert draw apart
    assert not np.array_equal(full["moe"]["shared"]["w_up"][0],
                              full["moe"]["w_up"][0, 0])
    assert not np.array_equal(full["moe"]["w_up"][0, 0],
                              full["moe"]["w_up"][0, 1])
