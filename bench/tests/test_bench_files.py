"""The benchmark's data files, and the harness that finds them by name."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import cells, peaks, weights

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def test_benchmark_json_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    shapes = {"configs": {"name", "source", "file", "reduced", "why"},
              "workloads": {"name", "config", "traffic", "chips", "why"}}
    for kind, keys in shapes.items():
        for entry in bench[kind]:
            assert set(entry) == keys, entry
            assert NAME.match(entry["name"])
    for entry in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cells.load_benchmark()["workloads"]])
def test_every_cell_loads_and_names_existing_entries(cell, bench):
    c = cells.load_cell(cell, bench)
    conf = {x["name"]: x for x in bench["configs"]}[c.config["name"]]
    assert conf["file"] == f"bench/configs/{c.config['name']}.json"
    assert sorted(conf["reduced"]) == sorted(c.config["reduced"])
    assert c.traffic["name"] == \
        {w["name"]: w for w in bench["workloads"]}[cell]["traffic"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert os.path.exists(cells.reader_path(
            m["name"], os.path.join(cells.BENCH_DIR, "metrics")))
    for m in c.end_to_end:
        assert os.path.exists(cells.reader_path(
            m["name"], os.path.join(cells.BENCH_DIR, "end_to_end")))
    limits = c.correct["limits"]
    assert "off_best_share" in limits
    assert all(v is not None for v in limits.values())


def test_every_config_is_used_and_matches_the_program(bench):
    from bench.harness import model_config

    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], bench)
        model_config(cell)   # raises on any size that differs


@pytest.mark.parametrize("name", ["megatron-moe-32e-2l", "mixtral-8x7b-2l",
                                  "megatron-moe-32e-12l-ep4"])
def test_weight_layout_matches_the_programs_init(name):
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    conf = cells.load_config(name)
    m = conf["model"]
    cfg = get_config(conf["registry"], n_layers=m["n_layers"],
                     param_dtype=m["param_dtype"])
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert weights.check_layout(shapes, m) is None


def test_a_reader_dropped_into_metrics_is_found(tmp_path):
    metrics = tmp_path / "metrics"
    shutil.copytree(os.path.join(cells.BENCH_DIR, "metrics"), metrics)
    (metrics / "new_layer.dropped_in.py").write_text(
        "def read(run):\n    return run.window_s * 2\n")
    entries = [{"name": "new_layer.dropped_in", "unit": "s"},
               {"name": "lowering.compiles_in_window", "unit": "count"}]

    class Run:
        window_s = 1.5
        programs_in_window = 0

    got = cells.read_metrics(entries, Run(), str(metrics))
    assert got == {"new_layer.dropped_in": {"value": 3.0, "unit": "s"},
                   "lowering.compiles_in_window": {"value": 0.0,
                                                   "unit": "count"}}


def test_metric_entries_follow_benchmark_json(bench):
    e2e, layer = cells.metrics_for(bench, "megatron32e.prefill.1c")
    assert {m["name"] for m in e2e} == {"tokens_per_s", "ttft_ms_p95",
                                        "setup_s"}
    assert "plan.hit_share" not in {m["name"] for m in layer}
    e2e, layer = cells.metrics_for(bench, "mixtral8x7b.decode_plan.1c")
    assert {m["name"] for m in e2e} == {"tokens_per_s", "tpot_ms_p95",
                                        "setup_s"}
    assert {"plan.hit_share", "plan.latency_ms_p95"} <= {
        m["name"] for m in layer}


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "megatron32e.prefill.1c", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.strip().startswith("{")]
    assert "needs a TPU" in proc.stderr


def test_run_in_a_tree_without_the_program_fails(tmp_path):
    shutil.copytree(cells.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(cells.BENCHMARK_FILE, tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "megatron32e.prefill.1c", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_unknown_device_kind_raises(tmp_path):
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "x", "devices": {
        "TPU v5 lite": {"bf16_flops_per_s": 1.0}}}))
    assert peaks.peaks_for("TPU v5 lite", str(table))["bf16_flops_per_s"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary", str(table))
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_one_layer_regenerates_exactly():
    import jax.numpy as jnp
    import jax

    dims = dict(cells.load_config("megatron-moe-32e-2l")["model"],
                d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=16,
                vocab=64, num_experts=4, n_layers=3)
    seed = 2 ** 33 + 5
    full = weights.make_params(dims, seed)
    maker = weights.LayerMaker(dims)
    for layer in range(3):
        one = maker(seed, layer)
        for a, b in zip(jax.tree.leaves(full["blocks"]),
                        jax.tree.leaves(one)):
            assert jnp.array_equal(a[layer], b)
    other = weights.make_params(dims, seed + 1)
    assert not jnp.array_equal(full["embed"], other["embed"])


def test_traffic_copies_match_their_originals():
    from benchmarks.fig_dynamic import _drift_trajectory
    from repro.core.traffic import ClusterSpec, moe_workload

    from bench.traffic.moe_traffic import DriftStream, moe_matrix

    cluster = ClusterSpec(4, 2)
    assert np.array_equal(moe_matrix(8, 512, 64, seed=9),
                          moe_workload(cluster, 512, 64, seed=9).matrix)
    stream = DriftStream(8, 4096, 2048, seed=4)
    for w in _drift_trajectory(cluster, 12, seed=4):
        assert np.array_equal(w.matrix, stream.next())


def test_zipf_prompts_are_seeded_and_skewed():
    from bench.seeds import seed_seq
    from bench.traffic.prompts import ZipfPrompts

    a = ZipfPrompts(1000, 1.0, seed_seq(2 ** 40, "p")).batch(4, 512)
    b = ZipfPrompts(1000, 1.0, seed_seq(2 ** 40, "p")).batch(4, 512)
    c = ZipfPrompts(1000, 1.0, seed_seq(2 ** 40 + 1, "p")).batch(4, 512)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1000
    assert (a == 0).mean() > 5 * (a == 9).mean()
