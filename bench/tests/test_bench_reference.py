"""The plain reference against the served path, and its control, at a
size the CPU holds: prefill, decode through the KV cache, MoE with
capacity drops, and the plan daemon's answers."""

import jax
import numpy as np
import pytest

from bench import cells, harness
from bench.reference import check, model
from bench.tests import smoke

moe_gqa = cells.load_arch("moe_gqa")

# finished batches a window serves: the smoke prefill compares 4 of them,
# the smoke decode 4 rows of 1
BATCHES = {"prefill": 6, "decode": 2}


def run(cell, seed):
    return harness.run(cell, seed, 1.5, False, jax.devices(),
                       lows=(check.CONTROL,))


@pytest.mark.parametrize("kind", ["prefill", "decode"])
@pytest.mark.parametrize("seed", [21, 2 ** 31 + 22, 10 ** 12 + 23])
def test_served_path_agrees_and_the_e4m3_control_fails(kind, seed,
                                                       monkeypatch):
    monkeypatch.setattr(harness.RequestPool, "run_window",
                        smoke.run_batches(BATCHES[kind]))
    out = run(smoke.cell(kind), seed)
    checks, info = out["line"]["checks"], out["info"]
    assert out["line"]["correct"], checks
    assert info["compared_batches"] == min(BATCHES[kind],
                                           smoke.cell(kind).correct[
                                               "sample_batches"])
    assert info["ref_dropped_assignments"] > 0       # the drop path ran
    control = info["control_checks"]
    assert not info["control_correct"], control
    tokens = {k: c for k, c in control.items() if not k.startswith("plan_")}
    assert not check.passes(tokens), control         # the control fails
    if kind == "decode":
        assert info["compared_tokens"] == 4 * 12      # 4 rows x 12 tokens
        assert checks["plan_bytes_err"]["value"] <= 1e-9
        assert checks["plan_unanswered"]["value"] == 0
        assert control["plan_bytes_err"]["value"] > \
            checks["plan_bytes_err"]["limit"]


def test_capacity_keeps_first_come_per_group():
    eids = np.array([[0, 1], [0, 2], [0, 1], [1, 0], [0, 3]])
    group = np.array([0, 0, 0, 1, 1], np.int32)
    caps = np.array([2, 1], np.int32)
    keep = np.asarray(moe_gqa.keep_mask(eids, group, caps, 4))
    # group 0: expert 0 takes tokens 0 and 1, token 2's choice is dropped;
    # group 1 has its own capacity: token 3 keeps both, token 4 drops 0.
    assert keep.tolist() == [[True, True], [True, True], [False, True],
                             [True, True], [False, True]]


def test_routing_groups_follow_the_served_calls():
    group, sizes = model.routing_groups(batch=4, seq=6, prompt_len=3,
                                        n_shards=2)
    g = group.reshape(4, 6)
    assert g[:, :3].tolist() == [[0] * 3, [0] * 3, [1] * 3, [1] * 3]
    assert g[:, 3].tolist() == [2, 2, 3, 3] and g[:, 5].tolist() == \
        [6, 6, 7, 7]
    assert sizes.tolist() == [6, 6, 2, 2, 2, 2, 2, 2]
    assert moe_gqa.capacity(2.0, 8192, 2, 32) == 1152
    assert moe_gqa.capacity(2.0, 64, 2, 8) == 40
