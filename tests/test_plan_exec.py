"""Plan -> device lowering (comm.plan_exec.lower_plan): host-side checks.

The lowering is a pure host computation (tuples of ints, no shard_map), so
these run single-device; the on-device bit-identity goldens live in
tests/test_comm.py.
"""

import numpy as np
import pytest

from repro.comm.plan_exec import DeviceSchedule, is_lowered, lower_plan
from repro.core.schedulers import get_scheduler
from repro.core.topology import Topology
from repro.core.traffic import ClusterSpec, Workload, moe_workload, \
    skewed_workload


def _random_workload(n_servers, m_gpus, seed=0):
    n = n_servers * m_gpus
    rng = np.random.default_rng(seed)
    mat = rng.integers(1, 50, size=(n, n)).astype(float)
    np.fill_diagonal(mat, 0)
    return Workload(ClusterSpec(n_servers, m_gpus), mat)


def _coverage(sched: DeviceSchedule):
    pairs = [pair for stage in sched.pairs for pair in stage]
    return pairs, set(pairs)


@pytest.mark.parametrize("algo", ["flash", "fanout"])
@pytest.mark.parametrize("n_servers,m_gpus", [(2, 4), (4, 2), (4, 8)])
def test_lowering_covers_every_pair_once(algo, n_servers, m_gpus):
    """Each ordered (src, dst) pod pair appears in exactly one stage --
    the property that makes the device exchange exact on capacity-padded
    buffers -- and every stage is a partial permutation (incast-free)."""
    w = _random_workload(n_servers, m_gpus)
    sched = lower_plan(get_scheduler(algo).synthesize(w))
    pairs, distinct = _coverage(sched)
    want = {(s, d) for s in range(n_servers) for d in range(n_servers)
            if s != d}
    assert distinct == want
    assert len(pairs) == len(distinct), "a pair was scheduled twice"
    for stage in sched.pairs:
        srcs = [s for s, _ in stage]
        dsts = [d for _, d in stage]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts), "incast within a stage"


def test_stage_tables_match_pairs():
    w = moe_workload(ClusterSpec(4, 2), tokens_per_gpu=128,
                     bytes_per_token=2, seed=3)
    sched = lower_plan(get_scheduler("flash").synthesize(w))
    for k, stage in enumerate(sched.pairs):
        for s, d in stage:
            assert sched.dst_of[k][s] == d
            assert sched.src_of[k][d] == s
        live_src = {s for s, _ in stage}
        live_dst = {d for _, d in stage}
        for q in range(sched.n_pods):
            if q not in live_src:
                assert sched.dst_of[k][q] == -1
            if q not in live_dst:
                assert sched.src_of[k][q] == -1


def test_plan_stages_precede_fallback():
    """Bulk traffic moves in the plan's own stage order; only the
    zero-traffic remainder rides the appended rotations."""
    w = skewed_workload(ClusterSpec(4, 2), mean_size=1e6, seed=1)
    sched = lower_plan(get_scheduler("flash").synthesize(w))
    assert sched.n_stages == sched.n_plan_stages + sched.n_fallback_stages
    assert sched.n_plan_stages >= 1
    # flash covers the full support of a positive matrix; no fallback
    assert sched.n_fallback_stages == 0


def test_fanout_lowering_is_all_fallback():
    """FanOutBurst plans carry no static permutations -- the lowering is
    entirely the coverage-completion rotations, still exact."""
    w = _random_workload(4, 2)
    sched = lower_plan(get_scheduler("fanout").synthesize(w))
    assert sched.n_plan_stages == 0
    assert sched.n_fallback_stages == sched.n_stages == 3
    _, distinct = _coverage(sched)
    assert len(distinct) == 12


def test_memoized_per_pod_count_and_is_lowered():
    w = _random_workload(4, 2)
    plan = get_scheduler("flash").synthesize(w)
    assert not is_lowered(plan)
    s1 = lower_plan(plan)
    assert is_lowered(plan) and is_lowered(plan, n_pods=4)
    s2 = lower_plan(plan, n_pods=4)
    assert s1 is s2


def test_determinism_per_fingerprint():
    """Two independent synth runs of the same workload lower identically."""
    w = moe_workload(ClusterSpec(4, 2), tokens_per_gpu=256,
                     bytes_per_token=2, seed=9)
    a = lower_plan(get_scheduler("flash").synthesize(w))
    b = lower_plan(get_scheduler("flash").synthesize(w))
    assert a is not b
    assert a.pairs == b.pairs
    assert a.plan_fingerprint == b.plan_fingerprint


def test_pod_count_mismatch_raises():
    w = _random_workload(4, 2)
    plan = get_scheduler("flash").synthesize(w)
    with pytest.raises(ValueError, match="4 servers"):
        lower_plan(plan, n_pods=8)


def test_executable_schedule_accepted():
    """lower_plan accepts a compiled ExecutableSchedule and shares the
    memo slot with its plan (the serving handoff path)."""
    w = _random_workload(2, 4)
    plan = get_scheduler("flash").synthesize(w)
    sched = plan.compile()
    dev = lower_plan(sched)
    assert dev is lower_plan(plan)
    assert dev is sched.lower_device()
    assert dev.algorithm == "flash"


def test_capacity_aware_dedup():
    """Capacity-aware synthesis repeats pairs across stages (byte
    proportional); the lowering keeps only each pair's first occurrence."""
    topo = Topology.from_cluster(ClusterSpec(4, 2))
    topo = topo.degrade_nic(0, 0, factor=0.25)
    n = 8
    rng = np.random.default_rng(5)
    mat = rng.integers(1, 80, size=(n, n)).astype(float)
    np.fill_diagonal(mat, 0)
    w = Workload(ClusterSpec(4, 2), mat, topology=topo)
    sched = lower_plan(get_scheduler("flash_ca").synthesize(w))
    pairs, distinct = _coverage(sched)
    assert len(pairs) == len(distinct) == 12


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", None)])
def test_default_interpret_is_explicit_per_backend(monkeypatch, backend,
                                                   want):
    """Native kernels on TPU, the interpreter on CPU, and an error on any
    other backend -- never a quiet fall back to interpret mode."""
    from repro.comm import plan_exec

    monkeypatch.setattr(plan_exec.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            plan_exec._default_interpret()
    else:
        assert plan_exec._default_interpret() is want


@pytest.mark.parametrize("n_servers", [2, 4])
def test_slot_indices_match_stage_tables(n_servers):
    """Pack order = own block then each stage's target (own block when
    idle); unpack target = each stage's source (trash block P when idle)."""
    from repro.comm.plan_exec import slot_indices

    sched = lower_plan(get_scheduler("flash").synthesize(
        moe_workload(ClusterSpec(n_servers, 2), 256, 2, seed=3)))
    p = sched.n_pods
    for pod in range(p):
        dst, src = (np.asarray(a) for a in slot_indices(sched, pod))
        assert dst.tolist() == [pod] + [
            d if d >= 0 else pod for d in (row[pod] for row in sched.dst_of)]
        assert src.tolist() == [pod] + [
            s if s >= 0 else p for s in (row[pod] for row in sched.src_of)]
