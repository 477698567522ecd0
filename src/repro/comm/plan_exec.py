"""Plan-driven device All-to-All: lower a synthesized Plan into shard_map.

This is the bridge between the two halves of the reproduction: the
host-side scheduler (``repro.core``: FLASH synthesis -> typed ``Plan`` ->
``ExecutableSchedule``) and the jit-integrated comm layer
(``comm.all_to_all``).  ``lower_plan`` turns a plan's Birkhoff permutation
stages into a static ``DeviceSchedule``; ``plan_all_to_all`` executes that
schedule inside ``shard_map`` and is registered as ``impl="plan"`` in the
one A2A registry, so ``resolve_all_to_all`` / ``models/moe.py`` /
``launch/serve.py`` pick it up with zero call-site changes.

Static-pattern constraint (why lowering exists at all): XLA compiles a
*static* communication pattern, so the dynamic plan cannot be interpreted
on device.  Instead the stage permutations are baked as Python constants
into the traced program -- one ``lax.ppermute`` over the slow axis per
lowered stage -- and the lowering is memoized on the ``Plan`` object per
pod count, exactly like ``Plan.compile`` memoizes per execution-topology
fingerprint.  A serving loop that hands out cached plans therefore hands
out their lowered schedules for free: a drifted MoE matrix re-lowers only
on a cache miss (see ``serving.client.PlanClient.get_device_schedule``).

Exactness: the device exchange moves the *capacity-padded* MoE buffer --
every (src pod, dst pod) pair owes exactly one equal-size block, so a
correct program delivers each ordered pair exactly once.  A plan's stages
schedule pairs in proportion to *bytes* (a pair can appear in many
capacity-aware stages, a zero-traffic pair in none), so the lowering takes
each pair's **first** occurrence as its transfer stage and then appends
rotation stages covering any pairs the plan never named (zero-traffic
pairs still carry their padding block).  The result is bit-identical to
``direct_all_to_all`` on every routed-token exchange while moving bulk
traffic in the plan's stage order -- the property the subprocess golden
tests in tests/test_comm.py pin down.

Phase mapping (mirrors ``flash_all_to_all``, which lowers the *uniform*
special case of the same schedule):

  load balance  -> the per-stage send blocks are packed destination-
                   contiguously (``kernels/a2a_pack``) and rail-aligned by
                   ONE intra-pod all_to_all over the fast axes -- the
                   plan's LoadBalancePhase, with the targets carried by
                   the packed stage order;
  merged xfer   -> one ``lax.ppermute`` over the slow axis per lowered
                   stage, each shipping a stage-sized contiguous buffer;
  redistribute  -> a no-op in the aligned layout; the received stage
                   buffers are scattered back to source-shard slots on
                   device (``a2a_unpack``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, PartitionSpec as P

from .. import scopes
from .all_to_all import _as_tuple, axis_sizes, register_all_to_all_impl

__all__ = ["DeviceSchedule", "lower_plan", "is_lowered", "slot_indices",
           "plan_all_to_all"]

_MEMO_ATTR = "_device_sched"
_MEMO_CAP = 8  # serving loops see 1-2 pod counts per plan (Plan.compile's cap)


@dataclasses.dataclass(frozen=True)
class DeviceSchedule:
    """A plan lowered to static ppermute stages over ``n_pods`` pods.

    ``pairs[k]`` is stage ``k``'s ppermute permutation -- the live
    ``(src, dst)`` pod pairs, incast-free (a partial permutation; pods can
    idle).  ``dst_of[k][q]`` / ``src_of[k][q]`` are pod ``q``'s send
    target / receive source in stage ``k`` (-1 = idle), the tables the
    SPMD program gathers its own role from at trace time.  Stages
    ``< n_plan_stages`` came from the plan (first occurrence of each
    pair, plan order); the remaining ``n_fallback_stages`` are the
    coverage-completing rotations for pairs the plan never scheduled.
    """

    n_pods: int
    pairs: Tuple[Tuple[Tuple[int, int], ...], ...]
    dst_of: Tuple[Tuple[int, ...], ...]
    src_of: Tuple[Tuple[int, ...], ...]
    n_plan_stages: int
    n_fallback_stages: int
    plan_fingerprint: Optional[str]
    algorithm: str

    @property
    def n_stages(self) -> int:
        return len(self.pairs)


def _iter_perm_stages(plan):
    """Every inter-server permutation of ``plan`` in execution order.

    Delegates to ``Plan.iter_perm_stages`` (the core-side device-lowering
    view); the structural fallback keeps duck-typed plan stand-ins from
    tests working.
    """
    view = getattr(plan, "iter_perm_stages", None)
    if view is not None:
        yield from view()
        return
    from ..core.plan import PermutationBlock, PermutationStage

    for phase in plan.phases:
        if isinstance(phase, PermutationStage):
            yield phase.perm
        elif isinstance(phase, PermutationBlock):
            for row in phase.perms:
                yield tuple(int(j) for j in row)


def _as_plan(plan_or_schedule):
    """Accept a Plan or anything carrying one (ExecutableSchedule)."""
    inner = getattr(plan_or_schedule, "plan", None)
    return plan_or_schedule if inner is None else inner


def _stage_tables(n: int, stage_pairs):
    dst = [-1] * n
    src = [-1] * n
    for s, d in stage_pairs:
        dst[s] = d
        src[d] = s
    return tuple(dst), tuple(src)


def lower_plan(plan_or_schedule, n_pods: Optional[int] = None
               ) -> DeviceSchedule:
    """Lower a ``Plan`` / ``ExecutableSchedule`` to a ``DeviceSchedule``.

    Pure function of (plan stages, n_pods) -- deterministic per plan
    fingerprint -- and memoized on the plan object keyed by ``n_pods``,
    alongside the ``Plan.compile`` slot, so a ``PlanCache`` hit (or a
    daemon answer) carries the lowering with it.
    """
    plan = _as_plan(plan_or_schedule)
    n = int(plan.cluster.n_servers)
    p = n if n_pods is None else int(n_pods)
    if p != n:
        raise ValueError(
            f"mesh slow axis has {p} pods but the plan was synthesized "
            f"for {n} servers; re-plan on a matching ClusterSpec")
    memo = plan.__dict__.get(_MEMO_ATTR)
    if memo is None:
        memo = {}
        object.__setattr__(plan, _MEMO_ATTR, memo)
    sched = memo.get(p)
    if sched is not None:
        return sched

    delivered = set()
    stages = []
    for perm in _iter_perm_stages(plan):
        fresh = []
        for s, d in enumerate(perm[:p]):
            d = int(d)
            if d < 0 or d == s or (s, d) in delivered:
                continue  # idle slot / self traffic / already shipped
            delivered.add((s, d))
            fresh.append((s, d))
        if fresh:
            stages.append(tuple(fresh))
    n_plan_stages = len(stages)
    # Coverage completion: pairs the plan never scheduled (zero traffic in
    # the matrix) still owe their capacity-padding block.  Each shift's
    # residue is itself a partial permutation, so incast-freedom holds.
    for shift in range(1, p):
        missing = tuple((q, (q + shift) % p) for q in range(p)
                        if (q, (q + shift) % p) not in delivered)
        if missing:
            stages.append(missing)
    sched = DeviceSchedule(
        n_pods=p,
        pairs=tuple(stages),
        dst_of=tuple(_stage_tables(p, st)[0] for st in stages),
        src_of=tuple(_stage_tables(p, st)[1] for st in stages),
        n_plan_stages=n_plan_stages,
        n_fallback_stages=len(stages) - n_plan_stages,
        plan_fingerprint=plan.fingerprint,
        algorithm=plan.algorithm,
    )
    if len(memo) >= _MEMO_CAP:
        memo.clear()
    memo[p] = sched
    return sched


def is_lowered(plan_or_schedule, n_pods: Optional[int] = None) -> bool:
    """True when ``lower_plan`` for this pod count would be a memo hit."""
    plan = _as_plan(plan_or_schedule)
    p = int(plan.cluster.n_servers) if n_pods is None else int(n_pods)
    return p in plan.__dict__.get(_MEMO_ATTR, {})


def slot_indices(sched: DeviceSchedule, my_pod
                 ) -> Tuple[jax.Array, jax.Array]:
    """Pod ``my_pod``'s pack / unpack block indices, each int32 ``[S+1]``.

    ``dst_idx`` is the pack gather order: slot 0 is the pod's own
    (intra-pod) block, slot ``k+1`` the block it ships in stage ``k``; an
    idle stage packs the local block again, which is never shipped (the
    pod is absent from that stage's ppermute pairs).  ``src_idx`` is where
    unpack scatters each received slot: the source pod's output block, or
    the trash block ``P`` for a stage that delivers nothing here.
    ``my_pod`` may be traced (``lax.axis_index``) or a plain int.
    """
    pod = jnp.asarray(my_pod, jnp.int32)

    def column(table, idle):
        if not sched.n_stages:
            return pod[None]
        col = jnp.take(jnp.asarray(table, jnp.int32), pod, axis=1)
        return jnp.concatenate([pod[None], jnp.where(col < 0, idle, col)])

    return (column(sched.dst_of, pod),
            column(sched.src_of, jnp.int32(sched.n_pods)))


def _default_interpret() -> bool:
    """Native Pallas on TPU, the Pallas interpreter on CPU, nothing else.

    Any other backend is an error rather than a quiet switch to interpret
    mode: a TPU run that lost its chip must fail, not keep going slowly.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"plan_all_to_all's Pallas kernels run natively on TPU or "
        f"interpreted on CPU, not on backend {backend!r}; pass "
        f"interpret= explicitly or use_kernel=False")


def _manual_over_auto_axes(kernel):
    """``kernel`` made manual over the mesh axes still automatic here.

    A Mosaic kernel cannot be partitioned automatically, and the MoE island
    is a partial-manual shard_map (``model`` stays automatic for the expert
    FFN).  So the kernel gets a shard_map of its own over those axes, in
    which every device runs it on the whole, replicated operands.
    """
    mesh = jax.sharding.get_abstract_mesh()
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual}
    if not auto:
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=auto, check_vma=False)


@register_all_to_all_impl("plan")
def plan_all_to_all(x: jax.Array, slow_axis: str, fast_axes,
                    *, plan=None, schedule=None, use_kernel: bool = True,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Execute a lowered plan as the two-tier All-to-All schedule.

    Same contract as every registry impl -- ``x`` is ``[n_shards, ...]``
    slow-major, the result row ``s`` is the chunk combined shard ``s``
    sent here, bit-identical to ``direct_all_to_all`` -- but the DCN
    stage order comes from the synthesized plan instead of the fixed
    rotations.  ``plan`` (or ``schedule``) must be supplied;
    ``resolve_all_to_all(..., plan=...)`` closes over it.

    ``use_kernel`` routes the on-device slot packing/unpacking through the
    ``kernels/a2a_pack`` Pallas pair (scalar-prefetch DMA gather/scatter);
    False falls back to jnp gather/scatter (identical bits, no Pallas --
    the stable denominator for CPU wall-clock benchmarks).
    """
    src = schedule if schedule is not None else plan
    if src is None:
        raise ValueError(
            'impl="plan" needs a synthesized plan: pass plan=/schedule= '
            "through resolve_all_to_all (or DistContext.plan)")
    fast = _as_tuple(fast_axes) if fast_axes else ()
    p = lax.axis_size(slow_axis)
    i = axis_sizes(fast) if fast else 1
    n, rest = x.shape[0], x.shape[1:]
    if n != p * i:
        raise ValueError(f"leading dim {n} != slow*fast = {p}*{i}")
    sched = lower_plan(src, n_pods=p)
    if interpret is None:
        interpret = _default_interpret()
    my_pod = lax.axis_index(slow_axis)

    # 2D row view for the pack/unpack kernels: pod q's block is the
    # contiguous run of rows [q*B, (q+1)*B).
    inner = 1
    for dim in rest[:-1]:
        inner *= dim
    d = rest[-1] if rest else 1
    block = i * inner                     # rows per pod block
    x2 = x.reshape(p * block, d)
    s = sched.n_stages

    # Slot packing: bundle this device's send block for every stage into
    # one destination-contiguous buffer.
    dst_idx, src_idx = slot_indices(sched, my_pod)
    with jax.named_scope(scopes.EXCHANGE_PACK):
        if use_kernel:
            from ..kernels.a2a_pack.a2a_pack import a2a_pack, a2a_unpack

            send = _manual_over_auto_axes(partial(
                a2a_pack, block_rows=block, interpret=interpret))(x2,
                                                                  dst_idx)
        else:
            send = jnp.take(x2.reshape(p, block, d), dst_idx,
                            axis=0).reshape(-1, d)
        buf = send.reshape(s + 1, i, *rest) if rest else \
            send.reshape(s + 1, i)

    with jax.named_scope(scopes.EXCHANGE_STAGE):
        # Load balance: ONE intra-pod all_to_all rail-aligns every stage
        # block (the plan's LoadBalancePhase; redistribute is a no-op).
        if fast:
            buf = lax.all_to_all(buf, fast, split_axis=1, concat_axis=1,
                                 tiled=True)

        # Merged transfers: one ppermute per lowered stage, stage-sized
        # contiguous buffers, static (src, dst) pairs baked from the plan.
        recv = [buf[0]]
        for k in range(s):
            recv.append(lax.ppermute(buf[k + 1], slow_axis,
                                     list(sched.pairs[k])))
        stack = jnp.stack(recv)                          # (S+1, i, *rest)

    # Slot unpacking: scatter each received stage block to its source
    # pod's output slot; non-receiving stages land in a trash block that
    # the final slice drops.  Coverage completion guarantees every real
    # output block is written exactly once.
    with jax.named_scope(scopes.EXCHANGE_UNPACK):
        stack2 = stack.reshape((s + 1) * block, d)
        if use_kernel:
            out2 = _manual_over_auto_axes(partial(
                a2a_unpack, n_out_blocks=p + 1, block_rows=block,
                interpret=interpret))(stack2, src_idx)
        else:
            out2 = jnp.zeros(((p + 1) * block, d), x.dtype)
            out2 = out2.reshape(p + 1, block, d).at[src_idx].set(
                stack2.reshape(s + 1, block, d)).reshape(-1, d)
        return out2[: p * block].reshape(n, *rest)
