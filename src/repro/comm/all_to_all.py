"""jit-integrated All-to-All collectives: FLASH two-tier schedule on TPU.

All functions here are meant to be called *inside* ``shard_map`` over a mesh
whose axes include one *slow* axis (inter-pod DCN, the paper's inter-server
network) and one or more *fast* axes (intra-pod ICI, the paper's NVLink/xGMI).

Semantics contract: every variant computes exactly

    out[src_shard] = chunk that shard ``src_shard`` addressed to this device

for ``x`` of shape ``[n_shards, ...]`` with the combined shard index ordered
slow-axis-major -- i.e. all variants are bit-identical to
``direct_all_to_all`` and interchangeable under a config flag.

TPU adaptation of the paper (see DESIGN.md section 3): XLA compiles a static
communication pattern, so the jit-integrated FLASH schedule is the
Birkhoff decomposition of the *balanced* post-load-balance matrix -- the
P-1 cyclic rotations sigma_k(p) = (p+k) mod P, each lowered to one
``collective_permute`` over the slow axis (a permutation collective is
incast-free by construction; equal static chunk sizes make it
straggler-free).  The three paper phases map to:

  load balance  -> intra-pod ``all_to_all`` aligning each chunk's carrier
                   with its final destination index ("rail" alignment)
  merged xfer   -> one ``ppermute`` per rotation over the slow axis; the
                   per-(pod pair) buffer is a single contiguous block
  redistribute  -> becomes a no-op in the aligned layout (the intra A2A ran
                   *before* the DCN hop); the MSCCL-style baseline
                   ``hierarchical_all_to_all`` runs it *after* instead

The genuinely dynamic-traffic form of FLASH (arbitrary skewed matrices, true
Hopcroft-Karp BvN) lives in ``repro.core`` and drives the host-side runtime
and the benchmarks.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "direct_all_to_all",
    "flash_all_to_all",
    "hierarchical_all_to_all",
    "ALL_TO_ALL_IMPLS",
    "register_all_to_all_impl",
    "available_all_to_all_impls",
    "resolve_all_to_all",
    "axis_sizes",
]

AxisNames = Union[str, Tuple[str, ...]]

# name -> fn(x, slow_axis, fast_axes); the single registry through which
# model code, launch/ and benchmarks select jit-integrated A2A schedules.
ALL_TO_ALL_IMPLS: dict = {}


def register_all_to_all_impl(name: str):
    """Decorator: register a two-tier all_to_all implementation."""

    def deco(fn):
        ALL_TO_ALL_IMPLS[name] = fn
        return fn

    return deco


def available_all_to_all_impls() -> list:
    _ensure_extra_impls()
    return sorted(ALL_TO_ALL_IMPLS)


def _as_tuple(axes: AxisNames) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def axis_sizes(axes: AxisNames) -> int:
    """Product of mesh-axis sizes (valid inside shard_map)."""
    total = 1
    for a in _as_tuple(axes):
        total *= lax.axis_size(a)
    return total


@register_all_to_all_impl("direct")
def direct_all_to_all(x: jax.Array, slow_axis: str,
                      fast_axes: AxisNames) -> jax.Array:
    """Single flat all_to_all over the combined (slow, fast...) axis.

    This is the RCCL/NCCL-default analogue: one collective, every pair of
    shards exchanging its chunk point-to-point, with cross-pod chunks riding
    DCN as many small flows.  Combined shard index is slow-major, matching
    mesh axis order ("pod", "data", ...).
    """
    axes = (slow_axis, *(_as_tuple(fast_axes)))
    return lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)


def intra_all_to_all(x: jax.Array, fast_axes: AxisNames) -> jax.Array:
    """all_to_all restricted to the fast (intra-pod) axes."""
    return lax.all_to_all(
        x, _as_tuple(fast_axes), split_axis=0, concat_axis=0, tiled=True)


@register_all_to_all_impl("flash")
def flash_all_to_all(x: jax.Array, slow_axis: str,
                     fast_axes: AxisNames) -> jax.Array:
    """FLASH two-tier All-to-All: balance over ICI first, then one
    contiguous peer-to-peer DCN transfer per Birkhoff rotation.

    Args:
      x: [n_shards, ...] where n_shards = size(slow) * size(fast); row
        ``d`` is the chunk this device sends to combined shard ``d``
        (slow-major order).
      slow_axis: the inter-pod mesh axis name.
      fast_axes: intra-pod mesh axis name(s).

    Returns:
      [n_shards, ...]: row ``s`` is the chunk combined shard ``s`` sent here.
    """
    fast = _as_tuple(fast_axes)
    p = lax.axis_size(slow_axis)
    i = axis_sizes(fast)
    n, rest = x.shape[0], x.shape[1:]
    if n != p * i:
        raise ValueError(f"leading dim {n} != slow*fast = {p}*{i}")
    my_pod = lax.axis_index(slow_axis)

    x4 = x.reshape(p, i, *rest)  # [dst_pod, dst_fast, ...]
    out = jnp.zeros_like(x4)
    for shift in range(p):
        dst_pod = lax.rem(my_pod + shift, p)
        # Chunk of everything this device owes pod ``dst_pod``:
        blk = lax.dynamic_index_in_dim(x4, dst_pod, axis=0, keepdims=False)
        # Phase 1 -- load balance / rail alignment (intra-pod all_to_all):
        # after this, local device ``i`` carries the block destined to
        # *fast index i* of the destination pod, gathered from all local
        # sources: blk_aligned[k] = chunk (local src k -> dst (dst_pod, i)).
        blk_aligned = intra_all_to_all(blk, fast)
        if shift == 0:
            recv = blk_aligned  # purely intra-pod: overlapped with stage 1
            src_pod = my_pod
        else:
            # Phase 2 -- merged transfer: one contiguous buffer to the rail
            # peer (same fast index) in the destination pod.  Rotation
            # ``shift`` is one stage of the balanced Birkhoff schedule.
            perm = [(q, (q + shift) % p) for q in range(p)]
            recv = lax.ppermute(blk_aligned, slow_axis, perm)
            src_pod = lax.rem(my_pod - shift + p, p)
        # Phase 3 -- redistribute: no-op (alignment happened pre-DCN).
        out = lax.dynamic_update_index_in_dim(out, recv, src_pod, axis=0)
    return out.reshape(n, *rest)


@register_all_to_all_impl("hierarchical")
def hierarchical_all_to_all(x: jax.Array, slow_axis: str,
                            fast_axes: AxisNames) -> jax.Array:
    """MSCCL-style baseline: DCN transfer first, intra redistribute after.

    Same rotations over the slow axis, but each device ships its *own,
    unbalanced* per-destination block across DCN and the receiving pod then
    redistributes over ICI (gather-then-send of the paper's section 6.1
    MSCCL description, phases reversed relative to FLASH).  Byte counts on
    each tier match FLASH; only the phase order (and hence what can be
    overlapped / pooled) differs.
    """
    fast = _as_tuple(fast_axes)
    p = lax.axis_size(slow_axis)
    i = axis_sizes(fast)
    n, rest = x.shape[0], x.shape[1:]
    if n != p * i:
        raise ValueError(f"leading dim {n} != slow*fast = {p}*{i}")
    my_pod = lax.axis_index(slow_axis)

    x4 = x.reshape(p, i, *rest)
    out = jnp.zeros_like(x4)
    for shift in range(p):
        dst_pod = lax.rem(my_pod + shift, p)
        blk = lax.dynamic_index_in_dim(x4, dst_pod, axis=0, keepdims=False)
        if shift == 0:
            recv = blk
            src_pod = my_pod
        else:
            perm = [(q, (q + shift) % p) for q in range(p)]
            recv = lax.ppermute(blk, slow_axis, perm)
            src_pod = lax.rem(my_pod - shift + p, p)
        # Redistribute *after* the DCN hop (the un-balanced order).
        recv = intra_all_to_all(recv, fast)
        out = lax.dynamic_update_index_in_dim(out, recv, src_pod, axis=0)
    return out.reshape(n, *rest)


def fast_only_all_to_all(x: jax.Array, slow_axis: str,
                         fast_axes: AxisNames) -> jax.Array:
    """Degenerate case: EP axis entirely inside one pod (no slow traffic)."""
    del slow_axis
    return intra_all_to_all(x, _as_tuple(fast_axes))


def rotation_all_to_all(x: jax.Array, axis: str) -> jax.Array:
    """All-to-all over one axis as P-1 ppermute rotations.

    Semantically identical to ``lax.all_to_all(x, axis, 0, 0, tiled=True)``
    (rows = per-destination chunks) but lowered as the balanced Birkhoff
    rotation schedule -- one permutation collective per stage.  This is the
    FLASH-native form for a slow-axis-only exchange (mixtral: EP over
    ``pod``), and also works around an XLA SPMD crash ("Invalid binary
    instruction opcode copy") when all_to_all targets a single manual axis
    inside a partial-manual shard_map.
    """
    p = lax.axis_size(axis)
    my = lax.axis_index(axis)
    n, rest = x.shape[0], x.shape[1:]
    if n != p:
        raise ValueError(f"leading dim {n} != axis size {p}")
    out = jnp.zeros_like(x)
    for shift in range(p):
        dst = lax.rem(my + shift, p)
        blk = lax.dynamic_index_in_dim(x, dst, axis=0, keepdims=False)
        if shift == 0:
            recv, src = blk, my
        else:
            perm = [(q, (q + shift) % p) for q in range(p)]
            recv = lax.ppermute(blk, axis, perm)
            src = lax.rem(my - shift + p, p)
        out = lax.dynamic_update_index_in_dim(out, recv, src, axis=0)
    return out


def _ensure_extra_impls() -> None:
    """Import-on-demand registrations (plan_exec imports this module, so
    it cannot be imported at module scope without a cycle)."""
    if "plan" not in ALL_TO_ALL_IMPLS:
        from . import plan_exec  # noqa: F401  (registers impl="plan")


def all_to_all_by_name(name: str):
    _ensure_extra_impls()
    try:
        return ALL_TO_ALL_IMPLS[name]
    except KeyError:
        raise ValueError(
            f"unknown all_to_all impl {name!r}; pick from "
            f"{sorted(ALL_TO_ALL_IMPLS)}")


def resolve_all_to_all(
    dist=None,
    *,
    slow_axis: Optional[str] = None,
    ep_axes: Optional[Sequence[str]] = None,
    impl: str = "flash",
    topology=None,
    plan=None,
) -> Optional[Callable[[jax.Array], jax.Array]]:
    """Select the jit-integrated A2A schedule for an EP-axis layout.

    The single dispatch point for model code, ``launch/`` and benchmarks
    (previously hand-rolled inside ``models/moe.py``).  Pass either a
    ``DistContext``-like object (attributes ``slow_axis``, ``ep_axes``,
    ``a2a_impl``, optionally ``plan``) or the raw keyword form.

    Selection:
      * EP spans the slow axis plus fast axes -> the registered two-tier
        impl ``impl`` (flash | direct | hierarchical | plan | ...).
      * EP is exactly the slow axis -> the FLASH rotation schedule (every
        DCN link carries one contiguous chunk per stage, incast-free by
        construction), or the plan-driven stage schedule when
        ``impl="plan"``.
      * EP is fast-only -> a plain intra all_to_all over ICI.
      * No EP axes -> None (no exchange needed).

    ``impl="auto"`` resolves from what the caller knows: with a
    synthesized ``plan`` (or ``ExecutableSchedule``) supplied, auto picks
    ``"plan"`` -- the schedule already encodes the traffic *and* the
    fabric.  Otherwise it resolves from the fabric alone: on a
    heterogeneous or oversubscribed ``Topology`` (core/topology.py) the
    FLASH schedule's load-balance phase aligns per-rail shares with real
    link capacities, so auto picks ``flash``; on a homogeneous
    full-bisection fabric (or with no topology information) auto picks
    ``direct`` -- one fused collective, no balancing needed when every
    link is equal.

    ``impl="plan"`` (explicit or via auto) closes the returned callable
    over ``plan``; the per-fingerprint lowering happens in
    ``comm.plan_exec`` at trace time.

    Returns a unary ``buf -> buf`` callable, or None.
    """
    if dist is not None:
        slow_axis = dist.slow_axis
        ep_axes = dist.ep_axes
        impl = dist.a2a_impl
        topology = getattr(dist, "topology", topology)
        plan = getattr(dist, "plan", plan)
    if impl == "auto":
        if plan is not None:
            impl = "plan"
        else:
            hetero = topology is not None and not topology.is_homogeneous
            impl = "flash" if hetero else "direct"
    # Fail fast on unknown impl names on every path, including the
    # rotation/ICI-only ones that do not dispatch through the registry.
    two_tier = all_to_all_by_name(impl)
    if impl == "plan":
        if plan is None:
            raise ValueError(
                'impl="plan" needs a synthesized plan/schedule: pass '
                "plan= (or set DistContext.plan)")
        two_tier = partial(two_tier, plan=plan)
    ep = tuple(ep_axes or ())
    if not ep:
        return None
    if slow_axis in ep and len(ep) > 1:
        fast = tuple(a for a in ep if a != slow_axis)
        return partial(two_tier, slow_axis=slow_axis, fast_axes=fast)
    if ep == (slow_axis,):
        if impl == "plan":
            # slow-axis-only EP still follows the plan's stage order.
            return partial(two_tier, slow_axis=slow_axis, fast_axes=())
        return partial(rotation_all_to_all, axis=slow_axis)
    return partial(intra_all_to_all, fast_axes=ep)
