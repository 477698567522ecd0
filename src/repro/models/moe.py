"""Mixture-of-Experts layer with expert-parallel dispatch over FLASH.

The MoE block is where the paper's All-to-All appears in a real model: top-k
routing produces a token->expert traffic matrix that changes every step
(paper Fig 4), and dispatch/combine are All-to-All collectives over the EP
mesh axes.  When the EP axes include the slow ``pod`` axis, dispatch crosses
DCN and the configured ``a2a_impl`` (flash | direct | hierarchical | plan)
decides the schedule -- the jit-integrated analogue of swapping RCCL's fanout for
FLASH in Megatron-LM (paper section 5).  Implementation selection happens
in ``comm.all_to_all.resolve_all_to_all`` (one registry for model code,
launch/ and benchmarks), never inline here.

Static-shape contract: capacity-factor padding (standard TPU MoE practice)
bounds every (source shard, expert) chunk at C tokens; overflow tokens are
dropped (contribute zero), underflow is zero-padded.  This padding is what
makes the *post-load-balance* traffic matrix uniform, which in turn is why
the balanced Birkhoff schedule inside ``flash_all_to_all`` is exact (see
DESIGN.md section 3).

The single-device path (``dist=None``) runs the same sort-dispatch math with
G=1 and no collectives; it is the correctness oracle for the island.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm.all_to_all import resolve_all_to_all
from .. import scopes
from ..configs.registry import ModelConfig
from .dist import DistContext
from .layers import dense_init


__all__ = ["init_moe", "moe_apply"]


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    def stack(k, din, dout):
        return jax.vmap(lambda kk: dense_init(kk, din, dout, dtype))(
            jax.random.split(k, e))
    return {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": stack(ks[1], d, f),
        "w_up": stack(ks[2], d, f),
        "w_down": stack(ks[3], f, d),
    }


def _capacity(cfg: ModelConfig, n_tokens: int, n_experts: int) -> int:
    c = int(cfg.moe.capacity_factor * n_tokens * cfg.moe.top_k
            // n_experts) + 1
    # pad to the 128-lane register tile (TPU adaptation of the paper's
    # cache-line alignment, implementation note (3) in section 5)
    return max(8, -(-c // 8) * 8) if n_tokens < 1024 else -(-c // 128) * 128


@scopes.scoped(scopes.MOE_ROUTE)
def _route(cfg: ModelConfig, router_w, x_flat):
    """Top-k routing. Returns (gates [T,k], eids [T,k], aux_loss scalar)."""
    e = cfg.moe.num_experts
    logits = (x_flat.astype(jnp.float32) @ router_w)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.moe.top_k)         # [T, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss (fraction * mean prob).
    onehot = jax.nn.one_hot(eids[:, 0], e, dtype=jnp.float32)
    frac = onehot.mean(0)
    aux = e * jnp.sum(frac * probs.mean(0))
    return gates.astype(x_flat.dtype), eids, aux


@scopes.scoped(scopes.MOE_DISPATCH)
def _dispatch(x_flat, eids, capacity: int, n_experts: int):
    """Sort-based dispatch into a [E * C, d] buffer.

    Returns (buffer, slot [T*k], keep [T*k], order [T*k]) where ``slot`` is
    each (token, choice)'s position in the buffer (only valid where keep).
    """
    t, k = eids.shape
    flat_eid = eids.reshape(-1)                                # [T*k]
    order = jnp.argsort(flat_eid, stable=True)
    sorted_eid = flat_eid[order]
    first = jnp.searchsorted(sorted_eid, sorted_eid, side="left")
    pos_in_e = jnp.arange(t * k) - first
    keep_sorted = pos_in_e < capacity
    slot_sorted = sorted_eid * capacity + pos_in_e
    tokens_sorted = x_flat[order // k]
    buf = jnp.zeros((n_experts * capacity, x_flat.shape[-1]), x_flat.dtype)
    safe_slot = jnp.where(keep_sorted, slot_sorted, n_experts * capacity)
    buf = buf.at[safe_slot].set(tokens_sorted, mode="drop")
    # map back to unsorted (token, choice) order
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    keep = jnp.zeros((t * k,), bool).at[order].set(keep_sorted)
    return buf, slot, keep


@scopes.scoped(scopes.MOE_COMBINE)
def _combine(y_buf, slot, keep, gates, t: int, k: int):
    """Gather expert outputs back to (token, choice), weight, and sum."""
    y = y_buf[slot] * keep[:, None]
    y = y.reshape(t, k, -1)
    return (y * gates[..., None]).sum(axis=1)


@scopes.scoped(scopes.MOE_EXPERT_FFN)
def _expert_ffn(cfg: ModelConfig, w_gate, w_up, w_down, tokens):
    """tokens: [E_loc, C_tot, d] -> [E_loc, C_tot, d] (grouped SwiGLU).

    No sharding constraints in here: with_sharding_constraint on values
    that vary over manual axes is rejected inside a partial-manual
    shard_map; the expert-ff ("model") sharding of ``h`` propagates from
    the weights instead.
    """
    dt = tokens.dtype
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", tokens, w_gate.astype(dt))) \
        * jnp.einsum("ecd,edf->ecf", tokens, w_up.astype(dt))
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))


def _moe_island(cfg: ModelConfig, dist: DistContext, x, router_w,
                w_gate, w_up, w_down):
    """Runs on each (pod, data) shard with ``model`` still auto-sharded.

    x: [B_loc, S, d].  Expert stacks arrive E-sharded over the EP axes:
    [E_loc, d, f].
    """
    b, s, d = x.shape
    e = cfg.moe.num_experts
    g = dist.ep_size
    e_loc = e // g
    x_flat = x.reshape(b * s, d)
    t = b * s
    gates, eids, aux = _route(cfg, router_w, x_flat)
    cap = _capacity(cfg, t, e)
    buf, slot, keep = _dispatch(x_flat, eids, cap, e)
    buf = buf.reshape(g, e_loc * cap, d)

    if g > 1:
        a2a = scopes.scoped(scopes.MOE_EXCHANGE)(resolve_all_to_all(dist))
        recv = a2a(buf)                                     # [G, E_loc*C, d]
    else:
        recv = buf

    # [G, E_loc, C, d] -> [E_loc, G*C, d]: my experts, everyone's tokens.
    tokens = recv.reshape(g, e_loc, cap, d).transpose(1, 0, 2, 3) \
        .reshape(e_loc, g * cap, d)
    y = _expert_ffn(cfg, w_gate, w_up, w_down, tokens)
    y = y.reshape(e_loc, g, cap, d).transpose(1, 0, 2, 3) \
        .reshape(g, e_loc * cap, d)
    y = a2a(y) if g > 1 else y                              # return trip
    out = _combine(y.reshape(e * cap, d), slot, keep, gates, t,
                   cfg.moe.top_k)
    # Aux loss averaged over all manual shards so every shard returns the
    # same replicated scalar.
    aux = jax.lax.pmean(aux, dist.dp_axes)
    return out.reshape(b, s, d), aux


def _dp_size(dist: DistContext) -> int:
    shape = dict(zip(dist.mesh.axis_names, dist.mesh.devices.shape))
    n = 1
    for a in dist.dp_axes:
        n *= shape[a]
    return n


def _moe_pod_ep(cfg: ModelConfig, dist: DistContext, p: dict, x: jax.Array):
    """Split-island MoE: EP over the slow axis only (mixtral: 8e over
    pod=2), or no EP at all (p_pods=1: experts replicated, TP over model --
    mixtral on the single-pod mesh where 16 does not divide 8 experts).

    Expert weights must NOT enter the manual region: a bf16 weight
    replicated over a manual axis makes XLA:CPU's promoted-reduction pass
    emit an invalid 'copy' binary op during SPMD partitioning (CHECK-crash).
    Structure: island1 (route+dispatch+DCN rotation a2a) -> auto-world
    grouped FFN with experts sharded over 'pod' by plain constraints ->
    island2 (return a2a + combine).  Also the cleaner layout: GSPMD keeps
    full freedom over the FFN while the FLASH rotation schedule stays
    explicit.
    """
    mesh, dp, slow = dist.mesh, dist.dp_axes, dist.slow_axis
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep_axis = dist.ep_axes[0] if dist.ep_axes else None
    p_pods = shape[ep_axis] if ep_axis else 1
    exchange_slow = ep_axis is not None and ep_axis == slow
    n_shards = 1
    for a in dp:
        n_shards *= shape[a]
    e = cfg.moe.num_experts
    e_loc = e // p_pods
    b, s, d = x.shape
    t_loc = (b * s) // n_shards
    cap = _capacity(cfg, t_loc, e)
    k = cfg.moe.top_k

    @scopes.scoped(scopes.MOE_EXCHANGE)
    def _exchange(buf):
        """a2a over the EP axis: FLASH rotations on the slow (DCN) axis,
        flat all_to_all on a fast (ICI) axis; optionally int8-quantized.

        Beyond-paper (DeepSeek-V3-style low-precision dispatch): tokens are
        activations entering an expert FFN; per-row int8 with an f32 scale
        halves DCN bytes at ~0.4% RMS payload error.  The paper's own
        principle -- spend fast-tier resources to shrink slow-tier bytes.
        """
        a2a = resolve_all_to_all(
            slow_axis=ep_axis if exchange_slow else None,
            ep_axes=(ep_axis,), impl=dist.a2a_impl,
            plan=dist.plan if exchange_slow else None)

        if not (cfg.quantized_dispatch and exchange_slow):
            return a2a(buf)
        scale = jnp.maximum(jnp.max(jnp.abs(buf), axis=-1, keepdims=True),
                            1e-6) / 127.0
        q = jnp.clip(jnp.round(buf / scale), -127, 127).astype(jnp.int8)
        q = a2a(q)
        s = a2a(scale.astype(jnp.float32))
        return (q.astype(buf.dtype) * s.astype(buf.dtype))

    def island1(xl, router_w):
        bl, sl, _ = xl.shape
        x_flat = xl.reshape(bl * sl, d)
        gates, eids, aux = _route(cfg, router_w, x_flat)
        buf, slot, keep = _dispatch(x_flat, eids, cap, e)
        buf = buf.reshape(p_pods, e_loc * cap, d)
        recv = _exchange(buf) if p_pods > 1 else buf
        tokens = recv.reshape(p_pods, e_loc, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e_loc, p_pods * cap, d)
        aux = jax.lax.pmean(aux, dp)
        return (tokens[None], slot.reshape(bl, sl * k),
                keep.reshape(bl, sl * k), gates.reshape(bl, sl * k), aux)

    def island2(y_tokens, slot, keep, gates):
        y = y_tokens[0].reshape(e_loc, p_pods, cap, d).transpose(1, 0, 2, 3) \
            .reshape(p_pods, e_loc * cap, d)
        y = _exchange(y) if p_pods > 1 else y
        bl, sk = slot.shape
        out = _combine(y.reshape(e * cap, d), slot.reshape(-1),
                       keep.reshape(-1), gates.reshape(bl * sk // k, k),
                       bl * sk // k, k)
        return out.reshape(bl, sk // k, d)

    dp_spec = dp if len(dp) > 1 else dp[0]
    # check_vma=False: impl="plan" packs slots with a pallas kernel, which
    # has no replication rule under shard_map's checker.
    f1 = jax.shard_map(
        island1, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P()),
        out_specs=(P(dp_spec, None, None, None), P(dp_spec, None),
                   P(dp_spec, None), P(dp_spec, None), P()),
        axis_names=set(dp), check_vma=False)
    tokens_g, slot, keep, gates, aux = f1(x, p["router"])

    # auto-world grouped FFN: experts sharded over the slow axis, ff over TP
    from .sharding import current_rules
    rules = current_rules()

    def cstr(a, spec):
        if rules is None:
            return a
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, spec))

    # tokens_g rows are ordered by the dp shard index (dp-axis-major); the
    # EP group of a row is its coordinate along ep_axis.  Reshape so the EP
    # dim is explicit and contract the grouped FFN along it.
    dp_dims = [shape[a] for a in dp]
    dp_spec_full = tuple(dp)
    tg = tokens_g.reshape(*dp_dims, e_loc, p_pods * cap, d)
    tg = cstr(tg, P(*dp_spec_full, None, None, None))
    dt = tg.dtype
    ff_spec = None if cfg.pure_dp else "model"
    if ep_axis is None:
        wg = p["w_gate"].astype(dt)
        wu = p["w_up"].astype(dt)
        wd = p["w_down"].astype(dt)
        wg = cstr(wg, P(None, None, ff_spec))
        wu = cstr(wu, P(None, None, ff_spec))
        wd = cstr(wd, P(None, ff_spec, None))
        w_sub = "edf"
        wd_sub = "efd"
    else:
        wg = p["w_gate"].reshape(p_pods, e_loc, d, -1).astype(dt)
        wu = p["w_up"].reshape(p_pods, e_loc, d, -1).astype(dt)
        wd = p["w_down"].reshape(p_pods, e_loc, -1, d).astype(dt)
        wg = cstr(wg, P(ep_axis, None, None, ff_spec))
        wu = cstr(wu, P(ep_axis, None, None, ff_spec))
        wd = cstr(wd, P(ep_axis, None, ff_spec, None))
        ep_char = "pg"[dp.index(ep_axis)] if len(dp) > 1 else "p"
        w_sub = ep_char + "edf"
        wd_sub = ep_char + "efd"
    tok_sub = ("pgecd" if len(dp) > 1 else "pecd")
    out_sub = tok_sub.replace("d", "f")
    with jax.named_scope(scopes.MOE_EXPERT_FFN):
        h = jax.nn.silu(jnp.einsum(f"{tok_sub},{w_sub}->{out_sub}", tg,
                                   wg)) \
            * jnp.einsum(f"{tok_sub},{w_sub}->{out_sub}", tg, wu)
        y = jnp.einsum(f"{out_sub},{wd_sub}->{tok_sub}", h, wd)
    y = cstr(y, P(*dp_spec_full, None, None, None))
    y = y.reshape(n_shards, e_loc, p_pods * cap, d)

    f2 = jax.shard_map(
        island2, mesh=mesh,
        in_specs=(P(dp_spec, None, None, None), P(dp_spec, None),
                  P(dp_spec, None), P(dp_spec, None)),
        out_specs=P(dp_spec, None, None),
        axis_names=set(dp), check_vma=False)
    out = f2(y, slot, keep, gates)
    return out, aux


def moe_apply(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    dist: Optional[DistContext] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,d], aux_loss scalar)."""
    if dist is not None and x.shape[0] % _dp_size(dist) != 0:
        # batch does not divide the DP shards (long_500k decode: B=1) --
        # the token math is replicated per device; run the local path.
        dist = None
    if dist is not None and (
            dist.ep_axes is None or len(dist.ep_axes) == 1):
        # single-axis EP (mixtral: pod/DCN; dbrx: data/ICI) or no-EP
        # (experts replicated + TP): all use the split-island form, which
        # keeps expert weights out of the manual region (XLA:CPU crash,
        # see _moe_pod_ep) and lets GSPMD own the grouped FFN.
        return _moe_pod_ep(cfg, dist, p, x)
    if dist is None or dist.ep_axes is None or dist.ep_size == 1:
        b, s, d = x.shape
        x_flat = x.reshape(b * s, d)
        gates, eids, aux = _route(cfg, p["router"], x_flat)
        cap = _capacity(cfg, b * s, cfg.moe.num_experts)
        buf, slot, keep = _dispatch(x_flat, eids, cap, cfg.moe.num_experts)
        tokens = buf.reshape(cfg.moe.num_experts, cap, d)
        y = _expert_ffn(cfg, p["w_gate"], p["w_up"], p["w_down"], tokens)
        out = _combine(y.reshape(-1, d), slot, keep, gates, b * s,
                       cfg.moe.top_k)
        return out.reshape(b, s, d), aux

    mesh = dist.mesh
    dp = dist.dp_axes
    ep = dist.ep_axes
    ep_spec = ep if len(ep) > 1 else ep[0]
    island = partial(_moe_island, cfg, dist)
    fn = jax.shard_map(
        island,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),            # x: batch over DP axes
            P(),                          # router: replicated
            P(ep_spec, None, None),       # expert stacks: E over EP axes
            P(ep_spec, None, None),
            P(ep_spec, None, None),
        ),
        out_specs=(P(dp, None, None), P()),
        axis_names=set(dp),               # "model" stays auto inside
        check_vma=False,                  # pallas pack under impl="plan"
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
