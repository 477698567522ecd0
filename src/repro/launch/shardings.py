"""Parameter / state / batch sharding rules (path-based, MaxText-style).

``param_spec(path, ndim)`` matches the *trailing* dimensions of a leaf by
its name and pads leading dims (the scan-stacked layer axis) with None.
The same table covers optimizer moments (same spec as their parameter) and
decode caches.

Conventions (production mesh: pod x data x model):
  * TP over "model": attention heads / FFN hidden / vocab.
  * DP over ("pod", "data"): batch dim of activations, caches, token inputs.
  * EP over choose_ep_axes(cfg, mesh): expert-stacked MoE weight dim.
  * KV caches shard head_dim over "model" (always divisible: 64/128) and
    batch over DP -- decode attention becomes a dh-partial dot + psum,
    parallelizing cache bandwidth, the decode bottleneck.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.registry import ModelConfig
from ..models.dist import choose_ep_axes

__all__ = ["param_shardings", "batch_shardings", "cache_shardings",
           "state_shardings", "spec_tree"]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# trailing-dim spec tables ---------------------------------------------------

_MOE_TABLE = {
    "router": (None, None),
    "w_gate": ("__ep__", None, "model"),
    "w_up": ("__ep__", None, "model"),
    "w_down": ("__ep__", "model", None),
}

_PARAM_TABLE = {
    # embeddings / heads
    "embed": ("model", None),
    "lm_head": (None, "model"),
    "enc_pos": (None, None),
    "dec_pos": (None, None),
    # attention
    "wq": (None, "model"),
    "wk": (None, "model"),
    "wv": (None, "model"),
    "wo": ("model", None),
    "q_norm": (None,),
    "k_norm": (None,),
    # dense mlp
    "w_gate": (None, "model"),
    "w_up": (None, "model"),
    "w_down": ("model", None),
    "b_up": ("model",),
    "b_down": (None,),
    # xlstm
    "wif": (None, "model"),
    "wz": (None, "model"),
    "w": (None, "model"),
    "r": (None, "model"),
    # mamba
    "in_proj": (None, "model"),
    "out_proj": ("model", None),
    "conv_w": (None, "model"),
    "a_log": ("model", None),
    "d_skip": ("model",),
    "wb": ("model", None),
    "wc": ("model", None),
    "w_dt": ("model", None),
    "w_dt2": (None, "model"),
    "dt_bias": ("model",),
    # norms
    "scale": (None,),
    "bias": (None,),
}

_CACHE_TABLE = {
    # self-attention, head-major [*, B, K, phys, dh]
    "k": ("__dp__", None, None, "model"),
    "v": ("__dp__", None, None, "model"),
    # cross-attention [B, Se, K, dh]
    "xk": ("__dp__", None, None, "model"),
    "xv": ("__dp__", None, None, "model"),
    # mlstm state
    "C": ("__dp__", None, None, "model"),
    "n": ("__dp__", None, "model"),
    "m": ("__dp__", None),
    # slstm state
    "c": ("__dp__", "model"),
    "h": ("__dp__", "model", None),   # also mamba h [B, d_in, N]
    # mamba conv window [B, K-1, d_in]
    "conv": ("__dp__", None, "model"),
}

# slstm n/h/m collide with mlstm names at different ranks; rank disambiguates.
_CACHE_BY_RANK = {
    ("n", 2): ("__dp__", "model"),
    ("h", 2): ("__dp__", "model"),
    ("m", 1): ("__dp__",),
    ("m", 2): ("__dp__", None),
}


def _resolve(entry, ep, dp):
    out = []
    for e in entry:
        if e == "__ep__":
            out.append(ep)
        elif e == "__dp__":
            out.append(dp)
        else:
            out.append(e)
    return tuple(out)


def _axis_size(mesh: Mesh, entry) -> int:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if entry is None:
        return 1
    if isinstance(entry, str):
        return shape[entry]
    n = 1
    for a in entry:
        n *= shape[a]
    return n


def _drop_uneven(mesh: Mesh, entry: tuple, shape: tuple) -> tuple:
    """jit in_shardings demand even divisibility; replicate dims that the
    assigned axes do not divide (odd vocab sizes, batch=1 decode, 14-head
    attention on a 16-way TP axis, ...)."""
    out = []
    for dim, e in zip(shape, entry):
        if e is not None and dim % _axis_size(mesh, e) != 0:
            e = None
        out.append(e)
    return tuple(out)


def _trailing_spec(name: str, ndim: int, path: str, ep, dp) -> P:
    in_moe = "/moe/" in path or path.endswith("moe")
    table = dict(_PARAM_TABLE)
    if in_moe:
        table.update(_MOE_TABLE)
    entry = table.get(name)
    if entry is None:
        return P()  # replicate unknown leaves
    entry = _resolve(entry, ep, dp)
    if len(entry) > ndim:
        entry = entry[len(entry) - ndim:]
    pad = (None,) * (ndim - len(entry))
    return P(*(pad + tuple(entry)))


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_shape):
    """Tree of NamedSharding matching a params shape-tree."""
    ep_axes = choose_ep_axes(cfg, mesh)
    ep = None if ep_axes is None else \
        (ep_axes if len(ep_axes) > 1 else ep_axes[0])
    dp = tuple(a for a in mesh.axis_names if a != "model")

    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)

    def one(path, leaf):
        name = None
        for part in reversed(path):
            if hasattr(part, "key"):
                name = str(part.key)
                break
        spec = _trailing_spec(name or "", leaf.ndim, _path_str(path), ep, dp)
        entry = tuple(spec)
        if cfg.pure_dp:  # small models: replicate weights, no TP
            entry = tuple(None if e == "model" else e for e in entry)
        if cfg.fsdp and leaf.ndim >= 2:
            # ZeRO-3: additionally shard each weight over the *intra-pod*
            # DP axes on the first free, evenly-divisible dim (GSPMD
            # inserts the FSDP all-gather before use / reduce-scatter on
            # grads).  The pod axis is deliberately excluded: per-layer
            # weight gathers are the hottest collective in the step and
            # must ride ICI, not DCN -- the paper's keep-the-slow-tier-
            # clean principle applied to parameter sharding.
            fsdp_dp = tuple(a for a in mesh.axis_names if a != "pod") \
                if cfg.pure_dp else (tuple(a for a in dp if a != "pod")
                                     or dp)
            fsdp_entry = fsdp_dp if len(fsdp_dp) > 1 else fsdp_dp[0]
            used = {a for e in entry if e
                    for a in ((e,) if isinstance(e, str) else e)}
            if not used & set(fsdp_dp):
                for i, (e, dim) in enumerate(zip(entry, leaf.shape)):
                    if e is None and dim % _axis_size(mesh, fsdp_entry) == 0:
                        entry = entry[:i] + (fsdp_entry,) + entry[i + 1:]
                        break
        entry = _drop_uneven(mesh, entry, leaf.shape)
        return NamedSharding(mesh, P(*entry))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_shape):
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_entry = dp if len(dp) > 1 else dp[0]

    def one(path, leaf):
        name = None
        for part in reversed(path):
            if hasattr(part, "key"):
                name = str(part.key)
                break
        # strip the scan-stacked layer dim if present
        entry = _CACHE_BY_RANK.get((name, leaf.ndim)) \
            or _CACHE_BY_RANK.get((name, leaf.ndim - 1)) \
            or _CACHE_TABLE.get(name)
        if entry is None:
            return NamedSharding(mesh, P())
        entry = _resolve(entry, None, dp_entry)
        if len(entry) > leaf.ndim:
            entry = entry[len(entry) - leaf.ndim:]
        pad = (None,) * (leaf.ndim - len(entry))
        entry = _drop_uneven(mesh, pad + tuple(entry), leaf.shape)
        return NamedSharding(mesh, P(*entry))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def batch_shardings(mesh: Mesh, batch_shape, pure_dp: bool = False):
    dp = tuple(mesh.axis_names) if pure_dp \
        else tuple(a for a in mesh.axis_names if a != "model")
    dp_entry = dp if len(dp) > 1 else dp[0]

    def one(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        entry = _drop_uneven(
            mesh, (dp_entry,) + (None,) * (leaf.ndim - 1), leaf.shape)
        return NamedSharding(mesh, P(*entry))

    return jax.tree.map(one, batch_shape)


def state_shardings(cfg: ModelConfig, mesh: Mesh, state_shape):
    """TrainState = {params, opt(m, v, count), step}: moments follow params."""
    from ..optim import OptState  # avoid cycle
    del OptState
    params_sh = param_shardings(cfg, mesh, state_shape["params"])
    m_sh = param_shardings(cfg, mesh, state_shape["opt"].m)
    v_sh = param_shardings(cfg, mesh, state_shape["opt"].v)
    opt_sh = type(state_shape["opt"])(
        m=m_sh, v=v_sh, count=NamedSharding(mesh, P()))
    return {"params": params_sh, "opt": opt_sh,
            "step": NamedSharding(mesh, P())}


def spec_tree(shardings):
    return jax.tree.map(lambda s: s.spec, shardings)
