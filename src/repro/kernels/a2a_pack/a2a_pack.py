"""Destination-contiguous token packing Pallas kernels (paper section 5 (2)).

FLASH's implementation note: "bundle the data having the same destination
... eliminating data fragmentation and allowing for consecutive memory
reads."  On TPU the analogue is packing routed token rows into
destination-contiguous order *before* the dispatch All-to-All so every
ppermute chunk is one contiguous HBM stream (and the 128-lane tiles stay
dense).  ``a2a_unpack`` is the inverse scatter used after the exchange to
put each received stage buffer back at its source-shard slot.

Both kernels are gathers/scatters driven from scalar-prefetch memory: the
index vector rides in SMEM ahead of the grid, and each grid step's
BlockSpec index_map dereferences it -- so the DMA engine fetches (or
stores) exactly the block each slot needs: a data-dependent DMA schedule,
no gather lowering in XLA.

Block structure: ``block_rows`` rows move per index.  ``block_rows=1`` is
the general row gather (interpret mode only: compiled for TPU, a block
must be a multiple of 8 rows or the whole array, and anything else raises
``ValueError``); the plan-driven A2A path uses pod-sized blocks
(``block_rows = fast_size * capacity_rows``), and when ``block_rows`` is a
multiple of 8 the grid tiles each block into (8, D) sublane tiles so the
f32 (8, 128) register tile stays dense.  ``D`` need not be a multiple of
128: inputs are zero-padded up to the next lane-tile boundary and the
result sliced back (pad-and-slice fallback).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128       # last-dim tile width every TPU dtype shares
_SUBLANE = 8      # f32 second-minor tile height


def _pad_lanes(x: jax.Array) -> jax.Array:
    """Zero-pad the last dim up to the next multiple of the 128-lane tile."""
    d = x.shape[-1]
    if d % _LANE == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, _LANE - d % _LANE)))


def _copy_kernel(idx_ref, x_ref, o_ref):
    del idx_ref  # consumed by the index maps
    o_ref[...] = x_ref[...]


def _block_call(x, idx, *, n_out_rows: int, block_rows: int,
                in_map, out_map, interpret: bool):
    """Shared pallas_call builder for pack (gather) and unpack (scatter).

    ``in_map`` / ``out_map`` build the BlockSpec index maps from the
    per-sublane-tile block count ``t`` (blocks per index step); the grid is
    (m,) for single-tile blocks and (m, t) when ``block_rows`` splits into
    8-row sublane tiles.
    """
    d_in = x.shape[-1]
    xp = _pad_lanes(x)
    d = xp.shape[-1]
    m = idx.shape[0]
    whole = block_rows == x.shape[0] == n_out_rows
    if not interpret and block_rows % _SUBLANE and not whole:
        # Mosaic only tiles (8k, 128j) blocks or whole arrays; fail here
        # with the cause instead of deep inside lowering.
        raise ValueError(
            f"block_rows={block_rows} is neither a multiple of {_SUBLANE} "
            f"nor the whole array ({x.shape[0]} in, {n_out_rows} out rows), "
            f"which the TPU tiling refuses; pad blocks to a multiple of "
            f"{_SUBLANE} rows or run with interpret=True")
    if block_rows % _SUBLANE == 0 and block_rows > _SUBLANE:
        t = block_rows // _SUBLANE
        grid = (m, t)
        rows = _SUBLANE
        semantics = ("arbitrary", "arbitrary")
    else:
        t = 1
        grid = (m,)
        rows = block_rows
        semantics = ("arbitrary",)
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((rows, d), in_map(t))],
            out_specs=pl.BlockSpec((rows, d), out_map(t)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_out_rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(idx.astype(jnp.int32), xp)
    return out[:, :d_in] if d != d_in else out


def a2a_pack(
    x: jax.Array,          # [N, D] token rows (N % block_rows == 0)
    idx: jax.Array,        # [M] int32 block indices: output block m
                           #     <- x rows [idx[m]*r, (idx[m]+1)*r)
    *,
    block_rows: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Gather ``block_rows``-row blocks of ``x`` in ``idx`` order.

    ``block_rows=1`` is the plain row gather ``out[m] = x[idx[m]]``.
    Returns ``[M * block_rows, D]``.
    """
    n, _ = x.shape
    m = idx.shape[0]
    r = block_rows
    if r < 1 or n % r != 0:
        raise ValueError(f"block_rows={r} must divide N={n}")

    if r % _SUBLANE == 0 and r > _SUBLANE:
        # grid (m, t): tile j of output block i <- tile j of block idx[i].
        def in_map(t):
            return lambda i, j, idx_ref: (idx_ref[i] * t + j, 0)

        def out_map(t):
            return lambda i, j, idx_ref: (i * t + j, 0)
    else:
        def in_map(t):
            del t
            return lambda i, idx_ref: (idx_ref[i], 0)

        def out_map(t):
            del t
            return lambda i, idx_ref: (i, 0)

    return _block_call(x, idx, n_out_rows=m * r, block_rows=r,
                       in_map=in_map, out_map=out_map, interpret=interpret)


def a2a_unpack(
    x: jax.Array,          # [M * block_rows, D] packed rows
    idx: jax.Array,        # [M] int32 block indices: output block idx[m]
                           #     <- x rows [m*r, (m+1)*r)
    *,
    n_out_blocks: int = 0,  # output blocks (0 => M); blocks not named by
                            # idx are unspecified (NaN-filled in interpret
                            # mode, stale HBM on hardware) -- callers slice
                            # a trash block off, never read it
    block_rows: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Inverse scatter of ``a2a_pack``: output block ``idx[m]`` <- block
    ``m`` of ``x`` (``block_rows=1``: ``out[idx[m]] = x[m]``).

    ``idx`` must be injective over real output blocks (one writer each;
    duplicate writes to a sliced-off trash block are tolerated -- the grid
    is serial, one lands).  Output blocks not named by ``idx`` are
    unspecified -- full-coverage permutations (the plan-exec use) define
    every real row.  Returns ``[max(M, n_out_blocks) * block_rows, D]``.
    """
    n, _ = x.shape
    m = idx.shape[0]
    r = block_rows
    if r < 1 or n != m * r:
        raise ValueError(f"x rows {n} != M*block_rows = {m}*{r}")
    n_out = max(m, n_out_blocks) * r

    if r % _SUBLANE == 0 and r > _SUBLANE:
        def in_map(t):
            return lambda i, j, idx_ref: (i * t + j, 0)

        def out_map(t):
            return lambda i, j, idx_ref: (idx_ref[i] * t + j, 0)
    else:
        def in_map(t):
            del t
            return lambda i, idx_ref: (i, 0)

        def out_map(t):
            del t
            return lambda i, idx_ref: (idx_ref[i], 0)

    return _block_call(x, idx, n_out_rows=n_out, block_rows=r,
                       in_map=in_map, out_map=out_map, interpret=interpret)
