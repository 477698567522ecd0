"""Grouped (per-expert) matmul Pallas TPU kernel for MoE FFNs.

Computes y[e] = x[e] @ w[e] for E experts with optional per-expert valid row
counts (capacity buffers are padded; rows beyond ``counts[e]`` are garbage
and must not pollute the MXU accumulation -- they are zero-masked on the
final write, the TPU analogue of megablocks' ragged grouped GEMM).

VMEM tiling: (block_c x block_d) x (block_d x block_f) tiles, f32
accumulator scratch of (block_c, block_f); grid (E, C/bc, F/bf, D/bd) with
the contraction dimension innermost and 'arbitrary'.  All tile dims default
to 128/512 -- MXU-aligned multiples of 128.

Per-expert counts ride in scalar-prefetch memory so the index maps and the
masking see them before the tiles stream in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(counts_ref, x_ref, w_ref, y_ref, acc_scr,
                *, block_c: int, block_f: int, n_d_blocks: int):
    # program_ids hoisted out of pl.when bodies (interpret-mode requirement)
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)          # [bc, bd]
    w = w_ref[0].astype(jnp.float32)          # [bd, bf]
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(di == n_d_blocks - 1)
    def _write():
        rows = ci * block_c + jax.lax.broadcasted_iota(
            jnp.int32, (block_c, block_f), 0)
        valid = rows < counts_ref[e]
        y_ref[0, ...] = jnp.where(valid, acc_scr[...], 0.0).astype(y_ref.dtype)


def grouped_matmul(
    x: jax.Array,                    # [E, C, D]
    w: jax.Array,                    # [E, D, F]
    counts: Optional[jax.Array] = None,   # [E] int32 valid rows per expert
    *,
    block_c: int = 128,
    block_d: int = 512,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w.shape[-1]
    assert w.shape == (e, d, f)
    block_c = min(block_c, c)
    block_d = min(block_d, d)
    block_f = min(block_f, f)
    assert c % block_c == 0 and d % block_d == 0 and f % block_f == 0
    if counts is None:
        counts = jnp.full((e,), c, jnp.int32)
    n_d = d // block_d

    kernel = functools.partial(
        _gmm_kernel, block_c=block_c, block_f=block_f, n_d_blocks=n_d)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e, c // block_c, f // block_f, n_d),
            in_specs=[
                pl.BlockSpec((1, block_c, block_d),
                             lambda e_, ci, fi, di, counts: (e_, ci, di)),
                pl.BlockSpec((1, block_d, block_f),
                             lambda e_, ci, fi, di, counts: (e_, di, fi)),
            ],
            out_specs=pl.BlockSpec((1, block_c, block_f),
                                   lambda e_, ci, fi, di, counts:
                                   (e_, ci, fi)),
            scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="grouped_matmul",
    )(counts.astype(jnp.int32), x, w)
