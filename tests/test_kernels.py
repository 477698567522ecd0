"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dev dep: skip property-based tests
    from _hypothesis_fallback import given, settings, st

from repro.kernels.a2a_pack import a2a_pack_op, a2a_pack_ref, \
    a2a_unpack_op, a2a_unpack_ref
from repro.kernels.flash_attention import attention_ref, flash_attention_op
from repro.kernels.grouped_matmul import grouped_matmul_op, grouped_matmul_ref


@pytest.mark.parametrize("b,h,kv,s,d,causal,window", [
    (2, 4, 2, 256, 64, True, None),
    (1, 4, 4, 256, 128, True, 64),
    (2, 2, 1, 512, 64, False, None),
    (1, 8, 2, 256, 128, True, 128),
    (1, 2, 2, 128, 128, True, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, h, kv, s, d, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention_op(q, k, v, causal=causal, window=window,
                             interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 256)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 64))
    out = flash_attention_op(q, k, v, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("e,c,d,f,masked", [
    (4, 128, 256, 128, False),
    (8, 256, 512, 256, True),
    (2, 128, 1024, 512, True),
    (1, 128, 128, 128, False),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_ref(e, c, d, f, masked, dtype):
    ks = jax.random.split(jax.random.PRNGKey(e + c), 3)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype)
    counts = jax.random.randint(ks[2], (e,), 0, c + 1) if masked else None
    y = grouped_matmul_op(x, w, counts, interpret=True)
    ref = grouped_matmul_ref(x, w, counts)
    scale = float(jnp.abs(ref.astype(jnp.float32)).max()) + 1e-9
    err = float(jnp.abs(y.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max()) / scale
    assert err < (1e-5 if dtype == jnp.float32 else 2e-2), err


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 64), st.integers(1, 64), st.integers(0, 2 ** 31 - 1))
def test_a2a_pack_property(n, m, seed):
    d = 128
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, n)
    y = a2a_pack_op(x, idx, interpret=True)
    assert jnp.array_equal(y, a2a_pack_ref(x, idx))


def test_a2a_pack_moe_layout():
    """Pack scattered token rows destination-contiguously (the paper's
    anti-fragmentation bundling): packed buffer equals sort-by-destination."""
    n, d, n_dst = 64, 128, 4
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (n, d))
    dst = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, n_dst)
    order = jnp.argsort(dst, stable=True)
    packed = a2a_pack_op(x, order.astype(jnp.int32), interpret=True)
    assert jnp.array_equal(packed, x[order])
    # destination-contiguity: dst of packed rows is non-decreasing
    assert bool(jnp.all(jnp.diff(dst[order]) >= 0))


@pytest.mark.parametrize("d", [5, 64, 130, 200, 256])
def test_a2a_pack_non_tile_lanes(d):
    """D need not divide the 128-lane tile: pad-and-slice inside the op."""
    n, m = 16, 9
    key = jax.random.PRNGKey(d)
    x = jax.random.normal(key, (n, d), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, n)
    y = a2a_pack_op(x, idx, interpret=True)
    assert jnp.array_equal(y, a2a_pack_ref(x, idx))


@pytest.mark.parametrize("block_rows", [1, 3, 8, 16, 24])
@pytest.mark.parametrize("d", [128, 72])
def test_a2a_pack_block_rows(block_rows, d):
    """Row blocks beyond 1: out block m = in block idx[m], any block size
    (8-row sublane tiling kicks in for multiples of 8)."""
    n_blocks, m = 6, 10
    key = jax.random.PRNGKey(block_rows * d)
    x = jax.random.normal(key, (n_blocks * block_rows, d), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (m,), 0, n_blocks)
    y = a2a_pack_op(x, idx, block_rows=block_rows, interpret=True)
    assert jnp.array_equal(y, a2a_pack_ref(x, idx, block_rows=block_rows))


@pytest.mark.parametrize("block_rows", [1, 8, 24])
@pytest.mark.parametrize("d", [128, 130])
def test_a2a_unpack_matches_ref(block_rows, d):
    """Inverse scatter: out block idx[m] <- in block m.  Blocks never
    named by idx are unspecified, so parity is checked on named blocks
    only (the plan-exec caller slices its trash block off the same way)."""
    n_out, m = 8, 5
    key = jax.random.PRNGKey(3 * block_rows + d)
    x = jax.random.normal(key, (m * block_rows, d), jnp.float32)
    perm = jax.random.permutation(jax.random.fold_in(key, 1), n_out)
    idx = perm[:m].astype(jnp.int32)
    y = a2a_unpack_op(x, idx, n_out_blocks=n_out, block_rows=block_rows,
                      interpret=True)
    ref = a2a_unpack_ref(x, idx, n_out_blocks=n_out, block_rows=block_rows)
    named = np.asarray(
        y.reshape(n_out, block_rows, d))[np.asarray(idx)]
    named_ref = np.asarray(
        ref.reshape(n_out, block_rows, d))[np.asarray(idx)]
    assert np.array_equal(named, named_ref)


@pytest.mark.parametrize("block_rows", [1, 4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a2a_pack_unpack_round_trip(block_rows, seed):
    """unpack(pack(x, perm), perm) == x for any permutation of blocks."""
    n_blocks, d = 7, 128
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n_blocks * block_rows, d), jnp.float32)
    perm = jax.random.permutation(
        jax.random.fold_in(key, 1), n_blocks).astype(jnp.int32)
    packed = a2a_pack_op(x, perm, block_rows=block_rows, interpret=True)
    back = a2a_unpack_op(packed, perm, n_out_blocks=n_blocks,
                         block_rows=block_rows, interpret=True)
    assert jnp.array_equal(back, x)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(1, 12), st.integers(1, 180),
       st.integers(0, 2 ** 31 - 1))
def test_a2a_pack_unpack_round_trip_property(n_blocks, block_rows, d, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n_blocks * block_rows, d), jnp.float32)
    perm = jax.random.permutation(
        jax.random.fold_in(key, 1), n_blocks).astype(jnp.int32)
    packed = a2a_pack_op(x, perm, block_rows=block_rows, interpret=True)
    assert jnp.array_equal(
        packed, a2a_pack_ref(x, perm, block_rows=block_rows))
    back = a2a_unpack_op(packed, perm, n_out_blocks=n_blocks,
                         block_rows=block_rows, interpret=True)
    assert jnp.array_equal(back, x)


@pytest.mark.parametrize("op", ["pack", "unpack"])
@pytest.mark.parametrize("block_rows", [1, 4, 12])
def test_a2a_block_rows_off_tpu_tiling_raise(op, block_rows):
    """Compiled for TPU (interpret=False), a block that is neither a
    multiple of 8 rows nor the whole array is refused up front with the
    cause, not deep inside Mosaic lowering."""
    from repro.kernels.a2a_pack.a2a_pack import a2a_pack, a2a_unpack

    x = jnp.zeros((3 * block_rows, 128), jnp.float32)
    idx = jnp.arange(3, dtype=jnp.int32)
    fn = a2a_pack if op == "pack" else a2a_unpack
    with pytest.raises(ValueError, match="multiple of 8"):
        fn(x, idx, block_rows=block_rows, interpret=False)
