"""Per-kernel sweeps in Pallas interpret mode vs pure-jnp oracle.

Every kernel is swept over shapes and dtypes and asserted allclose (exact
for sorts — integer/float compare-exchange is exact; tolerant for attention).
Interpret mode checks what a kernel computes, not that the chip's compiler
accepts it: ``test_tpu_compile.py`` compiles the main path's kernels for a
described v5e, and ``chip_smoke.py`` runs them on one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare interpreter: property tests skip, the rest run
    from _hypstub import given, settings, st

from repro.kernels import bitonic, ops, ref


@pytest.mark.parametrize("rows", [1, 2, 8, 16])
@pytest.mark.parametrize("n", [2, 8, 128, 256, 1024])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.bfloat16, jnp.uint32])
def test_sort_tiles_sweep(rows, n, dtype):
    key = jax.random.PRNGKey(rows * 10_000 + n)
    if jnp.issubdtype(dtype, jnp.integer):
        x = jax.random.randint(key, (rows, n), 0, 1 << 20).astype(dtype)
    else:
        x = jax.random.normal(key, (rows, n)).astype(dtype)
    out = ops.sort_rows(x)
    np.testing.assert_array_equal(
        np.asarray(out, np.float64), np.asarray(ref.sort_ref(x), np.float64)
    )


@pytest.mark.parametrize("n", [8, 128, 512])
def test_sort_kv_unique_keys(n):
    key = jax.random.PRNGKey(n)
    perm = jax.random.permutation(key, jnp.arange(n, dtype=jnp.int32))
    keys = perm[None, :]
    vals = (perm * 7 + 1)[None, :]
    ks, vs = ops.sort_rows_kv(keys, vals)
    ek, ev = ref.sort_kv_ref(keys, vals)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(ek))
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(ev))


@pytest.mark.parametrize("n", [16, 256])
def test_sort_kv_duplicate_keys_pairing_preserved(n):
    """With duplicate keys the network is unstable; the invariant is that
    (key, value) *pairs* are preserved and keys come out sorted."""
    key = jax.random.PRNGKey(n + 1)
    keys = jax.random.randint(key, (4, n), 0, 7, dtype=jnp.int32)
    vals = jnp.arange(4 * n, dtype=jnp.int32).reshape(4, n)
    ks, vs = ops.sort_rows_kv(keys, vals)
    assert (np.diff(np.asarray(ks), axis=1) >= 0).all()
    for r in range(4):
        got = set(zip(np.asarray(ks)[r].tolist(), np.asarray(vs)[r].tolist()))
        want = set(zip(np.asarray(keys)[r].tolist(), np.asarray(vals)[r].tolist()))
        assert got == want


@pytest.mark.parametrize("n", [8, 128, 1024])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_merge_tiles_sweep(n, dtype):
    key = jax.random.PRNGKey(n)
    ka, kb = jax.random.split(key)
    if jnp.issubdtype(dtype, jnp.integer):
        a = jnp.sort(jax.random.randint(ka, (8, n), 0, 1000).astype(dtype), axis=-1)
        b = jnp.sort(jax.random.randint(kb, (8, n), 0, 1000).astype(dtype), axis=-1)
    else:
        a = jnp.sort(jax.random.normal(ka, (8, n)).astype(dtype), axis=-1)
        b = jnp.sort(jax.random.normal(kb, (8, n)).astype(dtype), axis=-1)
    out = ops.merge_rows(a, b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.merge_ref(a, b)))


@given(st.integers(1, 6), st.integers(0, 2**32 - 1))
@settings(max_examples=30, deadline=None)
def test_blockwise_sort_matches_core(log_block, seed):
    """kernels.ops.blockwise_sort == core.marathon.blockwise_sort — ties the
    Pallas path to the paper-faithful semantics."""
    from repro.core import blockwise_sort as np_blockwise

    block = 1 << log_block
    n = block * 16
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 10_000, size=n).astype(np.int32)
    out = ops.blockwise_sort(jnp.asarray(x), block)
    np.testing.assert_array_equal(np.asarray(out), np_blockwise(x, block))


def test_argsort_padded_non_pow2():
    x = jnp.asarray([5, 3, 9, 1, 7], dtype=jnp.int32)
    ks, vs = ops.argsort_padded(x)
    np.testing.assert_array_equal(np.asarray(ks), [1, 3, 5, 7, 9])
    np.testing.assert_array_equal(np.asarray(x)[np.asarray(vs)], [1, 3, 5, 7, 9])


@pytest.mark.parametrize(
    "B,T,S,H,KVH,d",
    [
        (1, 128, 128, 2, 2, 64),   # MHA
        (2, 256, 256, 4, 2, 64),   # GQA 2:1
        (1, 128, 128, 8, 2, 128),  # GQA 4:1, d=128
        (1, 256, 256, 4, 1, 64),   # MQA
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, T, S, H, KVH, d, dtype):
    keys = jax.random.split(jax.random.PRNGKey(T + H), 3)
    q = (jax.random.normal(keys[0], (B, T, H, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(keys[1], (B, S, KVH, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(keys[2], (B, S, KVH, d)) * 0.5).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.mha_ref(q, k, v, causal=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=atol, rtol=2e-2
    )


def test_flash_attention_noncausal():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(keys[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(keys[2], (1, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    want = ref.mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-3)


def test_sort_guards_reject_float_keys():
    """The bitonic pad sentinel is the dtype max; floats have no usable one
    (NaN ordering), so the entry points raise instead of mis-sorting."""
    with pytest.raises(TypeError, match="integer keys"):
        ops.sort_rows_padded(jnp.ones((2, 4), jnp.float32))
    with pytest.raises(TypeError, match="integer keys"):
        ops.merge_tournament(jnp.ones((2, 4), jnp.float32))
    with pytest.raises(TypeError, match="integer keys"):
        bitonic.tournament_merge_array(jnp.ones((2, 4), jnp.float32))


def test_sort_guards_reject_int64_without_x64():
    """Without an x64 scope jax truncates int64 at the jit boundary; the
    guard fires pre-dispatch so packed key+payload records never silently
    lose their top 32 bits."""
    x = np.arange(8, dtype=np.int64).reshape(2, 4)
    with pytest.raises(TypeError, match="x64"):
        ops.sort_rows_padded(x)
    with pytest.raises(TypeError, match="x64"):
        ops.merge_tournament(x)


def test_sort_rows_padded_int64_packed_payload_records():
    """64-bit packed (key << nbits) | row records, non-pow2 row count: the
    row padding stays distinct and the payload row indices ride the sort."""
    rng = np.random.default_rng(0)
    rows, b, nbits = 5, 16, 20  # 5 rows: exercises the pad-to-8 path
    keys = rng.integers(0, 1 << 40, size=(rows, b)).astype(np.int64)
    rec = (keys << nbits) | np.arange(rows * b, dtype=np.int64).reshape(rows, b)
    with ops.x64_scope():
        out = np.asarray(ops.sort_rows_padded(jnp.asarray(rec)))
    np.testing.assert_array_equal(out, np.sort(rec, axis=1))
    # unpacked keys sorted; every payload row index survives the pack
    assert (np.diff(out >> nbits, axis=1) >= 0).all()
    np.testing.assert_array_equal(
        np.sort((out & ((1 << nbits) - 1)).ravel()), np.arange(rows * b)
    )


def test_merge_tournament_int64_packed_runs():
    rng = np.random.default_rng(1)
    P, B, nbits = 4, 32, 12
    pad = np.iinfo(np.int64).max
    lens = rng.integers(1, B + 1, size=P)  # ragged runs inside padded rows
    mat = np.full((P, B), pad, np.int64)
    want = []
    row = 0
    for i, ln in enumerate(lens):
        k = np.sort(rng.integers(0, 1 << 40, size=ln).astype(np.int64))
        packed = (k << nbits) | (row + np.arange(ln))
        mat[i, :ln] = packed
        want.append(packed)
        row += int(ln)
    with ops.x64_scope():
        out = np.asarray(ops.merge_tournament(jnp.asarray(mat)))
    total = int(lens.sum())
    np.testing.assert_array_equal(out[:total], np.sort(np.concatenate(want)))
    assert (out[total:] == pad).all()


def test_merge_tournament_non_pow2_shapes_raise():
    with pytest.raises(ValueError, match="powers of two"):
        ops.merge_tournament(jnp.ones((3, 8), jnp.int32))
    with pytest.raises(ValueError, match="powers of two"):
        ops.merge_tournament(jnp.ones((4, 6), jnp.int32))
    with pytest.raises(ValueError, match="power of two"):
        ops.sort_rows_padded(jnp.ones((2, 6), jnp.int32))


def test_bitonic_network_stage_count():
    """log²: n=1024 -> 10 rounds, 55 compare-exchange stages (the paper's
    'pipeline stages' budget on TPU)."""
    stages = list(bitonic._stages(1024))
    assert len(stages) == 55
