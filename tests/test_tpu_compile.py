"""The main path's kernels compile for a TPU v5e — checked without a chip.

Interpret-mode tests (``test_kernels.py``) say nothing about what Mosaic
accepts.  These compile each kernel the dataplane runs on the chip for a
*described* v5e (``jax.experimental.topologies``) at the shapes it uses:
the device epoch's block sort ``sort_rows_padded`` on int32 ``(R, 64)`` and
``(R, 32)`` — also inside an x64 scope, as the epoch traces it — and on
stacked sort words, the row merge, and the run-arena tournament at the
largest bucket under the VMEM cap.  Each asserts that the kernel (``tpu_custom_call``) is in the
compiled program.  ``chip_smoke.py`` runs them on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitonic, ops


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64_scope"])
@pytest.mark.parametrize("cols", [64, 32])
def test_sort_rows_padded_compiles(one_chip, cols, x64):
    with ops.x64_scope() if x64 else jax.enable_x64(False):
        text = _compiled_text(
            lambda x: ops.sort_rows_padded(x, interpret=False),
            one_chip, ((64, cols), jnp.int32),
        )
    assert "tpu_custom_call" in text


def test_merge_rows_compiles(one_chip):
    text = _compiled_text(
        lambda a, b: ops.merge_rows(a, b, interpret=False),
        one_chip, ((64, 64), jnp.int32), ((64, 64), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64_scope"])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint16])
def test_merge_tournament_largest_bucket_compiles(one_chip, dtype, x64):
    B = 4096
    P = bitonic.TOURNAMENT_MAX_ELEMS // B
    with ops.x64_scope() if x64 else jax.enable_x64(False):
        text = _compiled_text(
            lambda x: ops.merge_tournament(x, interpret=False),
            one_chip, ((P, B), dtype),
        )
    assert "tpu_custom_call" in text


def test_int64_keys_compile_without_a_kernel(one_chip):
    """The dtype rule: packed int64 records lower through XLA."""
    with ops.x64_scope():
        text = _compiled_text(
            lambda x: ops.merge_tournament(x, interpret=False),
            one_chip, ((16, 64), jnp.int64),
        )
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64_scope"])
@pytest.mark.parametrize("words", [4, 5])
def test_multi_word_block_sort_compiles(one_chip, words, x64):
    """The wide-key block sort: 10- and 16-byte keys as 3 and 4 sort words
    plus the row, stacked (K, R, 64) int32."""
    with ops.x64_scope() if x64 else jax.enable_x64(False):
        text = _compiled_text(
            lambda x: ops.sort_rows_padded(x, interpret=False),
            one_chip, ((words, 60, 64), jnp.int32),
        )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("words,B", [(4, 64), (5, 128)])
def test_multi_word_tournament_compiles_without_a_kernel(one_chip, words, B):
    """The wide records' arena tournament lowers through XLA, at the
    largest bucket the arena gives one call."""
    cap = 1 << ((bitonic.TOURNAMENT_MAX_ELEMS // words).bit_length() - 1)
    with ops.x64_scope():
        text = _compiled_text(
            lambda x: ops.merge_tournament(x, interpret=False),
            one_chip, ((words, cap // B, B), jnp.int32),
        )
    assert "tpu_custom_call" not in text
