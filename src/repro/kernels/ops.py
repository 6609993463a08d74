"""Public wrappers around the Pallas kernels, and the rule that picks
each call's lowering.

Every sort or merge entry point runs one of three lowerings:

* ``"pallas"`` — the Mosaic kernel, on a TPU backend;
* ``"interpret"`` — the same kernel in Pallas interpret mode, the default
  off-TPU (CPU runs validate the kernels this way; it proves nothing about
  what the chip's compiler accepts — ``chip_smoke.py`` and
  ``tests/test_tpu_compile.py`` do);
* ``"xla"`` — the plain-jnp network or ``jnp.sort``.  This is the dtype
  rule: 64-bit keys (the dataplane's packed key+row records) never enter a
  Pallas kernel, on any backend.  Off-TPU the run-arena tournament also
  takes it, since interpreting its kernel is orders of magnitude slower,
  and the multi-word tournament takes it on every backend.

Wide keys enter as ``K`` stacked 32-bit word arrays (a 3-D argument to
:func:`sort_rows_padded` and :func:`merge_tournament`), compared as tuples.

:data:`LOWERING_COUNTS` records each call's choice, so a run can show that
no call on the chip fell back to interpret mode or to XLA.
"""

from __future__ import annotations

import collections
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import bitonic
from .flash_attention import flash_attention as _flash_attention

#: Calls of the sort/merge entry points by ``"<op>:<lowering>:<key
#: dtype>"``, ``<dtype>x<K>`` for a multi-word call on ``K`` stacked word
#: arrays.  A call made while a larger program is traced (the device
#: epoch's block sort) counts once per trace, not once per execution.  The
#: device epoch also counts each hop it traces, as
#: ``"epoch_hop:scatter_free:int64"`` (the hop's stage math lowers without
#: a scatter), and a wide-key hop of ``W`` sort words as
#: ``"epoch_hop:wide_key:w<W>"`` besides.
LOWERING_COUNTS: collections.Counter = collections.Counter()


def reset_lowering_counts() -> None:
    LOWERING_COUNTS.clear()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default(interpret: bool | None) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def x64_scope():
    """The one x64 scope of the repo: 64-bit keys (the dataplane's packed
    key+row records) are traced and dispatched inside it, or jax would
    truncate them to 32 bits at the jit boundary."""
    return jax.enable_x64(True)


def _kernel_scope(lowering: str):
    """Kernels trace with x64 off, even inside an x64 program (the device
    epoch): their keys are 32-bit, and Mosaic rejects the int64 scalars a
    bare int becomes under x64."""
    if lowering == "xla":
        return contextlib.nullcontext()
    return jax.enable_x64(False)


def _lowering(dtype, interpret: bool | None, op: str, words: int = 0) -> str:
    """Pick and count the lowering of one call (module docstring).

    ``words`` > 0 marks a multi-word call on that many stacked arrays,
    counted as ``<dtype>x<words>``."""
    dtype = np.dtype(dtype)
    if dtype.itemsize == 8:
        lowering = "xla"
    else:
        lowering = "interpret" if _interpret_default(interpret) else "pallas"
    if op == "merge_tournament" and lowering == "interpret":
        lowering = "xla"
    name = f"{dtype.name}x{words}" if words else dtype.name
    LOWERING_COUNTS[f"{op}:{lowering}:{name}"] += 1
    return lowering


def _kernel_only(fn, x, interpret: bool | None, op: str, *args):
    """Call ``fn(*args, interpret=...)`` for an entry point that has only
    the kernel form."""
    if np.dtype(x.dtype).itemsize == 8:
        raise TypeError(
            f"{op}: 64-bit keys never enter a Pallas kernel; use "
            "sort_rows_padded / merge_tournament, which lower them via XLA"
        )
    lowering = _lowering(x.dtype, interpret, op)
    with _kernel_scope(lowering):
        return fn(*args, interpret=lowering == "interpret")


def _next_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


def _check_sort_keys(x, op: str) -> None:
    """Key-dtype guard for the sorting/merging entry points.

    The bitonic networks compare integer keys and use the dtype max as the
    pad sentinel; float keys (NaN ordering) and non-numeric dtypes have no
    such sentinel.  64-bit keys — the dataplane's packed key+payload-row
    records — are valid but only under an x64 scope: without it jax would
    silently truncate them to 32 bits at the jit boundary, so the guard
    runs *before* dispatch and raises instead.
    """
    dtype = np.dtype(x.dtype)
    if dtype.kind not in "iu":
        raise TypeError(
            f"{op} sorts integer keys only, got dtype {dtype}; the bitonic "
            "network needs an integer pad sentinel"
        )
    if dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        raise TypeError(
            f"{op}: 64-bit keys require an x64 scope "
            "(ops.x64_scope()); without it the jit boundary "
            "would silently truncate them to 32 bits"
        )


def blockwise_sort(
    x: jax.Array, block: int, interpret: bool | None = None
) -> jax.Array:
    """MergeMarathon segment emission on TPU: sort consecutive ``block``
    chunks of a 1-D stream with the bitonic kernel.

    ``block`` must be a power of two and divide ``x.size`` (the ops-level
    contract; ragged tails are padded by the caller with the dtype max).
    """
    (n,) = x.shape
    if block & (block - 1) or n % block:
        raise ValueError(f"n={n} block={block}: need pow2 block dividing n")
    return _kernel_only(_blockwise_sort, x, interpret, "blockwise_sort", x, block)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _blockwise_sort(x: jax.Array, block: int, interpret: bool) -> jax.Array:
    (n,) = x.shape
    rows = n // block
    out = bitonic.sort_tiles(
        x.reshape(rows, block), rows_per_tile=_row_tile(rows),
        interpret=interpret,
    )
    return out.reshape(n)


def _row_tile(rows: int, target: int = 8) -> int:
    """Largest divisor of ``rows`` that is <= target (grid tiling)."""
    for t in range(min(target, rows), 0, -1):
        if rows % t == 0:
            return t
    return 1


def sort_rows_padded(x: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Row sort for an arbitrary row count: the fused hop engine's one
    device call per switch hop, and the device epoch's block sort.

    A 3-D ``x`` of ``K`` stacked 32-bit word arrays ``(K, rows, B)`` is the
    multi-word entry: each row sorts as ``K``-word tuples compared
    lexicographically (word 0 most significant; the device epoch's wide
    keys, with the input row as the last word), in the Pallas kernel
    (:func:`repro.kernels.bitonic.sort_tiles_stacked`), counted as
    ``sort_rows_padded:<lowering>:int32x<K>``; its pad rows are all-max
    tuples.

    Pads the row dimension up to a multiple of 8 with dtype-max rows so the
    grid always tiles 8 rows per kernel invocation (``sort_rows`` would fall
    to 1-row tiles whenever the row count is prime), sorts, and slices the
    padding back off.  Column count must be a power of two (the bitonic
    contract); ragged *columns* are the caller's padding, done once per hop.
    Keys must be integers narrow enough for the active precision
    (:func:`_check_sort_keys`) — the guard runs pre-dispatch so a 64-bit
    column without an x64 scope raises instead of truncating.  64-bit keys
    sort with ``jnp.sort`` (the dtype rule); for integers the result is the
    kernel's, element for element.
    """
    _check_sort_keys(x, "sort_rows_padded")
    b = x.shape[-1]
    if b & (b - 1):
        raise ValueError(f"column count must be a power of two, got {b}")
    if x.ndim == 3:
        _check_words(x, "sort_rows_padded")
        lowering = _lowering(x.dtype, interpret, "sort_rows_padded", x.shape[0])
        with _kernel_scope(lowering):
            return _sort_rows_padded_stacked(x, lowering=lowering)
    lowering = _lowering(x.dtype, interpret, "sort_rows_padded")
    with _kernel_scope(lowering):
        return _sort_rows_padded(x, lowering=lowering)


def _check_words(x, op: str) -> None:
    if np.dtype(x.dtype).itemsize != 4:
        raise TypeError(f"{op}: stacked words are 32-bit, got {x.dtype}")


@functools.partial(jax.jit, static_argnames=("lowering",))
def _sort_rows_padded_stacked(x: jax.Array, lowering: str) -> jax.Array:
    K, rows, b = x.shape
    if rows == 0:
        return x
    pad = (-rows) % 8
    if pad:
        fill = jnp.full((K, pad, b), jnp.iinfo(x.dtype).max, x.dtype)
        x = jnp.concatenate([x, fill], axis=1)
    out = bitonic.sort_tiles_stacked(
        x, rows_per_tile=8, interpret=lowering == "interpret"
    )
    return out[:, :rows]


@functools.partial(jax.jit, static_argnames=("lowering",))
def _sort_rows_padded(x: jax.Array, lowering: str) -> jax.Array:
    rows, b = x.shape
    if rows == 0:
        return x
    if lowering == "xla":
        return jnp.sort(x, axis=1)
    pad = (-rows) % 8
    if pad:
        fill = jnp.full((pad, b), jnp.iinfo(x.dtype).max, x.dtype)
        x = jnp.concatenate([x, fill], axis=0)
    out = bitonic.sort_tiles(
        x, rows_per_tile=8, interpret=lowering == "interpret"
    )
    return out[:rows]


def sort_rows(x: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Sort each row of (rows, B); B power of two."""
    return _kernel_only(_sort_rows, x, interpret, "sort_rows", x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sort_rows(x: jax.Array, interpret: bool) -> jax.Array:
    return bitonic.sort_tiles(
        x, rows_per_tile=_row_tile(x.shape[0]), interpret=interpret
    )


def sort_rows_kv(
    keys: jax.Array, vals: jax.Array, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Row-wise key-value sort (MoE dispatch: key=expert id, val=token idx)."""
    return _kernel_only(
        _sort_rows_kv, keys, interpret, "sort_rows_kv", keys, vals
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sort_rows_kv(
    keys: jax.Array, vals: jax.Array, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    return bitonic.sort_tiles_kv(
        keys, vals, rows_per_tile=_row_tile(keys.shape[0]),
        interpret=interpret,
    )


def merge_rows(
    a: jax.Array, b: jax.Array, interpret: bool | None = None
) -> jax.Array:
    """Row-wise merge of two sorted (rows, B) arrays -> (rows, 2B)."""
    return _kernel_only(_merge_rows, a, interpret, "merge_rows", a, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _merge_rows(a: jax.Array, b: jax.Array, interpret: bool) -> jax.Array:
    return bitonic.merge_tiles(
        a, b, rows_per_tile=_row_tile(a.shape[0]), interpret=interpret
    )


def merge_tournament(x: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Merge ``P`` padded sorted rows (P, B) into one sorted (P*B,) stream —
    the run-arena engine's one device call per segment.  A 3-D ``x`` of
    ``K`` stacked 32-bit word arrays ``(K, P, B)`` merges as ``K``-word
    tuples into ``(K, P*B)`` (:func:`_merge_stacked`).

    Rows are runs padded with the dtype max (pads stay at row tails through
    every round and are sliced off by the caller); each round merges adjacent
    row pairs with the log-depth bitonic *merge* network, so the whole
    tournament is ``sum_r log2(2^r B)`` compare-exchange stages instead of a
    fresh log² sort.  P and B must be powers of two — the shape-bucketing
    contract that keeps the jit cache to a handful of compiled shapes.

    On TPU the matrix stays VMEM-resident for all rounds in one Pallas call
    (:func:`repro.kernels.bitonic.tournament_tiles`; keys narrower than 32
    bits are widened to int32 for it, which keeps their dtype-max pads
    maximal); ``P * B`` above :data:`~repro.kernels.bitonic.
    TOURNAMENT_MAX_ELEMS` raises there — the caller splits such a bucket.
    64-bit keys, and every call off-TPU, lower the *identical* stage
    schedule through XLA (:func:`repro.kernels.bitonic.
    tournament_merge_array`).
    """
    _check_sort_keys(x, "merge_tournament")
    P, B = x.shape[-2:]
    if P & (P - 1) or B & (B - 1):
        raise ValueError(f"tournament shape must be powers of two, got {x.shape}")
    if x.ndim == 3:
        return _merge_stacked(x)
    lowering = _lowering(x.dtype, interpret, "merge_tournament")
    if lowering == "pallas" and P * B > bitonic.TOURNAMENT_MAX_ELEMS:
        raise ValueError(
            f"tournament {P}x{B} exceeds the kernel's "
            f"{bitonic.TOURNAMENT_MAX_ELEMS}-key VMEM cap; split the bucket"
        )
    with _kernel_scope(lowering):
        return _merge_tournament(x, lowering=lowering)


def _merge_stacked(x: jax.Array) -> jax.Array:
    """The multi-word tournament: ``K`` stacked 32-bit word arrays
    ``(K, P, B)`` merged as ``K``-word tuples into ``(K, P*B)``, in the XLA
    form on every backend: a Pallas kernel of its unrolled rounds, with
    every compare-exchange ``K`` words wide, took seconds to trace in each
    process on a v5e, more than the XLA form's added device time in a
    whole run (PERF.md, Findings)."""
    _check_words(x, "merge_tournament")
    LOWERING_COUNTS[f"merge_tournament:xla:{x.dtype.name}x{x.shape[0]}"] += 1
    return _merge_tournament_stacked(x)


@jax.jit
def _merge_tournament_stacked(x: jax.Array) -> jax.Array:
    return bitonic.tournament_merge_stacked(x)


@functools.partial(jax.jit, static_argnames=("lowering",))
def _merge_tournament(x: jax.Array, lowering: str) -> jax.Array:
    if lowering == "xla":
        return bitonic.tournament_merge_array(x)
    wide = x.astype(jnp.int32) if x.dtype.itemsize < 4 else x
    return bitonic.tournament_tiles(wide, interpret=False).astype(x.dtype)


def flash_attention(
    q, k, v, *, causal=True, scale=None, block_q=512, block_k=512,
    interpret: bool | None = None,
):
    return _flash_attention(
        q, k, v,
        causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
        interpret=_interpret_default(interpret),
    )


def argsort_padded(
    keys: jax.Array, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """1-D argsort via the kv kernel, padding to the next power of two with
    the dtype max (padding sorts to the tail and is sliced off)."""
    (n,) = keys.shape
    m = _next_pow2(max(n, 2))
    pad = m - n
    kp = jnp.concatenate(
        [keys, jnp.full((pad,), jnp.iinfo(keys.dtype).max, keys.dtype)]
    )
    vp = jnp.arange(m, dtype=jnp.int32)
    ks, vs = sort_rows_kv(kp[None, :], vp[None, :], interpret=interpret)
    return ks[0, :n], vs[0, :n]
