"""Bitonic sorting network as a Pallas TPU kernel — the MergeMarathon segment.

The paper's segment is a pipeline of ``y`` match-action stages doing one
compare-swap each, with strictly stage-local memory (RMT).  The TPU-native
equivalent (DESIGN.md §2) is a **bitonic network** over a VMEM-resident tile:
a fixed, data-independent sequence of ``log²(B)`` compare-exchange stages,
each stage a full-width vectorized min/max — i.e. the same hardware idea
(systolic compare-exchange with local memory) at VREG width instead of
packet width.  With tile == segment_length this computes *exactly* the
MergeMarathon emitted stream (see repro.core.marathon).

The run-arena tournament has two forms: the Pallas kernel
(:func:`tournament_tiles`) and its XLA form (:func:`tournament_merge_array`),
used for 64-bit keys and off-TPU.  Keys wider than 32 bits that are not
packed (the dataplane's wide keys) run as ``K`` stacked int32 word arrays:
the block sort kernel :func:`sort_tiles_stacked` and the tournament's XLA
form :func:`tournament_merge_stacked` take one compare-exchange decision
per pair, lexicographic over the words (:func:`lex_gt_lt`), and move every
word with it.  The Pallas kernels never reshape the
lane axis or reverse a value — Mosaic lowers neither — so in a kernel a
stage finds each element's partner with two ``pltpu.roll`` rotations and
an ``iota`` index mask (:func:`_network`), and pairs whole rows by aligned
row slices when the stride spans rows.  Tiles are (rows, B) with B a power
of two; the MXU is not involved (sorting is a VPU workload).  The kernels
are traced with x64 off (``repro.kernels.ops`` sees to it): under an x64
scope every bare int in them would become an int64 scalar, which Mosaic
rejects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _stages(n: int):
    """The bitonic network schedule: (k, j) compare-exchange stages."""
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            yield k, j
            j //= 2
        k *= 2


def _merge_stages(n: int):
    """The merge network alone (the last k-round): ascending everywhere."""
    j = n // 2
    while j >= 1:
        yield n, j
        j //= 2


def _reverse_odd_rows(x: jax.Array) -> jax.Array:
    """Sorted runs as rows (the last two axes), odd rows reversed: adjacent
    row pairs are then bitonic, which is the state a bitonic sort reaches
    after its rounds k <= B.  Done in XLA: Pallas TPU has no lowering for
    ``lax.rev``."""
    odd = (jnp.arange(x.shape[-2]) % 2 == 1)[:, None]
    return jnp.where(odd, jnp.flip(x, axis=-1), x)


def tournament_merge_array(x: jax.Array) -> jax.Array:
    """Merge all ``P`` sorted rows of ``x`` (P, B) into one sorted (P*B,) row
    — the XLA form of :func:`tournament_tiles`, for 64-bit keys and off-TPU.

    The run-arena merge engine: rows are padded sorted runs (pads = dtype
    max).  With the odd rows reversed, the bitonic rounds k = 2B .. P*B over
    the flat sequence finish the sort; each round merges adjacent run pairs,
    log²-free.  The stages run as one loop over a (k, j) table, each finding
    every element's partner ``i ^ j`` with two rotations, so the compiled
    program does not grow with P: unrolled, the int64 network at 16384x64
    took minutes to compile for a TPU.  P and B powers of two.
    """
    P, B = x.shape
    if P & (P - 1) or B & (B - 1):
        raise ValueError(f"tournament shape must be powers of two, got {x.shape}")
    _check_integer(x.dtype)
    n = P * B
    v = _reverse_odd_rows(x).reshape(n)
    stages = [(k, j) for k, j in _stages(n) if k > B]
    if not stages:
        return v
    ks, js = (jnp.asarray(col, jnp.int32) for col in zip(*stages))
    g = jax.lax.iota(jnp.int32, n)

    def stage(s, v):
        k, j = ks[s], js[s]
        lower = (g & j) == 0
        p = jnp.where(lower, jnp.roll(v, -j), jnp.roll(v, j))
        take_min = lower == ((g & k) == 0)
        return jnp.where(take_min, jnp.minimum(v, p), jnp.maximum(v, p))

    return jax.lax.fori_loop(0, len(stages), stage, v)


def tournament_merge_stacked(x: jax.Array) -> jax.Array:
    """:func:`tournament_merge_array` for ``K`` stacked word arrays
    ``(K, P, B)``: every compare-exchange is lexicographic over the ``K``
    words (word 0 most significant) and moves all of them.  Returns the
    merged ``(K, P*B)`` words.  Pads are all-``dtype max`` tuples."""
    K, P, B = x.shape
    if P & (P - 1) or B & (B - 1):
        raise ValueError(f"tournament shape must be powers of two, got {x.shape}")
    _check_integer(x.dtype)
    n = P * B
    v = _reverse_odd_rows(x).reshape(K, n)
    stages = [(k, j) for k, j in _stages(n) if k > B]
    if not stages:
        return v
    ks, js = (jnp.asarray(col, jnp.int32) for col in zip(*stages))
    g = jax.lax.iota(jnp.int32, n)

    def stage(s, v):
        k, j = ks[s], js[s]
        lower = (g & j) == 0
        p = jnp.where(lower, jnp.roll(v, -j, axis=1), jnp.roll(v, j, axis=1))
        take_min = lower == ((g & k) == 0)
        gt, lt = lex_gt_lt(tuple(v), tuple(p))
        return jnp.where(jnp.where(take_min, ~gt, ~lt), v, p)

    return jax.lax.fori_loop(0, len(stages), stage, v)


def _check_integer(dtype) -> None:
    if jnp.dtype(dtype).kind not in "iu":
        # The row pads are the dtype max; only integer keys have a total
        # order in which that sentinel is guaranteed maximal (float NaNs
        # break the compare-exchange invariant silently).
        raise TypeError(f"tournament merges integer keys only, got dtype {dtype}")


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _partner(xs, g, j: int, shift: int, axis: int):
    """Each array of ``xs`` at flat index ``g ^ j``.

    ``g`` holds every element's flat index in the network.  Rotating by
    ``shift`` both ways puts the elements at ``g - j`` and ``g + j`` under
    each slot; rotating ``g`` the same way says which of the two is the
    partner, so the result does not depend on the rotation's direction
    convention.  When ``shift`` is half the axis both rotations agree.
    """
    n = xs[0].shape[axis]
    hit = pltpu.roll(g, shift, axis) == (g ^ j)
    return tuple(
        jnp.where(hit, pltpu.roll(x, shift, axis), pltpu.roll(x, n - shift, axis))
        for x in xs
    )


def lex_gt_lt(a, b):
    """Lexicographic ``a > b`` and ``a < b`` over the word tuples ``a`` and
    ``b`` (first word most significant), from elementwise compares alone."""
    gt = a[-1] > b[-1]
    lt = a[-1] < b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        eq = x == y
        gt = (x > y) | (eq & gt)
        lt = (x < y) | (eq & lt)
    return gt, lt


def _network(xs, g, stages, width: int, num_keys: int = 1):
    """Apply compare-exchange ``stages`` to the keys ``xs[:num_keys]`` (and
    the values riding with them, ``xs[num_keys:]``) of one block.

    ``g`` is the flat network index of each element of the block, laid out
    row-major with ``width`` lanes per row: a stride below ``width`` pairs
    lanes within a row, a larger one pairs rows.  The direction of each
    pair ascends iff ``(g & k) == 0``.  One key alone takes the pair's
    min/max; otherwise the keys' swap decision, lexicographic over the
    ``num_keys`` key arrays (the first most significant), moves every
    array (for one key the result is the same as the XLA form's, element
    for element).
    """
    for k, j in stages:
        if j < width:
            p = _partner(xs, g, j, j, 1)
        else:
            p = _partner(xs, g, j, j // width, 0)
        lower = (g & j) == 0
        lo = tuple(jnp.where(lower, x, q) for x, q in zip(xs[:num_keys], p))
        hi = tuple(jnp.where(lower, q, x) for x, q in zip(xs[:num_keys], p))
        if len(xs) == 1:
            take_min = ((g >> (j.bit_length() - 1)) ^ (g >> (k.bit_length() - 1))) & 1 == 0
            xs = (jnp.where(
                take_min, jnp.minimum(lo[0], hi[0]), jnp.maximum(lo[0], hi[0])
            ),)
        else:
            asc = (g & k) == 0
            gt, lt = lex_gt_lt(lo, hi)
            # where() over booleans has no Mosaic lowering; and/or does.
            swap = (asc & gt) | (~asc & lt)
            xs = tuple(jnp.where(swap, q, x) for x, q in zip(xs, p))
    return xs


def _lane_index(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _rows_kernel(*refs, stages):
    """Independent rows: every stage pairs lanes within one row."""
    n_in = len(refs) // 2
    xs = tuple(r[...] for r in refs[:n_in])
    width = xs[0].shape[1]
    out = _network(xs, _lane_index(xs[0].shape), stages, width)
    for r, v in zip(refs[n_in:], out):
        r[...] = v


def _rows_call(xs, stages, rows_per_tile: int, interpret: bool):
    rows, n = xs[0].shape
    if rows % rows_per_tile:
        raise ValueError(f"rows {rows} % rows_per_tile {rows_per_tile} != 0")
    spec = pl.BlockSpec((rows_per_tile, n), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_rows_kernel, stages=tuple(stages)),
        out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs),
        grid=(rows // rows_per_tile,),
        in_specs=[spec] * len(xs),
        out_specs=tuple([spec] * len(xs)),
        interpret=interpret,
    )(*xs)


def _stacked_rows_kernel(x_ref, o_ref, *, stages):
    """Independent rows of ``K`` stacked word arrays, sorted lexicographically
    over all ``K`` (word 0 most significant)."""
    xs = tuple(x_ref[i] for i in range(x_ref.shape[0]))
    width = xs[0].shape[1]
    out = _network(xs, _lane_index(xs[0].shape), stages, width, len(xs))
    for i, v in enumerate(out):
        o_ref[i] = v


def sort_tiles_stacked(
    x: jax.Array,
    *,
    rows_per_tile: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """Sort each row of the ``K`` stacked arrays ``x`` (K, rows, B) as one
    sequence of ``K``-word tuples, compared lexicographically with word 0
    most significant: the multi-word block sort.

    One input and one output, so a profiler's result shape holds every
    word.  BlockSpec tiles (K, rows_per_tile, B) into VMEM; B power of two.
    """
    K, rows, n = x.shape
    _check_pow2(n)
    if rows % rows_per_tile:
        raise ValueError(f"rows {rows} % rows_per_tile {rows_per_tile} != 0")
    spec = pl.BlockSpec((K, rows_per_tile, n), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_stacked_rows_kernel, stages=tuple(_stages(n))),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(rows // rows_per_tile,),
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
    )(x)


def _check_pow2(n: int) -> None:
    if n & (n - 1):
        raise ValueError(f"bitonic length must be a power of two, got {n}")


def sort_tiles(
    x: jax.Array,
    *,
    rows_per_tile: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """Sort each row of ``x`` (rows, B) with the bitonic kernel.

    BlockSpec tiles (rows_per_tile, B) into VMEM; B power of two.  VMEM
    working set = rows_per_tile * B * itemsize (plus the network's
    temporaries) — callers pick rows_per_tile so this stays ≪ 16 MB.
    """
    _check_pow2(x.shape[1])
    return _rows_call((x,), _stages(x.shape[1]), rows_per_tile, interpret)[0]


def sort_tiles_kv(
    keys: jax.Array,
    vals: jax.Array,
    *,
    rows_per_tile: int = 8,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Key-value tile sort (the MoE dispatch primitive: keys=expert ids,
    vals=token indices)."""
    if keys.shape != vals.shape:
        raise ValueError("keys/vals shape mismatch")
    _check_pow2(keys.shape[1])
    return _rows_call(
        (keys, vals), _stages(keys.shape[1]), rows_per_tile, interpret
    )


def merge_tiles(
    a: jax.Array,
    b: jax.Array,
    *,
    rows_per_tile: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """Merge row-wise sorted ``a`` and ``b`` (rows, B) -> (rows, 2B).

    The XLA wrapper lays out ``concat(a, reverse(b))`` — bitonic — and the
    kernel runs the log(2B)-stage merge network over it.
    """
    if a.shape != b.shape:
        raise ValueError("a/b shape mismatch")
    _check_pow2(a.shape[1])
    x = jnp.concatenate([a, jnp.flip(b, axis=-1)], axis=-1)
    return _rows_call(
        (x,), _merge_stages(x.shape[1]), rows_per_tile, interpret
    )[0]


#: Largest tournament (``P * B`` keys) run as one VMEM-resident kernel.
#: The whole matrix sits in VMEM twice (input and working copy): 2 x 16 MiB
#: of int32 at this size, under :data:`TOURNAMENT_VMEM_LIMIT`.  The v5e
#: compile of the largest bucket under this cap is pinned by
#: ``tests/test_tpu_compile.py``; a larger bucket is split by the caller.
TOURNAMENT_MAX_ELEMS = 1 << 22

#: Scoped VMEM the tournament kernel asks for (v5e has 128 MiB per core;
#: the default scope of 16 MiB would cap the tournament at 1M keys).
TOURNAMENT_VMEM_LIMIT = 48 << 20

#: Rows of the (M, 128) tournament layout one loop step loads.
_CHUNK_ROWS = 32


def _tournament_kernel(x_ref, o_ref, *, run: int, chunk: int):
    """Merge the sorted runs of length ``run`` laid end to end in ``x_ref``.

    The (M, W) matrix is the flat key sequence, row-major; odd runs arrive
    reversed (see :func:`tournament_tiles`), so the runs already form the
    state a bitonic sort reaches after its k <= ``run`` rounds, and the
    rounds k = 2*run .. M*W finish the sort in ``o_ref``.  A stage whose
    stride spans ``chunk`` rows or more pairs two aligned row blocks; every
    later stage of the round stays inside one ``chunk``-row block, which is
    loaded once for all of them.
    """
    M, W = o_ref.shape
    n = M * W
    span = chunk * W

    def block_index(r0):
        shape = (chunk, W)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + r0
        return rows * W + _lane_index(shape)

    def local_pass(src, stages):
        def body(c, carry):
            r0 = pl.multiple_of(c * chunk, chunk)
            v = src[pl.ds(r0, chunk), :]
            (out,) = _network((v,), block_index(r0), stages, W)
            o_ref[pl.ds(r0, chunk), :] = out
            return carry

        jax.lax.fori_loop(0, M // chunk, body, 0)

    def cross_pass(k: int, j: int):
        s = j // W  # row stride: a power of two, a multiple of ``chunk``

        def body(i, carry):
            blk = i * chunk
            # (blk // s) * 2s + blk % s, in bit operations: the lowering of
            # integer division under an x64 scope emits int64, which
            # Mosaic rejects.
            r0 = pl.multiple_of(blk + (blk & -s), chunk)
            r1 = pl.multiple_of(r0 + s, chunk)
            lo = o_ref[pl.ds(r0, chunk), :]
            hi = o_ref[pl.ds(r1, chunk), :]
            asc = ((r0 * W) & k) == 0  # constant over the block: k >= 2 * span
            mn = jnp.minimum(lo, hi)
            mx = jnp.maximum(lo, hi)
            o_ref[pl.ds(r0, chunk), :] = jnp.where(asc, mn, mx)
            o_ref[pl.ds(r1, chunk), :] = jnp.where(asc, mx, mn)
            return carry

        jax.lax.fori_loop(0, M // (2 * chunk), body, 0)

    local_pass(x_ref, ())  # copy in: every later pass works in o_ref
    k = 2 * run
    while k <= n:
        j = k // 2
        while j >= span:
            cross_pass(k, j)
            j //= 2
        local_pass(o_ref, tuple((k, jj) for _, jj in _merge_stages(2 * j)))
        k *= 2


def tournament_tiles(x: jax.Array, *, interpret: bool = True) -> jax.Array:
    """Run-arena tournament as one Pallas call: the entire padded run matrix
    lives in VMEM and every merge round happens without touching HBM.

    No grid — rounds couple all rows, so the matrix is a single block.  The
    XLA side reverses the odd rows (Mosaic has no lane reversal) and lays
    the keys out as a lane-dense (P*B/128, 128) matrix, free for any B;
    the kernel then runs the same merge rounds as
    :func:`tournament_merge_array`, and for integer keys the output is the
    same sorted row.  ``P * B`` is capped at :data:`TOURNAMENT_MAX_ELEMS`.
    Keys are 32-bit or narrower: ``ops.merge_tournament`` widens narrower
    ones and sends 64-bit keys to the XLA form.
    """
    P, B = x.shape
    if P & (P - 1) or B & (B - 1):
        raise ValueError(f"tournament shape must be powers of two, got {x.shape}")
    _check_integer(x.dtype)
    n = P * B
    if n > TOURNAMENT_MAX_ELEMS:
        raise ValueError(
            f"tournament matrix {P}x{B} exceeds the VMEM budget "
            f"({TOURNAMENT_MAX_ELEMS} elements)"
        )
    x = _reverse_odd_rows(x)
    W = min(n, 128)
    M = n // W
    out = pl.pallas_call(
        functools.partial(
            _tournament_kernel, run=B, chunk=min(M, _CHUNK_ROWS)
        ),
        out_shape=jax.ShapeDtypeStruct((M, W), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=TOURNAMENT_VMEM_LIMIT
        ),
        interpret=interpret,
    )(x.reshape(M, W))
    return out.reshape(n)
