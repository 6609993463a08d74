"""Keys wider than 64 bits: Sort Benchmark records through the device epoch.

``run_pipeline`` takes ``(n, B)`` uint8 keys (B = 9 to 16 bytes, memcmp
order) with an ``(n, P)`` payload on ``engine="device"``, and every answer
is held byte for byte to a plain NumPy reference that imports nothing of
the program:

* the order is ``np.lexsort`` over the key bytes, stable by input row;
* the payload is gathered by that order;
* each key's home segment is its place among Alg. 2's equal-width bounds
  over ``[0, 256**B)``, computed as Python ints.

The device epoch's block sort runs the multi-word Pallas kernel (in
interpret mode on the CPU) and the arena merge the multi-word tournament.
Every path that does not take wide keys refuses them, naming the one that
does.
"""

import ctypes

import numpy as np
import pytest

from repro.kernels import ops
from repro.net import run_pipeline

L, K_MERGE = 64, 10


# -- the reference ---------------------------------------------------------


def ref_order(keys: np.ndarray) -> np.ndarray:
    """Stable memcmp order: lexsort's last key is the first byte."""
    return np.lexsort(keys.T[::-1])


def ref_home(keys: np.ndarray, segments: int) -> np.ndarray:
    """Each key's segment among the equal-width bounds, as Python ints."""
    q, r = divmod(256 ** keys.shape[1], segments)
    lows = [s * q + min(s, r) for s in range(segments)]
    home = []
    for key in keys:
        v = int.from_bytes(key.tobytes(), "big")
        home.append(sum(lo <= v for lo in lows[1:]))
    return np.asarray(home, dtype=np.int64)


def ref_words(keys: np.ndarray) -> np.ndarray:
    """Big-endian 32-bit words of the zero-filled key, top bit flipped."""
    n, b = keys.shape
    w = -(-b // 4)
    vals = [
        [int.from_bytes(k[4 * i: 4 * i + 4].tobytes().ljust(4, b"\0"), "big")
         for i in range(w)]
        for k in keys
    ]
    return (np.asarray(vals, dtype=np.uint64) ^ (1 << 31)).astype(
        np.uint32).view(np.int32).reshape(n, w)


# -- the relations ---------------------------------------------------------


def draw(case: str, n: int, key_bytes: int, seed: int):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 256, size=(n, key_bytes), dtype=np.uint8)
    if case == "prefix_ties":
        # A quarter of the keys share their first 8 bytes with another.
        a = rng.choice(n, size=2 * (n // 4), replace=False)
        keys[a[: n // 4], :8] = keys[a[n // 4:], :8]
    elif case == "duplicates":
        # Whole keys repeated: n/8 distinct keys.
        keys = keys[rng.integers(0, max(1, n // 8), n)]
    elif case == "extremes":
        keys[rng.choice(n, n // 8, replace=False)] = 0x00
        keys[rng.choice(n, n // 8, replace=False)] = 0xFF
    elif case == "empty_segments":
        # Only the lowest and highest ranges hold keys.
        keys[:, 0] = np.where(keys[:, 0] < 128, keys[:, 0] % 8, 248 + keys[:, 0] % 8)
    payload = rng.integers(0, 256, size=(n, 90), dtype=np.uint8)
    return keys, payload


CASES = {
    # name: (relation, n, key bytes, segments, extra run_pipeline options)
    "uniform_b10_s16": ("uniform", 1 << 12, 10, 16, {}),
    "prefix_ties_b10_s16": ("prefix_ties", 1 << 14, 10, 16, {}),
    "prefix_ties_b10_s10": ("prefix_ties", 1 << 12, 10, 10, {}),
    "duplicates_b16_s10": ("duplicates", 1 << 12, 16, 10, {}),
    "extremes_b16_s16": ("extremes", 1 << 10, 16, 16, {}),
    "extremes_b10_s10": ("extremes", 1 << 10, 10, 10, {}),
    "empty_segments_b10_s16": ("empty_segments", 1 << 12, 10, 16, {}),
    "n_below_L_b10_s16": ("uniform", 40, 10, 16, {}),
    "n_below_L_b16_s10": ("duplicates", 17, 16, 10, {}),
    "leaf_spine_two_servers_b10_s10": (
        "prefix_ties", 1 << 12, 10, 10,
        {"topology": "leaf_spine", "num_leaves": 2, "num_flows": 3,
         "num_servers": 2},
    ),
}


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("case", list(CASES))
def test_wide_keys_match_the_reference(case):
    relation, n, key_bytes, segments, extra = CASES[case]
    keys, payload = draw(relation, n, key_bytes, seed=n + key_bytes)
    kw = dict(topology="single", num_flows=1, payload_size=256)
    kw.update(extra)
    res = run_pipeline(
        keys.copy(), payload=payload.copy(), num_segments=segments,
        segment_length=L, k=K_MERGE, range_mode="static", engine="device",
        backend="pallas", merge_backend="arena", seed=3, **kw,
    )
    order = ref_order(keys)
    assert res.output.dtype == np.uint8 and res.output.shape == keys.shape
    np.testing.assert_array_equal(res.output, keys[order])
    np.testing.assert_array_equal(res.sorted_payload, payload[order])
    np.testing.assert_array_equal(res.payload_row_order, order)
    wire = res.delivered
    rows = np.asarray(wire.row_index)
    np.testing.assert_array_equal(np.sort(rows), np.arange(n))
    home = ref_home(keys, segments)
    np.testing.assert_array_equal(wire.segment_id, home[rows])
    np.testing.assert_array_equal(wire.values, ref_words(keys[rows]))
    for s, got in enumerate(res.segment_multisets):
        want = ref_words(keys[order][home[order] == s])
        np.testing.assert_array_equal(got[np.lexsort(got.T[::-1])], want)
    assert res.hop_stats[-1].arrivals == n


# -- the multi-word kernels ------------------------------------------------


def _words(rng, k: int, rows: int, cols: int) -> np.ndarray:
    """``k`` stacked int32 word arrays with many ties in the first words and
    a distinct last word, as the device epoch's rows ride."""
    x = rng.integers(-3, 3, size=(k, rows, cols)).astype(np.int32)
    x[0, :, ::3] = np.iinfo(np.int32).max
    x[1, :, ::5] = np.iinfo(np.int32).min
    x[-1] = rng.permutation(rows * cols).reshape(rows, cols)
    return x


def _lex_rows(x: np.ndarray) -> np.ndarray:
    """Each row of the stacked words sorted as tuples, by np.lexsort."""
    out = np.empty_like(x)
    for r in range(x.shape[1]):
        o = np.lexsort(x[::-1, r, :])
        out[:, r, :] = x[:, r, o]
    return out


@pytest.mark.parametrize("k,rows,cols", [(4, 13, 64), (5, 8, 32), (2, 3, 16)])
def test_multi_word_block_sort_matches_lexsort(k, rows, cols):
    x = _words(np.random.default_rng(k * rows), k, rows, cols)
    ops.reset_lowering_counts()
    got = np.asarray(ops.sort_rows_padded(x, interpret=True))
    np.testing.assert_array_equal(got, _lex_rows(x))
    assert dict(ops.LOWERING_COUNTS) == {
        f"sort_rows_padded:interpret:int32x{k}": 1
    }


def test_one_key_network_stays_min_max():
    """For one key array the network is today's min/max network: the same
    result as a plain row sort, the kv path untouched."""
    x = np.random.default_rng(0).integers(-50, 50, (16, 64)).astype(np.int32)
    got = np.asarray(ops.sort_rows_padded(x, interpret=True))
    np.testing.assert_array_equal(got, np.sort(x, axis=1))


@pytest.mark.parametrize("k,P,B", [(4, 8, 64), (3, 4, 256), (5, 2, 16)])
def test_multi_word_tournament_matches_lexsort(k, P, B):
    rng = np.random.default_rng(P * B + k)
    x = _words(rng, k, P, B)
    runs = _lex_rows(x)
    pad = np.iinfo(np.int32).max
    runs[:, 1, B // 2:] = pad  # a short run, padded with all-max tuples
    ops.reset_lowering_counts()
    got = np.asarray(ops.merge_tournament(runs))
    assert dict(ops.LOWERING_COUNTS) == {f"merge_tournament:xla:int32x{k}": 1}
    flat = runs.reshape(k, -1)
    want = flat[:, np.lexsort(flat[::-1])]
    np.testing.assert_array_equal(got, want)


def test_wide_runs_merge_on_the_host_in_memcmp_order():
    """Below the device threshold, runs of word rows merge as byte strings;
    zero bytes inside and at the end of a word keep their order."""
    from repro.core.mergesort import merge_runs_flat

    rng = np.random.default_rng(5)
    rows = rng.integers(-2, 2, size=(600, 4)).astype(np.int32)
    rows[::7, 1] = 0
    rows[:, -1] = np.arange(600)
    parts = np.array_split(rows, 9)
    parts = [p[np.lexsort(p.T[::-1])] for p in parts]
    buf = np.concatenate(parts)
    lengths = np.array([len(p) for p in parts])
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    got = merge_runs_flat(buf, starts, lengths)
    np.testing.assert_array_equal(got, rows[np.lexsort(rows.T[::-1])])


class _MallInfo2(ctypes.Structure):
    _fields_ = [(name, ctypes.c_size_t) for name in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def test_a_records_job_keeps_freed_host_memory():
    """After a records job the process keeps the large blocks it frees:
    a 64 MiB array comes from the heap, not from an mmap of its own, and
    the heap keeps it once freed (glibc's ``mallinfo2``)."""
    from repro import hostmem

    keys, payload = draw("uniform", 64, 10, 3)
    run_pipeline(keys, payload=payload, engine="device",
                 merge_backend="arena", segment_length=L)
    assert hostmem.keep_freed_memory()
    mallinfo2 = ctypes.CDLL(None).mallinfo2
    mallinfo2.restype = _MallInfo2
    before = mallinfo2()
    block = np.ones(1 << 23)
    held = mallinfo2()
    del block
    after = mallinfo2()
    assert held.hblks == before.hblks
    assert held.uordblks >= before.uordblks + (1 << 26)
    assert after.arena == held.arena


def test_range_table_is_exact_at_every_edge():
    from repro.core.partition import key_words, set_ranges_wide, words_to_keys

    for key_bytes, segments in [(10, 16), (10, 10), (16, 3), (9, 7)]:
        q, r = divmod(256**key_bytes, segments)
        lows = [s * q + min(s, r) for s in range(segments)]
        table = set_ranges_wide(key_bytes, segments)
        back = words_to_keys(table, key_bytes)
        assert [int.from_bytes(b.tobytes(), "big") for b in back] == lows
        keys = np.array(
            [list(v.to_bytes(key_bytes, "big"))
             for lo in lows[1:] for v in (lo - 1, lo)],
            dtype=np.uint8,
        )
        np.testing.assert_array_equal(key_words(keys), ref_words(keys))


# -- refusals --------------------------------------------------------------


def _refusals():
    from repro.net import ControlPlane
    from repro.net.timing import NetworkConfig
    from repro.obs import MetricsRegistry

    return {
        "engine_fused": {"engine": "fused"},
        "engine_segment": {"engine": "segment"},
        "engine_faithful": {"engine": "faithful"},
        "engine_default": {"engine": None},
        "range_oracle": {"range_mode": "oracle"},
        "range_sampled": {"range_mode": "sampled"},
        "control": {"range_mode": None, "control": ControlPlane()},
        "network": {"network": NetworkConfig()},
        "fault_plan": {"fault_plan": "crash:switch@0"},
        "jitter_window": {"jitter_window": 4},
        "int_telemetry": {"int_telemetry": True},
        "metrics": {"metrics": MetricsRegistry()},
        "merge_numpy": {"merge_backend": "numpy"},
        "pool_shard_map": {"pool_backend": "shard_map"},
        "max_value": {"max_value": 1000},
    }


REFUSALS = [
    "engine_fused", "engine_segment", "engine_faithful", "engine_default",
    "range_oracle", "range_sampled", "control", "network", "fault_plan",
    "jitter_window", "int_telemetry", "metrics", "merge_numpy",
    "pool_shard_map", "max_value",
]


@pytest.mark.parametrize("name", REFUSALS)
def test_unwidened_paths_refuse_wide_keys(name):
    keys, payload = draw("uniform", 256, 10, seed=1)
    kw = dict(
        num_segments=16, segment_length=L, range_mode="static",
        engine="device", backend="pallas", merge_backend="arena",
    )
    kw.update(_refusals()[name])
    with pytest.raises(ValueError, match='engine="device"'):
        run_pipeline(keys, payload=payload, **kw)
