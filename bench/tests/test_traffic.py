"""The copied trace generators, and the Sort Benchmark record draw, draw
the same relation for the same seed, for good: a digest of each one's
output is pinned."""

import hashlib

import numpy as np
import pytest

import traffic
from relations import gensort

DIGESTS = {
    "random": "10f868c01aa34786f6c6096ffb63bc5cb833ab3a282550e863c653eb6c58a554",
    "network": "91b511d3ba47e47316c36d72b6eabe8d48fe843379d91105127170d66fad4c75",
    "memory": "eedae482d3512e33c09846f562293e287fc297ab2fbd59653046aef940cb068d",
}
UNIQUES = {"random": 32_768, "network": 1_475, "memory": 368}


def _digest(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_keys_digest_is_pinned(name):
    keys = traffic.draw_keys(traffic.load(name), 100_000, 2026)
    assert keys.dtype == np.int64
    assert _digest(keys.astype("<i8")) == DIGESTS[name]


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_keys_stay_in_the_value_set(name):
    params = traffic.load(name)
    keys = traffic.draw_keys(params, 200_000, 7)
    v = params["values"]
    assert keys.min() >= v["start"] and keys.max() <= traffic.max_value(params)
    assert np.all((keys - v["start"]) % v["step"] == 0)
    assert v["count"] == UNIQUES[name]


def test_large_and_negative_seeds_draw():
    params = traffic.load("random")
    big = traffic.draw_keys(params, 1000, 2**31 + 12345)
    assert np.array_equal(big, traffic.draw_keys(params, 1000, 2**31 + 12345))
    assert not np.array_equal(big, traffic.draw_keys(params, 1000, 2**31 + 12346))
    assert traffic.draw_keys(params, 10, -5).size == 10


GENSORT_DIGEST = (
    "13ffb325db99b1c8ca2dcc9cf6759ebe2682218cec3e8ce88a9943393309f3c1"
)


def _records(n, seed):
    return gensort.draw(traffic.load("gensort_uniform"), n, seed)


def test_gensort_digest_is_pinned():
    keys, payload = _records(65_536, 2026)
    assert keys.shape == (65_536, 10) and payload.shape == (65_536, 90)
    assert keys.dtype == payload.dtype == np.uint8
    assert hashlib.sha256(
        keys.tobytes() + payload.tobytes()
    ).hexdigest() == GENSORT_DIGEST
    assert bytes(payload[0x1234]) == (
        b"\x00\x11" + b"0" * 28 + b"1234" + b"\x88\x99\xaa\xbb"
        + b"0123456789ABCDEF" * 3 + b"\xcc\xdd\xee\xff"
    )


def test_gensort_draw_follows_the_seed():
    keys, payload = _records(1000, 2**31 + 12345)
    again, payload_again = _records(1000, 2**31 + 12345)
    other, _ = _records(1000, 2**31 + 12346)
    assert np.array_equal(keys, again)
    assert np.array_equal(payload, payload_again)
    assert not np.array_equal(keys, other)


@pytest.mark.parametrize("n", [4096, 1 << 16])
def test_gensort_draw_ties_first_eight_bytes_and_never_the_whole_key(n):
    params = traffic.load("gensort_uniform")
    keys, _ = gensort.draw(params, n, 2**31 + 99)
    ties = round(params["tie_share"] * n)
    assert ties >= 16
    hi, lo = gensort.split_key(keys)
    _, first8 = np.unique(hi, return_counts=True)
    assert (first8 == 2).sum() == ties and first8.max() == 2
    assert np.unique(keys, axis=0).shape[0] == n


@pytest.mark.parametrize("segments", [1, 3, 10, 16, 1000, 2**16 + 1])
def test_gensort_segments_agree_with_python_ints_at_every_edge(segments):
    """A key just below a bound shares its first eight bytes with the bound
    unless the bound's last two bytes are 0 (16 segments), so there the
    last two decide."""
    domain = 256**10
    q, r = divmod(domain, segments)
    edge = r * (q + 1)
    bounds = gensort.segment_bounds(10, segments)
    assert bounds[-1] == domain and len(bounds) == segments
    ints = sorted({0, domain - 1} | {
        k for b in bounds[:-1] for k in (b - 1, b)
    })
    keys = np.array(
        [list(k.to_bytes(10, "big")) for k in ints], dtype=np.uint8
    )
    want = [k // (q + 1) if k < edge else r + (k - edge) // q for k in ints]
    assert gensort.home_segments(keys, segments).tolist() == want
