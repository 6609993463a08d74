"""The copied trace generators draw the same keys for the same seed, for
good: a digest of each one's output is pinned."""

import hashlib

import numpy as np
import pytest

import traffic

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
