"""The traffic generator: deterministic per seed, with the stated shapes."""
import collections
import os

import pytest

from harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T_MIN, T_MAX, B_T = 0.0, 90 * 86400.0, 0.25 * 90 * 86400.0


def mix(name):
    return traffic.Mix.load(os.path.join(BENCH, "traffic", f"{name}.json"))


def stream(seed, n=400):
    s = traffic.closed_stream(mix("analyst"), seed, t_min=T_MIN, t_max=T_MAX,
                              b_t=B_T)
    return [next(s) for _ in range(n)]


def test_closed_stream_is_deterministic_per_seed():
    big = 2**33
    assert stream(big) == stream(big)
    assert stream(big) != stream(big + 1)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_closed_stream_centers_are_fresh_and_in_range(seed):
    reqs = stream(seed)
    flat = [t for r in reqs for t in r]
    assert len(set(flat)) == len(flat)
    assert min(flat) >= T_MIN + B_T and max(flat) <= T_MAX - B_T
    # every seed asks for the same window counts, in the same order
    assert [len(r) for r in reqs[:8]] == [1, 2, 3, 4, 1, 2, 3, 4]
    assert collections.Counter(len(r) for r in reqs) == {1: 100, 2: 100, 3: 100, 4: 100}


@pytest.mark.parametrize("body", [
    '{"loop": "open", "rate_hz": 1, "windows_per_request": [1], '
    '"centers": {"kind": "uniform", "lo": ["t_min", 0], "hi": ["t_max", 0]}}',
    '{"loop": "closed", "windows_per_request": [1], '
    '"centers": {"kind": "uniform", "lo": ["t_min", 0], "hi": ["t_max", 0]}}',
    '{"loop": "closed", "clients": 2, "windows_per_request": [1], '
    '"centers": {"kind": "lattice"}}',
])
def test_mix_files_are_checked(tmp_path, body):
    p = tmp_path / "bad.json"
    p.write_text(body)
    with pytest.raises(ValueError):
        traffic.Mix.load(str(p))
