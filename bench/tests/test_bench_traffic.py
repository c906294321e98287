"""The benchmark's copy of the traffic generator and the percentile."""
import math

import pytest

from bench import traffic


def test_poisson_same_seed_same_trace():
    a = traffic.poisson_offsets(150.0, 10.0, seed=2**31 + 3)
    assert a == traffic.poisson_offsets(150.0, 10.0, seed=2**31 + 3)
    assert a != traffic.poisson_offsets(150.0, 10.0, seed=2**31 + 4)


def test_poisson_same_work_for_every_seed():
    """Every seed offers the same count and the same gaps, in another order."""
    runs = [traffic.poisson_offsets(150.0, 10.0, seed=s) for s in (1, 2, 2**40)]
    gaps = [sorted(b - a for a, b in zip([0.0] + r[:-1], r)) for r in runs]
    assert {len(r) for r in runs} == {1500}
    for g in gaps[1:]:
        assert g == pytest.approx(gaps[0], rel=1e-9)
    for r in runs:
        assert r[-1] == pytest.approx(10.0)
        assert all(b > a for a, b in zip(r, r[1:]))


def test_poisson_gaps_are_exponential():
    r = traffic.poisson_offsets(200.0, 20.0, seed=9)
    gaps = [b - a for a, b in zip([0.0] + r[:-1], r)]
    mean = sum(gaps) / len(gaps)
    assert mean == pytest.approx(1 / 200.0, rel=1e-3)
    # an exponential's median is ln 2 times its mean
    assert traffic.percentile(gaps, 50) == pytest.approx(math.log(2) * mean, rel=0.02)


def test_mmpp_matches_program_generator():
    from repro.serving.loadgen import mmpp_trace

    kw = dict(duration_s=20.0, calm_s=2.0, burst_s=0.5, seed=77)
    ours = traffic.mmpp_offsets(100.0, 300.0, kw["duration_s"], kw["calm_s"],
                                kw["burst_s"], kw["seed"])
    theirs = mmpp_trace(100.0, 300.0, **kw).times
    assert tuple(ours) == theirs
    assert ours == traffic.arrival_offsets(
        {"process": "mmpp", "calm_rate": 100.0, "burst_rate": 300.0,
         "calm_s": 2.0, "burst_s": 0.5}, 20.0, 77)


def test_unknown_process_raises():
    with pytest.raises(ValueError):
        traffic.arrival_offsets({"process": "uniform"}, 1.0, 0)


def test_percentile_matches_serving_fixtures():
    """The fixtures of tests/test_serving.py::test_percentile_nearest_rank_pinned."""
    p = traffic.percentile
    assert p([], 50) == 0.0
    assert p([7.0], 99) == 7.0
    assert p([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert p([10.0, 20.0], 50) == 10.0
    assert p([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    xs = [float(i) for i in range(1, 101)]
    assert p(xs, 50) == 50.0
    assert p(xs, 95) == 95.0
    assert p(xs, 99) == 99.0
    assert p(xs, 100) == 100.0
    assert p(xs, 0) == 1.0
    win = [0.010, 0.012, 0.011, 0.013, 0.050, 0.012, 0.011, 0.012]
    assert p(win, 50) == 0.012
    assert p(win, 95) == 0.050
    assert p(win, 99) == 0.050
    assert p(list(reversed(xs)), 95) == 95.0


def test_percentile_agrees_with_program():
    from repro.serving import percentile

    xs = [0.3, 0.1, 0.7, 0.2, 0.9, 0.4, 0.4]
    for q in (0, 10, 50, 90, 95, 99, 100):
        assert traffic.percentile(xs, q) == percentile(xs, q)
