"""The traffic generator: deterministic per seed, the same work for every
seed, the stated rates, shares and burst shapes; the committed mixes
validate."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(**over):
    m = {"rate_per_s": 9.0, "streams": [
        {"class": "hp", "share": 8 / 9, "arrivals": "poisson",
         "prompt_lens": [32, 64, 128], "new_tokens": 1,
         "deadline_s": 0.062},
        {"class": "lp", "share": 1 / 9, "arrivals": "poisson",
         "prompt_lens": [256], "new_tokens": 128,
         "deadline_s": 1.59}]}
    m.update(over)
    return m


def test_same_seed_same_schedule_and_large_seeds_work():
    a = traffic.generate(mix(), 30.0, 2 ** 40 + 3)
    b = traffic.generate(mix(), 30.0, 2 ** 40 + 3)
    c = traffic.generate(mix(), 30.0, 2 ** 40 + 4)
    assert a == b
    assert a != c


def test_every_seed_gets_the_same_work():
    runs = [traffic.generate(mix(), 30.0, s) for s in (1, 2, 3)]
    for r in runs:
        assert len(r) == 270                      # 9/s * 30 s
        assert sum(a.cls == "hp" for a in r) == 240
        lens = sorted(a.prompt_len for a in r if a.cls == "hp")
        assert lens == sorted(x.prompt_len for x in runs[0] if x.cls == "hp")
        assert all(0.0 <= a.t < 30.0 for a in r)
        assert [a.t for a in r] == sorted(a.t for a in r)
    hp = [a.prompt_len for a in runs[0] if a.cls == "hp"]
    assert {hp.count(p) for p in (32, 64, 128)} == {80}


def test_poisson_gaps_have_the_stated_rate_and_spread():
    rng = np.random.default_rng(0)
    t = traffic.stream_times({"arrivals": "poisson"}, 5.0, 200.0, rng)
    gaps = np.diff(t)
    assert t.size == 1000
    assert gaps.mean() == pytest.approx(0.2, rel=0.01)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_bursts_are_size_requests_within_the_spread():
    s = {"arrivals": "burst", "burst_size": 8, "burst_spread_s": 0.01}
    t = traffic.stream_times(s, 16.0, 10.0, np.random.default_rng(5))
    assert t.size == 160                          # 20 bursts of 8
    bursts = t.reshape(20, 8)
    spread = bursts.max(1) - bursts.min(1)
    assert np.allclose(spread, 0.01)
    assert t.max() < 10.0


def test_periodic_arrivals_keep_one_period_from_a_drawn_phase():
    s = {"arrivals": "periodic"}
    a = traffic.stream_times(s, 0.5, 30.0, np.random.default_rng(1))
    b = traffic.stream_times(s, 0.5, 30.0, np.random.default_rng(2))
    assert a.size == b.size == 15
    assert np.allclose(np.diff(a), 2.0) and np.allclose(np.diff(b), 2.0)
    assert a[0] != b[0] and 0.0 <= a[0] < 2.0 and a[-1] < 30.0


def test_stratified_arrivals_meet_each_period_at_the_same_phases():
    """216 HP in 8-bin cycles beside 27 LP frames: one HP per bin, and the
    HP phases within the LP period are the same evenly spaced set for
    every seed; only their order differs."""
    m = mix(rate_per_s=243 / 51, streams=[
        dict(mix()["streams"][0], arrivals="stratified", cycle=8),
        dict(mix()["streams"][1], arrivals="periodic")])
    period = 51.0 / 27
    orders = []
    for seed in (1, 2, 2 ** 40 + 3):
        a = traffic.generate(m, 51.0, seed)
        hp = np.array([x.t for x in a if x.cls == "hp"])
        lp = np.array([x.t for x in a if x.cls == "lp"])
        assert hp.size == 216 and lp.size == 27
        assert np.array_equal(np.floor(hp / (51.0 / 216)), np.arange(216))
        phase = np.sort((hp - lp[0]) % period) / period
        assert np.allclose(np.diff(phase), 1 / 216)
        assert phase[0] < 1 / 216
        orders.append(hp)
    assert not np.allclose(orders[0], orders[1])


def test_rate_override_scales_every_stream():
    r = traffic.generate(mix(), 10.0, 1, rate_per_s=18.0)
    assert len(r) == 180
    assert sum(a.cls == "lp" for a in r) == 20


def test_shapes_and_deadlines():
    assert traffic.shapes(mix()) == {("hp", 32, 1), ("hp", 64, 1),
                                     ("hp", 128, 1), ("lp", 256, 128)}
    hp, lp = mix()["streams"]
    assert traffic.relative_deadline(hp) == pytest.approx(0.062)
    assert traffic.relative_deadline(lp) == pytest.approx(1.59)


@pytest.mark.parametrize("bad", [
    {"rate_per_s": 0},
    {"streams": []},
    {"streams": [dict(mix()["streams"][0], share=0.5)]},
    {"streams": [dict(mix()["streams"][0], share=1.0, **{"class": "mid"})]},
    {"streams": [dict(mix()["streams"][0], share=1.0, new_tokens=0)]},
    {"streams": [dict(mix()["streams"][0], share=1.0, deadline_s=0)]},
    {"streams": [dict(mix()["streams"][0], share=1.0, cycle=0)]},
])
def test_malformed_mixes_are_refused(bad):
    with pytest.raises((ValueError, KeyError)):
        traffic.generate(mix(**bad), 10.0, 1)


@pytest.mark.parametrize("path", sorted(MIXES.glob("*.json")),
                         ids=lambda p: p.stem)
def test_committed_mixes_validate(path):
    m = json.loads(path.read_text())
    traffic.validate(m)
    assert traffic.generate(m, 5.0, 7)
