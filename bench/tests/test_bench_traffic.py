"""The request generator repeats exactly for one seed, and every seed
sends the same mix in another order."""
import collections
import json
import os

import pytest

import _paths  # noqa: F401
import traffic

BIG = 2**40 + 12345          # seeds reach past 32 bits


def _mix(**kw):
    mix = {"kind": "closed", "popularity": "rounds",
           "policies": {"bt": 4, "lossless": 1}, "signals_per_sensor": 8,
           "compare": {"lossless": 4, "bt": 4}}
    mix.update(kw)
    return mix


def test_plan_repeats_for_one_seed():
    for mix in (_mix(), _mix(popularity="zipf", zipf_s=1.0,
                             policies={"bt": 3, "dp": 1, "lossless": 1})):
        a = traffic.Plan(mix, 32, BIG, 0).take(500)
        b = traffic.Plan(mix, 32, BIG, 0).take(500)
        assert a == b
        assert a != traffic.Plan(mix, 32, BIG + 1, 0).take(500)
        assert a != traffic.Plan(mix, 32, BIG, 1).take(500)


def test_every_seed_sends_the_same_mix():
    mix = _mix()
    counts = []
    for seed in (1, BIG, 7 * BIG):
        plan = traffic.Plan(mix, 32, seed, 0).take(320)
        counts.append((collections.Counter(p[1] for p in plan),
                       collections.Counter(p[0] for p in plan)))
    assert all(c == counts[0] for c in counts)
    assert counts[0][0] == {"bt": 256, "lossless": 64}
    assert set(counts[0][1].values()) == {10}


def test_warm_up_and_window_draw_different_signals():
    mix = _mix(signals_per_sensor=64)
    warm = traffic.Plan(mix, 32, BIG, 1).take(32 * 32)
    window = traffic.Plan(mix, 32, BIG, 0).take(32 * 32)
    used = lambda plan: {(s, k) for s, _, k in plan}
    assert len(used(window)) == len(window)
    assert not used(warm) & used(window)


def test_zipf_popularity_is_one_deck_in_another_order():
    # every seed draws the same popularity counts, each sensor's rank
    # shuffled: the hottest sensor takes the largest share of each deck
    mix = _mix(popularity="zipf", zipf_s=1.0)
    shares = []
    for seed in (3, BIG):
        plan = traffic.Plan(mix, 32, seed, 0).take(traffic.DECK)
        shares.append(sorted(collections.Counter(p[0] for p in plan)
                             .values()))
    assert shares[0] == shares[1]
    assert sum(shares[0]) == traffic.DECK
    assert shares[0][-1] > 4 * shares[0][0]


def test_open_kind_is_refused(tmp_path):
    os.makedirs(tmp_path / "bench" / "traffic")
    json.dump(_mix(kind="open"),
              open(tmp_path / "bench" / "traffic" / "arrivals.json", "w"))
    with pytest.raises(ValueError):
        traffic.load(str(tmp_path), "arrivals")


def test_committed_mixes_load():
    d = os.path.join(_paths.BENCH, "traffic")
    for f in os.listdir(d):
        mix = traffic.load(_paths.ROOT, f[:-5])
        plan = traffic.Plan(mix, 32, BIG, 0).take(40)
        assert all(p[1] in mix["policies"] for p in plan)
        json.dumps(plan)


def _load(tmp_path, mix):
    os.makedirs(tmp_path / "bench" / "traffic", exist_ok=True)
    json.dump(mix, open(tmp_path / "bench" / "traffic" / "m.json", "w"))
    return traffic.load(str(tmp_path), "m")


def test_a_key_may_name_its_transport(tmp_path):
    keys = {"bt": 2, "lossless": 1, "lossless/block8": 1}
    mix = _load(tmp_path, _mix(policies=keys, compare=keys))
    plan = traffic.Plan(mix, 4, BIG, 0).take(400)
    assert collections.Counter(p[1] for p in plan) == {
        "bt": 200, "lossless": 100, "lossless/block8": 100}
    assert traffic.split_key("lossless/block8", "ecsq") == ("lossless",
                                                            "block8")
    assert traffic.split_key("bt", "ecsq") == ("bt", "ecsq")


@pytest.mark.parametrize("key", ["lossless/int2", "lossless/", "fixed",
                                 "sgd/block8", "lossless/block8/ecsq"])
@pytest.mark.parametrize("where", ["policies", "compare"])
def test_an_unknown_policy_or_transport_is_refused(tmp_path, key, where):
    mix = _mix()
    mix[where] = {**mix[where], key: 1}
    with pytest.raises(ValueError):
        _load(tmp_path, mix)
