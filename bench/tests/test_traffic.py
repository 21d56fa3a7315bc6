import numpy as np
import pytest

import harness
import traffic_gen


@pytest.mark.parametrize("mix", ["congested", "calm", "live", "backlog"])
def test_a_seed_reproduces_its_traffic(mix):
    spec = dict(harness.traffic(mix), bank_rounds=8)
    if spec.get("telemetry"):
        spec["telemetry"] = dict(spec["telemetry"], bad_rounds=3)
    a = traffic_gen.round_bank(spec, 32, 5, 2**31 + 17)
    b = traffic_gen.round_bank(spec, 32, 5, 2**31 + 17)
    c = traffic_gen.round_bank(spec, 32, 5, 2**31 + 18)
    for name in ("z", "aq", "dx", "bw_scale"):
        x, y = getattr(a, name), getattr(b, name)
        if x is None:
            assert y is None and spec.get("telemetry") is None
            continue
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.dx, c.dx)
    assert not np.array_equal(a.z, c.z)


def test_congested_chain_holds_its_stated_bad_share():
    spec = harness.traffic("congested")
    tel = spec["telemetry"]
    for seed in (1, 2, 2**32 + 5):
        bank = traffic_gen.round_bank(spec, 4, 2, seed)
        bad = bank.bw_scale < 1.0
        # 77 of 256 rounds plan against 0.3x of the uplink, every seed
        assert bad.sum() == tel["bad_rounds"] == 77
        assert bank.rounds == 256
        np.testing.assert_array_equal(bank.bw_scale[bad], np.float32(0.3))
        # congestion comes in spells: more bad->bad than independent draws
        runs = np.count_nonzero(np.diff(bad.astype(int)) == 1)
        assert runs < bad.sum() * 0.8


def test_rounds_hold_the_same_spread_of_work():
    spec = dict(harness.traffic("congested"), bank_rounds=4, telemetry=None)
    a = traffic_gen.round_bank(spec, 4096, 2, 1)
    b = traffic_gen.round_bank(spec, 4096, 2, 2)
    # stratified: the sorted draws of any two rounds agree to a slice width
    za, zb = np.sort(a.z, axis=1), np.sort(b.z, axis=1)
    assert np.abs(za - zb).max() < 0.05
    qa, qb = np.sort(a.aq, axis=1), np.sort(b.aq, axis=1)
    assert np.abs(qa - qb).max() <= 0.3 / 4096 + 1e-6
    assert qa.min() >= 0.5 and qa.max() <= 0.8


def test_open_loop_due_times():
    spec = harness.traffic("live")
    assert [traffic_gen.due_time(spec, k) for k in range(3)] == [0.0, 1.0, 2.0]


def test_a_fleet_seed_gives_every_seed_the_same_cameras():
    from systems.routed_pools import FLEET_SEED, fleet

    key = lambda bank: sorted(map(tuple, np.concatenate(
        [bank.z.T, bank.aq.T, bank.dx.transpose(1, 0, 2).reshape(16, -1)], 1)))
    for mix in ("live", "backlog"):
        spec = dict(harness.traffic(mix), bank_rounds=3)
        (a, ga), (b, gb) = (fleet(spec, 16, 5, 2**31 + 1),
                            fleet(spec, 16, 5, 2**33))
        assert ga == gb == FLEET_SEED
        assert key(a) == key(b)                    # the same cameras ...
        assert not np.array_equal(a.z, b.z)        # ... in another order
