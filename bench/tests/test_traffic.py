"""The traffic generator: deterministic per seed, the mixes' shares and
chain depths per batch of 512, and fresh ids on every lap."""

import numpy as np
import pytest

from bench import spec
from bench.traffic import generator

BIG_SEED = 2**33 + 12345      # seeds beyond 32 bits are fine


@pytest.fixture(scope="module")
def churn():
    return generator.make_lap(spec.traffic_mix("churn-sat"), BIG_SEED)


@pytest.fixture(scope="module")
def flood():
    return generator.make_lap(spec.traffic_mix("flood16-sat"), BIG_SEED)


def _batches(ids, n=400, size=512):
    return [ids[b:b + size] for b in range(0, n * size, size)]


def test_deterministic_per_seed():
    mix = dict(spec.traffic_mix("churn-sat"), flows_active=4096)
    a = generator.make_lap(mix, 7)
    b = generator.make_lap(mix, 7)
    c = generator.make_lap(mix, 8)
    assert np.array_equal(a.packets, b.packets)
    assert not np.array_equal(a.packets, c.packets)
    assert a.size == 8 * 4096


def test_churn_shares_and_depth(churn):
    p = churn.packets
    assert churn.size == 8 * 262144
    assert not churn.flood.any()
    assert p[:, 1].min() >= 40 and p[:, 1].max() <= 1500
    assert np.all(p[:, 0] == np.round(p[:, 0])) and p[:, 0].max() < 2**24
    ids = p[:, 0].astype(np.int64)
    # attack flows (ddos_burst shape: 90 B mean, port 80) carry ~1%
    ports = set(np.unique(p[:, 3]).tolist())
    assert ports == {80.0, 443.0, 8080.0, 6881.0}
    # nearly every packet of a batch of 512 has its own flow
    distinct = [len(np.unique(b)) for b in _batches(ids)]
    assert np.mean(distinct) > 510
    deepest = [np.unique(b, return_counts=True)[1].max()
               for b in _batches(ids)]
    assert np.median(deepest) <= 2


def test_churn_attack_share():
    mix = spec.traffic_mix("churn-sat")
    kinds = mix["kinds"]
    q = generator._attack_flow_share(kinds, mix["attack"])
    share = np.asarray([k["flow_share"] for k in kinds])
    mean_b = sum(s * (k["pkts"][0] + k["pkts"][1] - 1) / 2
                 for s, k in zip(share, kinds))
    mean_a = (mix["attack"]["pkts"][0] + mix["attack"]["pkts"][1] - 1) / 2
    assert q * mean_a / (q * mean_a + (1 - q) * mean_b) == pytest.approx(0.01)


def test_flood16_shares_and_depth(flood):
    assert flood.flood.mean() == pytest.approx(0.5, abs=0.005)
    ids = flood.packets[:, 0].astype(np.int64)
    fl = flood.flood
    per_batch = [int(fl[b:b + 512].sum()) for b in range(0, 400 * 512, 512)]
    assert 230 < np.mean(per_batch) < 282
    # 16 flood flows live at a time, ~16 packets of each in a batch
    live = [len(np.unique(ids[b:b + 512][fl[b:b + 512]]))
            for b in range(0, 400 * 512, 512)]
    assert 16 <= np.median(live) <= 18
    deepest = [np.unique(ids[b:b + 512], return_counts=True)[1].max()
               for b in range(0, 400 * 512, 512)]
    assert 16 <= np.median(deepest) <= 40
    # a flood flow lasts flow_pkts packets of its own
    _, counts = np.unique(ids[fl], return_counts=True)
    assert counts.max() <= 4096


def test_no_repeated_ids_across_laps(churn):
    assert churn.max_laps >= 16
    base = churn.packets[:, 0].astype(np.int64)
    seen = set(np.unique(base).tolist())
    for lap in range(1, 4):
        ids = churn.flow_ids(lap * churn.size, churn.size)
        u = set(np.unique(ids).tolist())
        assert not (u & seen)
        seen |= u
    # the replay's rows equal the lap's but for the shifted ids
    rows = churn.take(churn.size - 3, 6)
    assert np.array_equal(rows[:3], churn.packets[-3:])
    assert np.array_equal(rows[3:, 1:], churn.packets[:3, 1:])
    assert np.array_equal(rows[3:, 0], churn.lap_ids(1, churn.packets[:3, 0]))
    assert np.array_equal(churn.take_rows(np.arange(churn.size - 3,
                                                    churn.size + 3)), rows)

