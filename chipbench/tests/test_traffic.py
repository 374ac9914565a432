"""The replay traffic: region schedules, and a function of the seed."""
import jax
import numpy as np
import pytest

from chipbench import weights
from chipbench.traffic import regions

PARAMS = {"regions": 3, "cameras_per_region": 2, "drifting_regions": [0, 2],
          "num_domains": 4, "domain_dim": 4, "tau": 0.15, "pool_rows": 64,
          "pool_len": 16, "draws_per_camera": 64, "lag_max": 2.0, "region_spread": 10.0,
          "region_distance": 1000.0, "sig_buckets": 16, "switch_time": 10.0,
          "region_stagger": 5.0}


def build(seed):
    key = jax.random.fold_in(weights.key_from_seed(seed), 1)
    return regions.build(PARAMS, vocab=128, seed=seed, key=key)


def draws(streams):
    return [s.sample(t, 4, 16) for t in (0.0, 30.0) for s in streams]


def test_schedule_follows_drifting_regions():
    streams = build(7)
    assert [s.stream_id for s in streams] == [
        f"cam{r}_{c}" for r in range(3) for c in range(2)]
    for i, s in enumerate(streams):
        r = int(s.stream_id[3])
        lag = s.lag
        assert 0.0 <= lag <= PARAMS["lag_max"]
        before = s.domain_at(0.0)
        if r in PARAMS["drifting_regions"]:
            ts = 10.0 + 5.0 * r
            assert s.domain_at(ts + lag) != before
            assert s.domain_at(ts + lag - 0.01) == before
        else:
            assert s.domain_at(1e6) == before
        # cameras of one region share its domains and location cluster
        assert abs(s.loc[0] - r * 1000.0) <= 10.0
        assert before == streams[2 * r].domain_at(0.0)


def test_samples_come_from_the_current_domains_pool():
    s = build(3)[0]
    for t in (0.0, 25.0):
        pool = s.pools[s.domain_at(t)]
        rows = {tuple(r) for r in pool}
        got = s.sample(t, 8, 16)
        assert got.shape == (8, 16)
        assert all(tuple(r) in rows for r in got)
    assert s.sample(0.0, 2, 8).shape == (2, 8)


def test_pools_are_bigram_chains_without_self_transitions():
    pools = build(5)[0].pools
    assert pools.shape == (4, 64, 16)
    assert pools.min() >= 0 and pools.max() < 128
    assert not np.any(pools[..., 1:] == pools[..., :-1])


def test_traffic_is_a_function_of_the_seed_only():
    a, b = draws(build(11)), draws(build(11))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    big = 2**31 + 12345
    c, d = draws(build(big)), draws(build(big))
    assert all(np.array_equal(x, y) for x, y in zip(c, d))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_a_camera_asking_again_gets_fresh_rows():
    a = build(13)[0]
    first, again = a.sample(0.0, 4, 16), a.sample(0.0, 4, 16)
    assert first.shape == again.shape == (4, 16)
    assert not np.array_equal(first, again)
    with pytest.raises(ValueError):
        first[0, 0] = 1             # the pools are read-only
