"""The traffic generator: seeded, reproducible, the same work for every seed."""
import collections

import pytest

import loadgen

open_poisson = loadgen.load_module(loadgen.TRAFFIC / "open_poisson.py",
                                   "traffic_open_poisson")
closed_sweep = loadgen.load_module(loadgen.TRAFFIC / "closed_sweep.py",
                                   "traffic_closed_sweep")
SIZES = [4096, 16384, 65536]
MIX = {"kind": "open_poisson", "rate_per_s": 10.0}
SWEEP = {"kind": "closed_sweep", "request_seeds": [0, 1]}
BIG = 2 ** 31 + 12345          # seeds above 32 signed bits are valid


def test_same_seed_same_schedule_and_requests():
    assert open_poisson.schedule(MIX, SIZES, BIG, 30) == \
        open_poisson.schedule(MIX, SIZES, BIG, 30)


@pytest.mark.parametrize("a,b", [(1, 2), (BIG, BIG + 1), (0, 2 ** 40)])
def test_different_seeds_give_disjoint_request_seeds(a, b):
    seeds_a = {d.seed for d in open_poisson.schedule(MIX, SIZES, a, 30)}
    seeds_b = {d.seed for d in open_poisson.schedule(MIX, SIZES, b, 30)}
    assert not seeds_a & seeds_b


def test_every_seed_gets_the_same_work_in_another_order():
    wa = open_poisson.schedule(MIX, SIZES, 3, 30)
    wb = open_poisson.schedule(MIX, SIZES, 4, 30)
    assert len(wa) == len(wb) == 300
    assert (collections.Counter(d.array_size for d in wa)
            == collections.Counter(d.array_size for d in wb))
    gaps = lambda w: sorted(round(y.at - x.at, 9) for x, y in zip(w, w[1:]))
    assert [d.array_size for d in wa] != [d.array_size for d in wb]
    # the same inter-arrival gaps but one, in another order
    assert len(set(gaps(wa)) ^ set(gaps(wb))) <= 2
    assert wa[0].at == 0.0 and wa[-1].at < 30.0


def test_window_seeds_are_distinct():
    seeds = [d.seed for d in open_poisson.schedule(MIX, SIZES, 9, 30)]
    assert len(seeds) == len(set(seeds)) == 300
    assert all(0 <= s < 2 ** 31 - 1 for s in seeds)


@pytest.mark.parametrize("n,widest", [(300, 16), (136, 16), (9, 3), (2, 3)])
def test_warm_up_sends_every_request_once_in_every_burst_size(n, widest):
    bursts = open_poisson.warm_bursts(n, widest)
    assert [i for b in bursts for i in b] == list(range(n))
    sizes = [len(b) for b in bursts]
    ramp = [k for k in range(1, widest + 1) if k * (k + 1) // 2 <= n]
    assert sizes[:len(ramp)] == ramp
    assert max(sizes) <= widest


def test_sweep_jobs_are_deterministic_and_do_the_same_work():
    job = closed_sweep.job_cells(SWEEP, SIZES, BIG, 0)
    assert job == closed_sweep.job_cells(SWEEP, SIZES, BIG, 0)
    assert sorted(job) == sorted((s, sd) for s in SIZES for sd in (0, 1))
    orders = {tuple(closed_sweep.job_cells(SWEEP, SIZES, seed, k))
              for seed in (1, 2, BIG) for k in range(3)}
    assert len(orders) > 1
    assert all(sorted(o) == sorted(job) for o in orders)
