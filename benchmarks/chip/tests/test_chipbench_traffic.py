"""The traffic generator: deterministic per seed, the same work for every
seed, and the mix's distributions and rate."""

import json
import math
import os

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import traffic

MIXES = os.path.join(chipbench_tiny.BENCH_DIR, "traffic")
BIG_SEED = 2**31 + 2**33 + 12345


def _mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_pool_deterministic_per_seed(name):
    mix = _mix(name)
    a = traffic.build_pool(mix, 51, BIG_SEED)
    b = traffic.build_pool(mix, 51, BIG_SEED)
    assert a == b
    c = traffic.build_pool(mix, 51, 7)
    assert [(p.prompt_len, p.max_new) for p in a] != \
        [(p.prompt_len, p.max_new) for p in c]


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_every_seed_offers_the_same_work(name):
    mix = _mix(name)
    pools = [traffic.build_pool(mix, 51, s) for s in (1, 2, BIG_SEED)]
    if mix["loop"] == "closed":
        sizes = [sorted((p.prompt_len, p.max_new) for p in pool)
                 for pool in pools]
        assert sizes[0] == sizes[1] == sizes[2]
        return
    # an open loop offers the same requests in each phase, the window's
    # among them, in another order and with other spacing
    for start, length in traffic.phases(mix, 51):
        inside = [sorted((p.prompt_len, p.max_new) for p in pool
                         if start <= p.offset < start + length)
                  for pool in pools]
        assert inside[0] == inside[1] == inside[2]
        assert len(inside[0]) == round(mix["rate_per_s"] * length)
    orders = [[(p.prompt_len, p.max_new) for p in pool] for pool in pools]
    assert orders[0] != orders[1]


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_length_distributions_hold(name):
    mix = _mix(name)
    pool = traffic.build_pool(mix, 51, 3)
    for key, attr in (("prompt_len", "prompt_len"), ("output_len",
                                                     "max_new")):
        spec = mix[key]
        xs = np.array([getattr(p, attr) for p in pool])
        assert xs.min() >= spec["min"] and xs.max() <= spec["max"]
        if spec["dist"] == "lognormal":
            assert abs(np.median(xs) / spec["median"] - 1) < 0.15
        else:
            mid = (spec["min"] + spec["max"]) / 2
            assert abs(xs.mean() / mid - 1) < 0.1


def test_open_loop_rate_holds():
    mix = _mix("chat")
    pool = traffic.build_pool(mix, 51, BIG_SEED)
    offsets = np.array([p.offset for p in pool])
    assert offsets[0] > -mix["preroll_s"]
    assert np.all(np.diff(offsets) >= 0)
    span = mix["preroll_s"] + 51 + mix["drain_limit_s"]
    assert abs(len(offsets) / span / mix["rate_per_s"] - 1) < 0.05
    assert offsets[-1] < 51 + mix["drain_limit_s"]
    # spacing within a phase is exponential-like: its coefficient of
    # variation is near 1 (a Poisson process given its count)
    window = offsets[(offsets >= 0) & (offsets < 51)]
    if len(window) > 8:
        gaps = np.diff(window)
        assert 0.4 < gaps.std() / gaps.mean() < 1.8


def test_prompts_replay_from_seed_and_uid():
    a = traffic.make_prompt(BIG_SEED, 3, 50, 1000)
    assert np.array_equal(a, traffic.make_prompt(BIG_SEED, 3, 50, 1000))
    assert not np.array_equal(a, traffic.make_prompt(BIG_SEED, 4, 50, 1000))
    assert a.min() >= 0 and a.max() < 1000 and a.dtype == np.int32


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        traffic.seed_words(-1)


def test_pool_size_counts_every_phase():
    mix = _mix("chat")
    assert traffic.pool_size(mix, 51) == len(traffic.build_pool(mix, 51, 1))
    span = mix["preroll_s"] + 51 + mix["drain_limit_s"]
    assert traffic.pool_size(mix, 51) >= math.floor(mix["rate_per_s"]
                                                    * span) - 2
