"""The generator: identical for one seed; across seeds the same sizes
and arrivals with other token ids; within each mix's stated ranges."""
import json
import os
import numpy as np
import pytest

from bench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


def shapes(tr):
    return [(s.intent, [(t.extra_tokens, t.output_tokens, t.think_s)
                        for t in s.turns]) for s in tr.sessions]


@pytest.mark.parametrize("name", ["agent", "long"])
def test_same_seed_same_traffic(name):
    a = traffic.generate(mix(name), 2.0, 40.0, 2**33 + 5)
    b = traffic.generate(mix(name), 2.0, 40.0, 2**33 + 5)
    assert shapes(a) == shapes(b)
    assert [s.arrival_s for s in a.sessions] == \
        [s.arrival_s for s in b.sessions]
    assert a.prefixes == b.prefixes
    assert a.ids(3, 1, 50) == b.ids(3, 1, 50)


@pytest.mark.parametrize("name", ["agent", "long"])
def test_seeds_permute_the_same_work(name):
    a = traffic.generate(mix(name), 2.0, 40.0, 1)
    b = traffic.generate(mix(name), 2.0, 40.0, 2)
    # the pool is fixed: every seed serves the same sizes and arrivals
    assert shapes(a) == shapes(b)
    assert [s.arrival_s for s in a.sessions] == \
        [s.arrival_s for s in b.sessions]
    # and other token ids
    assert a.ids(0, 0, 20) != b.ids(0, 0, 20)
    assert a.prefixes != b.prefixes or not a.prefixes
    # a longer horizon extends the same sessions
    c = traffic.generate(mix(name), 2.0, 80.0, 3)
    assert shapes(c)[:len(shapes(a))] == shapes(a)


def test_agent_ranges():
    m = mix("agent")
    tr = traffic.generate(m, 5.0, 400.0, 7)
    turns = [s.turns for s in tr.sessions]
    assert {len(t) for t in turns} <= set(range(1, 9))
    assert 2.6 < np.mean([len(t) for t in turns]) < 3.2
    outs = [t.output_tokens for ts in turns for t in ts]
    assert min(outs) >= 16 and max(outs) <= 256
    assert 40 <= np.median(outs) <= 56
    first = [ts[0].extra_tokens for ts in turns]
    assert min(first) >= 16 and max(first) <= 64
    tool = [t.extra_tokens for ts in turns for t in ts[1:]]
    assert min(tool) >= 32 and max(tool) <= 1024
    assert 110 <= np.median(tool) <= 150
    think = [t.think_s for ts in turns for t in ts]
    assert min(think) >= 0.1 and max(think) <= 1.0
    lens = sorted(len(p) for p in tr.prefixes)
    assert lens[0] == 2079 and lens[-1] == 3060 and len(lens) == 8
    ids = [i for p in tr.prefixes for i in p]
    assert min(ids) >= 6 and max(ids) < 8192
    # Zipf(1) over 8 intents: the top one takes about 37%
    top = np.mean([s.intent == 0 for s in tr.sessions])
    assert 0.33 < top < 0.41
    rate = len(tr.sessions) / 400.0
    assert 4.5 < rate < 5.5


def test_long_ranges():
    tr = traffic.generate(mix("long"), 3.0, 400.0, 9)
    assert not tr.prefixes
    assert all(s.intent is None and len(s.turns) == 1
               for s in tr.sessions)
    prompts = [s.turns[0].extra_tokens for s in tr.sessions]
    assert min(prompts) >= 1024 and max(prompts) <= 6144
    assert 1800 <= np.median(prompts) <= 2300
    outs = [s.turns[0].output_tokens for s in tr.sessions]
    assert min(outs) >= 16 and max(outs) <= 256
