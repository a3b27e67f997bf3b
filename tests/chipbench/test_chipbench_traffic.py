"""The one traffic generator: deterministic per seed, true to its
parameters, the same work for every seed."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[2] / "chipbench" / "traffic"


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def gen(name, seed, **kw):
    return traffic.generate(mix(name), seed=seed, vocab=1000, seconds=50.0,
                            **kw)


@pytest.mark.parametrize("name,kw", [("chat-short", {"rate_per_s": 2.0}),
                                     ("decode-long", {"requests": 40,
                                                      "block": 20})])
def test_same_seed_same_requests(name, kw):
    a, b = gen(name, 2**33 + 5, **kw), gen(name, 2**33 + 5, **kw)
    assert [(r.gen, r.offset, r.prompt.tolist()) for r in a] == \
        [(r.gen, r.offset, r.prompt.tolist()) for r in b]
    c = gen(name, 6, **kw)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("name,kw", [("chat-short", {"rate_per_s": 2.0}),
                                     ("decode-long", {"requests": 40,
                                                      "block": 20})])
def test_every_seed_serves_the_same_work(name, kw):
    a, b = gen(name, 1, **kw), gen(name, 2, **kw)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.gen for r in a) == sorted(r.gen for r in b)
    assert sorted(np.diff([0.0] + [r.offset for r in a])) == pytest.approx(
        sorted(np.diff([0.0] + [r.offset for r in b])))


def test_poisson_order_is_drawn_from_the_seed():
    a = gen("chat-short", 1, rate_per_s=2.0, warmup=30.0)
    b = gen("chat-short", 2**40 + 3, rate_per_s=2.0, warmup=30.0)
    assert [r.offset for r in a] != [r.offset for r in b]
    assert [r.gen for r in a] != [r.gen for r in b]
    assert sorted(r.gen for r in a) == sorted(r.gen for r in b)


def test_warmup_and_window_are_stratified_apart():
    a = gen("chat-short", 1, rate_per_s=2.0, warmup=30.0)
    b = gen("chat-short", 2, rate_per_s=2.0, warmup=30.0)
    assert len(a) == len(b) == 160  # 60 in the warm-up, 100 in the window
    win = [[r for r in x if r.offset >= 30.0] for x in (a, b)]
    assert len(win[0]) == len(win[1]) == 100
    assert sorted(len(r.prompt) for r in win[0]) == \
        sorted(len(r.prompt) for r in win[1])


def test_chat_short_matches_its_parameters():
    m = mix("chat-short")
    reqs = gen("chat-short", 3, rate_per_s=4.0)
    assert len(reqs) == 200  # rate x seconds
    offs = np.array([r.offset for r in reqs])
    assert np.all(np.diff(offs) > 0)
    assert offs[-1] == pytest.approx(50.0, rel=0.02)
    plen = np.array([len(r.prompt) for r in reqs])
    glen = np.array([r.gen for r in reqs])
    p, g = m["prompt_tokens"], m["output_tokens"]
    assert plen.min() >= p["min"] and plen.max() <= p["max"]
    assert glen.min() >= g["min"] and glen.max() <= g["max"]
    assert np.median(plen) == pytest.approx(p["median"], abs=2)
    assert np.median(glen) == pytest.approx(g["median"], abs=2)
    # lognormal sigma: log-lengths' spread between quartiles is 1.349 sigma
    q1, q3 = np.percentile(np.log(plen), [25, 75])
    assert (q3 - q1) / 1.349 == pytest.approx(p["sigma"], rel=0.05)
    assert all(r.prompt.max() < 1000 for r in reqs)


def test_backlog_first_block_is_the_stratified_set():
    m = mix("decode-long")
    reqs = gen("decode-long", 9, requests=40, block=20)
    assert all(r.offset == 0.0 for r in reqs)
    first = sorted(len(r.prompt) for r in reqs[:20])
    p = m["prompt_tokens"]
    want = sorted(traffic._quantiles(p, 20).tolist())
    assert sorted(len(r.prompt) for r in reqs[20:]) == want
    assert first == want
    assert min(first) >= p["min"] and max(first) <= p["max"]


def test_unknown_distribution_is_an_error():
    bad = dict(mix("chat-short"), prompt_tokens={"dist": "zipf", "min": 1,
                                                 "max": 2})
    with pytest.raises(ValueError):
        traffic.generate(bad, seed=0, vocab=10, seconds=1.0, rate_per_s=1.0)
