"""The traffic generator: deterministic per seed, the same work for every
seed, and the declared medians, clips, bursts and document shares."""
import numpy as np
import pytest

from chipbench import bench
from chipbench.gen import openloop

MIXES = ("chat", "chat-bursty", "rag-prefix")


def mix(name):
    return bench.load_json(bench.HERE / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = openloop.schedule(mix(name), 3.0, 40, 2**31 + 123, 49152)
    b = openloop.schedule(mix(name), 3.0, 40, 2**31 + 123, 49152)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    a = openloop.schedule(mix(name), 3.0, 40, 1, 49152)
    b = openloop.schedule(mix(name), 3.0, 40, 2, 49152)
    assert len(a) == len(b) == 120
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt)
                                                      for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    for s in (a, b):
        due = [x.due for x in s]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 40


@pytest.mark.parametrize("name", MIXES)
def test_medians_and_clips(name):
    m = mix(name)
    s = openloop.schedule(m, 10.0, 101, 7, 49152)       # 1010 requests
    head = m.get("prefix", {}).get("tokens", 0)
    plen = np.array([len(x.prompt) - head for x in s])
    olen = np.array([x.max_new for x in s])
    for lens, spec in ((plen, m["prompt"]), (olen, m["output"])):
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
        assert abs(np.median(lens) - spec["median"]) <= 1
    assert all(1 <= t < 49152 for x in s[:20] for t in x.prompt)


def test_square_wave_bursts():
    m = mix("chat-bursty")
    a = m["arrivals"]
    s = openloop.schedule(m, 10.0, 160, 3, 65024)   # 1600 requests
    high = np.mean([x.due % a["period_s"] < a["high_s"] for x in s])
    want = a["high"] * a["high_s"] / (
        a["high"] * a["high_s"] + a["low"] * (a["period_s"] - a["high_s"]))
    assert abs(high - want) < 0.04       # about 3 binomial sigmas


def test_zipf_documents():
    m = mix("rag-prefix")
    s = openloop.schedule(m, 8.0, 50, 9, 49152)
    counts = np.bincount([x.doc for x in s], minlength=16)
    assert counts.sum() == 400
    assert list(counts) == list(openloop.zipf_counts(400, 16, 1.0))
    assert counts[0] == max(counts) and counts[0] > 4 * counts[15]
    first = {}
    for x in s:
        head = tuple(x.prompt[:1024])
        assert first.setdefault(x.doc, head) == head
    assert len(set(first.values())) == 16
