"""The traffic generator: a fixed schedule per traffic file, prompts drawn
from the seed, lengths and gaps at the quantiles of the file's
distributions."""
import json
import os

import numpy as np
import pytest
from scipy import stats as sst

from chipbench import HERE, traffic as T

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))
               if f.endswith(".json"))


def mix(name):
    return T.load(os.path.join(HERE, "traffic", name + ".json"))


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_fixed_by_the_file(name):
    a = T.schedule(mix(name), 51)
    b = T.schedule(mix(name), 51)
    assert a == b
    m = mix(name)
    m.pop("requests", None)                  # the whole window's requests
    a = T.schedule(m, 51)
    m["schedule_seed"] += 1
    c = T.schedule(m, 51)
    assert sorted(r.max_new_tokens for r in c) == \
        sorted(r.max_new_tokens for r in a)
    assert [r.max_new_tokens for r in c] != [r.max_new_tokens for r in a]


@pytest.mark.parametrize("name", MIXES)
def test_every_request_is_due_in_the_window(name):
    m = mix(name)
    m.pop("requests", None)
    sched = T.schedule(m, 51)
    rate = m["arrivals"]["rate_per_s"]
    assert len(sched) == round(rate * 51)
    due = [r.due_s for r in sched]
    assert due == sorted(due) and due[0] > 0
    assert due[-1] == pytest.approx(len(sched) / rate)
    assert due[-1] <= 51 + 1 / rate


def test_prompts_follow_the_seed():
    sched = T.schedule(mix("code-bursty"), 20)
    a = T.prompts(sched, 32000, 2 ** 31 + 17)
    b = T.prompts(sched, 32000, 2 ** 31 + 17)
    c = T.prompts(sched, 32000, 2 ** 31 + 18)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    assert all(len(p) == r.prompt_len for p, r in zip(a, sched))
    flat = np.concatenate(a)
    assert flat.min() >= 1 and flat.max() < 32000 and flat.dtype == np.int32


def test_lengths_follow_the_lognormal():
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.8,
            "min": 16, "max": 512}
    n = 2000
    got = T.lengths(spec, n)
    assert np.median(got) == pytest.approx(128, abs=1)
    assert got.min() >= 16 and got.max() <= 512
    # away from the clip, the draws are the distribution's quantiles
    inner = (got > 16) & (got < 512)
    q = ((np.arange(n) + 0.5) / n)[inner]
    want = sst.lognorm.ppf(q, 0.8, scale=128)
    assert np.max(np.abs(got[inner] - want)) <= 0.5
    assert T.lengths({"dist": "fixed", "value": 1024}, 5).tolist() == \
        [1024] * 5


@pytest.mark.parametrize("cv", [1.0, 2.0])
def test_gaps_follow_the_gamma(cv):
    n, rate = 4000, 2.0
    g = T.gaps({"process": "gamma", "cv": cv, "rate_per_s": rate}, n)
    assert g.sum() == pytest.approx(n / rate)
    assert np.std(g) / np.mean(g) == pytest.approx(cv, rel=0.05)
    shape = 1 / cv ** 2
    ks = sst.kstest(g, "gamma", args=(shape, 0, 1 / (rate * shape)))
    assert ks.statistic < 0.01


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        T.lengths({"dist": "zipf"}, 3)
    with pytest.raises(ValueError):
        T.gaps({"process": "hawkes", "rate_per_s": 1, "cv": 1}, 3)


def test_mix_files_are_plain_data():
    for name in MIXES:
        with open(os.path.join(HERE, "traffic", name + ".json")) as f:
            m = json.load(f)
        assert {"prompt_tokens", "output_tokens", "arrivals",
                "schedule_seed"} <= set(m)


@pytest.mark.parametrize("name", MIXES)
def test_a_request_count_keeps_the_first_requests(name):
    m = mix(name)
    whole = T.schedule(dict(m, requests=10 ** 6), 51)
    assert len(whole) == round(m["arrivals"]["rate_per_s"] * 51)
    assert T.schedule(dict(m, requests=5), 51) == whole[:5]
    assert T.schedule(m, 51) == whole[:m.get("requests", len(whole))]
