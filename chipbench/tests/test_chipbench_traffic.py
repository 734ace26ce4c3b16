"""The traffic generator: seeds fix the schedule, lengths follow the
paper's Table 1, and popularity follows Zipf."""
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

from chipbench.generator import FIRST_ID, Generator, quantiles, zipf_ranks

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2 ** 31 + 977


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def unshared(name):
    """The mix with its context pool dropped: every context unique."""
    m = mix(name)
    for part in m["prompt"]:
        part.pop("pool", None)
    m.pop("lead_in", None)
    return m


def flat(sched):
    return [(a.due, tuple(a.prompt), a.max_new) for a in
            sched.lead_in + sched.window] + [tuple(w) for w in sched.warm]


@pytest.mark.parametrize("make,name", [
    (mix, "naive-rag-shared"), (unshared, "naive-rag-shared"),
    (mix, "graph-rag"), (mix, "llm-only")])
def test_same_seed_same_schedule(make, name):
    a = Generator(make(name), BIG_SEED, 19008).schedule(20.0)
    b = Generator(make(name), BIG_SEED, 19008).schedule(20.0)
    c = Generator(make(name), BIG_SEED + 1, 19008).schedule(20.0)
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)
    # another seed sends the same sizes in another order
    assert (sorted(x.max_new for x in a.window)
            == sorted(x.max_new for x in c.window))
    assert (sorted(len(x.prompt) for x in a.window)
            == sorted(len(x.prompt) for x in c.window))
    assert (sorted(x.topic or 0 for x in a.window)
            == sorted(x.topic or 0 for x in c.window))


@pytest.mark.parametrize("name", ["naive-rag-shared", "graph-rag",
                                  "llm-only"])
def test_window_holds_rate_times_seconds(name):
    m = mix(name)
    s = Generator(m, 5, 151936).schedule(30.0)
    assert len(s.window) == int(m["rate_per_s"] * 30.0)
    assert all(0.0 <= a.due < 30.0 for a in s.window)
    assert all(a.due <= 0.0 for a in s.lead_in)
    ids = [t for a in s.window for t in a.prompt]
    assert min(ids) >= FIRST_ID and max(ids) < 151936


def clipped_normal_moments(mean, std, lo, hi, n=200_000):
    nd = NormalDist(mean, std)
    u = (np.arange(n) + 0.5) / n
    x = np.clip([nd.inv_cdf(float(p)) for p in u], lo, hi)
    return float(np.mean(x)), float(np.std(x))


@pytest.mark.parametrize("name,prompt_mean,prompt_tol,out", [
    # Table 1: naive RAG in 3,632 +- 29, out 26.6 +- 19.8
    ("naive-rag-shared", 3632.0, 1.0, (26.6, 19.8, 1, 128)),
    # graph RAG in 9,017 +- 2,529 (clipped 4,096-15,872), out 142.7 +- 91.6
    ("graph-rag", 9017.0, 0.02 * 9017, (142.7, 91.6, 16, 512)),
])
def test_lengths_follow_table1(name, prompt_mean, prompt_tol, out):
    m = dict(mix(name), rate_per_s=50.0)
    s = Generator(m, 3, 151936).schedule(40.0)   # 2,000 requests
    plen = np.array([len(a.prompt) for a in s.window])
    assert abs(plen.mean() - prompt_mean) <= prompt_tol
    olen = np.array([a.max_new for a in s.window])
    mu, sd = clipped_normal_moments(*out)
    # stratified quantiles: only integer rounding separates the moments
    assert abs(olen.mean() - mu) < 0.5
    assert abs(olen.std() - sd) < 0.05 * sd


def test_llm_only_follows_table1():
    """Table 1's LLM-only arm: 16 +- 5 prompt tokens, all of them the
    request's own, 27.2 +- 14.8 out; a burst of 40 fills the 32 rows before
    a window of the cell's own length opens."""
    m = mix("llm-only")
    s = Generator(m, BIG_SEED, 151936).schedule(50.0)
    assert len(s.window) == int(m["rate_per_s"] * 50.0) >= 100
    plen = np.array([len(a.prompt) for a in s.window])
    olen = np.array([a.max_new for a in s.window])
    assert abs(plen.mean() - 16) <= 1 and plen.min() >= 1
    assert abs(olen.mean() - 27.2) <= 2 and olen.min() >= 1
    assert len(s.lead_in) == 40 and m["lead_in"]["fill"]
    assert all(a.due == 0.0 for a in s.lead_in)
    assert not s.warm and all(a.topic is None for a in s.window)
    # no two requests share a prompt token sequence, nor a prefix page
    prompts = [tuple(a.prompt) for a in s.lead_in + s.window]
    assert len(set(prompts)) == len(prompts)
    assert len({p[:16] for p in prompts if len(p) >= 16}) == sum(
        len(p) >= 16 for p in prompts)


def test_graph_rag_spread():
    s = Generator(dict(mix("graph-rag"), rate_per_s=50.0), 3,
                  19008).schedule(40.0)
    plen = np.array([len(a.prompt) for a in s.window])
    mu, sd = clipped_normal_moments(9017, 2529, 4096, 15872)
    assert abs(plen.std() - sd) < 0.02 * sd
    assert plen.min() >= 4096 and plen.max() <= 15872


def h(n, s=1.0):
    return sum(1.0 / k ** s for k in range(1, n + 1))


@pytest.mark.parametrize("n", [20, 10_000])
def test_zipf_ranks_follow_the_cdf(n):
    r = zipf_ranks(256, 1.0, n)
    assert len(r) == n and (np.diff(r) >= 0).all()
    assert r.min() == 0 and r.max() <= 255
    # the share of draws at or under each rank is within 1/n of the CDF
    for k in (1, 8, 32, 128):
        assert abs((r < k).mean() - h(k) / h(256)) <= 1.0 / n


def test_zipf_ranks_reach_the_tail():
    # the shared cell's 20 window arrivals: 9 to the 8 most popular
    # contexts, 11 to distinct contexts down to rank 220 of 256
    r = zipf_ranks(256, 1.0, 20)
    assert (r < 8).sum() == 9
    tail = r[r >= 8]
    assert len(set(tail.tolist())) == 11 and tail.max() == 219
    # i.i.d. Zipf 1.0 sends H(256) - H(16) over H(256) (~45%) past rank 16
    big = zipf_ranks(256, 1.0, 10_000)
    assert abs((big >= 16).mean() - (1 - h(16) / h(256))) < 1e-3


def test_shared_cell_draws_contexts_by_popularity():
    m = dict(mix("naive-rag-shared"), rate_per_s=50.0)
    s = Generator(m, 8, 151936).schedule(40.0)
    topics = np.array([a.topic for a in s.window])
    assert (np.sort(topics) == zipf_ranks(256, 1.0, len(topics))).all()
    assert len(set(topics.tolist())) > 128
    # one context per topic: every prompt of a topic starts the same way
    first = {}
    for a in s.window:
        ctx = tuple(a.prompt[:3584])
        assert first.setdefault(a.topic, ctx) == ctx
    # the warm set is the most popular topics
    assert len(s.warm) == m["lead_in"]["warm_contexts"]
    assert tuple(s.warm[0]) == first[0]


def test_unshared_mix_has_unique_contexts():
    s = Generator(unshared("naive-rag-shared"), 8, 151936).schedule(30.0)
    ctxs = {tuple(a.prompt[:3584]) for a in s.window}
    assert len(ctxs) == len(s.window)
    assert not s.warm


@pytest.mark.parametrize("spec,expect", [
    ({"dist": "const", "value": 7}, [7, 7, 7, 7]),
    ({"dist": "uniform", "min": 32, "max": 35}, [32, 33, 34, 35]),
])
def test_quantiles_exact(spec, expect):
    assert quantiles(spec, 4).tolist() == expect


@pytest.mark.parametrize("name", ["graph-rag", "naive-rag-shared"])
def test_order_seed_fixes_the_order(name):
    """A mix with ``order_seed`` sends every run seed the same sizes and
    contexts at the same times; the run seed draws only the token ids."""
    m = mix(name)
    assert "order_seed" in m
    a = Generator(m, BIG_SEED, 19008).schedule(50.0)
    b = Generator(m, BIG_SEED + 1, 19008).schedule(50.0)
    sizes = [[(x.due, len(x.prompt), x.max_new, x.topic)
              for x in s.lead_in + s.window] for s in (a, b)]
    assert sizes[0] == sizes[1]
    assert [x.prompt for x in a.window] != [x.prompt for x in b.window]
    m.pop("order_seed")
    c = Generator(m, BIG_SEED + 1, 19008).schedule(50.0)
    assert [(x.due, len(x.prompt), x.max_new) for x in c.window] != [
        (x.due, len(x.prompt), x.max_new) for x in b.window]
