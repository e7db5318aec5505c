"""The traffic generator: deterministic by seed, lengths within their
clips, Zipf shares, the same set of sizes for every seed, and the
requests in service drawn as in the arrival process's equilibrium."""
import json

import numpy as np
import pytest

from harness import spec, traffic

CHAT = json.loads((spec.BENCH_DIR / "traffic" / "chat_zipf64.json")
                  .read_text())


def gen(seed, **kw):
    kw.setdefault("seconds", 30)
    return traffic.generate(CHAT, seed, vocab=151936, max_total=4096,
                            slots=32, **kw)


def test_same_seed_same_requests():
    a, b = gen(2**31 + 77), gen(2**31 + 77)
    assert len(a.items) == len(b.items)
    assert len(a.in_service) == len(b.in_service)
    for x, y in zip(a.items + a.in_service, b.items + b.in_service):
        assert x.adapter == y.adapter and x.output_len == y.output_len
        assert x.due == y.due and np.array_equal(x.prompt, y.prompt)
    c = gen(2**31 + 78)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.items, c.items))


@pytest.mark.parametrize("part", ["window", "preroll", "in_service"])
def test_every_seed_offers_the_same_sizes(part):
    """Each part of a run is the same set of sizes and gaps for every
    seed: the requests due in the window are the same work."""
    def pick(t):
        if part == "in_service":
            return t.in_service, None
        win = part == "window"
        return ([i for i in t.items if (i.due >= 0) == win],
                0.0 if win else -CHAT["preroll_s"])

    (a, a0), (b, b0) = pick(gen(5, seconds=51)), pick(gen(6, seconds=51))
    assert len(a) == len(b) > 0
    # the same requests: prompt and output lengths paired alike
    assert sorted((len(i.prompt), i.output_len) for i in a) == \
        sorted((len(i.prompt), i.output_len) for i in b)
    assert [i.output_len for i in a] != [i.output_len for i in b]
    if a0 is not None:
        assert sorted(np.diff([a0] + sorted(i.due for i in a))) \
            == pytest.approx(sorted(np.diff([b0] + sorted(
                i.due for i in b))))


def test_lengths_within_clips():
    t = gen(9)
    for it in t.items:
        assert 8 <= it.output_len <= 512
        assert len(it.prompt) + it.output_len <= 4096
    us = traffic.lengths(CHAT["user_prompt"], 1000)
    assert us.min() >= 16 and us.max() <= 2048
    assert np.median(us) == pytest.approx(390, rel=0.02)
    out = traffic.lengths(CHAT["output"], 1000)
    assert np.median(out) == pytest.approx(129, rel=0.02)


def test_prompt_median_is_the_sources():
    """System prompt plus user turn: the Azure conversation trace's median
    prompt of 1020 tokens, within 3%."""
    t = gen(21, seconds=2000)
    assert np.median([len(i.prompt) for i in t.items]) == \
        pytest.approx(1020, rel=0.03)


def test_system_prompt_opens_every_request_of_an_adapter():
    t = gen(11)
    heads = {}
    for it in t.items + t.in_service:
        heads.setdefault(it.adapter, []).append(it.prompt)
    for prompts in heads.values():
        n = min(len(p) for p in prompts)
        common = 0
        while common < n and all(p[common] == prompts[0][common]
                                 for p in prompts):
            common += 1
        if len(prompts) > 1:
            assert common >= 256


def test_zipf_shares():
    c = traffic.zipf_counts(512, 64, 1.0)
    assert c.sum() == 512
    assert list(c) == sorted(c, reverse=True)
    h = sum(1.0 / k for k in range(1, 65))
    assert c[0] == pytest.approx(512 / h, abs=1)
    assert c[1] == pytest.approx(512 / h / 2, abs=1)
    t = gen(3, seconds=1270)
    counts = np.bincount([i.adapter for i in t.items], minlength=64)
    n_pre = round(CHAT["arrivals"]["rate_per_s"] * CHAT["preroll_s"])
    assert sorted(counts, reverse=True) == list(
        traffic.zipf_counts(n_pre, 64, 1.0)
        + traffic.zipf_counts(len(t.items) - n_pre, 64, 1.0))


def test_arrivals_fill_preroll_and_window():
    t = gen(4, seconds=51)
    rate, pre = CHAT["arrivals"]["rate_per_s"], CHAT["preroll_s"]
    due = sorted(i.due for i in t.items)
    win = [d for d in due if d >= 0]
    assert len(due) - len(win) == round(rate * pre)
    assert len(win) == round(rate * 51)
    assert due[0] >= -pre and win[-1] < 51
    assert win[-1] == pytest.approx(51, rel=0.25)


def test_in_service_is_the_equilibrium():
    """As many as the file says, at most the slots; outputs length-biased
    (their mean is E[O^2]/E[O], above the arrivals' E[O]); the tokens
    already served ride in the prompt, and the rest is asked for."""
    t = gen(8)
    m = CHAT["in_service"]["requests"]
    assert len(t.in_service) == m
    assert len(gen(8, in_service=99).in_service) == 32
    grid = traffic.lengths(CHAT["output"], 4096).astype(float)
    whole = traffic.length_biased(CHAT["output"], 4096).astype(float)
    assert whole.mean() == pytest.approx((grid ** 2).mean() / grid.mean(),
                                         rel=0.01)
    for it in t.in_service:
        assert it.due == -CHAT["preroll_s"]
        assert 1 <= it.output_len <= 512
        assert len(it.prompt) + it.output_len <= 4096
    # remaining outputs: about half of the length-biased mean, on average
    rest = np.mean([it.output_len for it in gen(8, in_service=32)
                    .in_service])
    assert rest == pytest.approx(whole.mean() / 2, rel=0.25)
