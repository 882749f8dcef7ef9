"""Arrival and length generators, and the corpus copy."""
import numpy as np
import pytest

from perfbench import gen

CHAT = {"prompt_len": {"dist": "lognormal", "median": 160, "sigma": 0.9,
                       "lo": 16, "hi": 1024},
        "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                       "lo": 8, "hi": 384},
        "arrivals": {"process": "poisson", "rate_per_s": 10.0}}
CLOSED = {"prompt_len": {"dist": "uniform", "lo": 512, "hi": 1536},
          "output_len": {"dist": "uniform", "lo": 128, "hi": 512},
          "arrivals": {"process": "closed", "backlog": 8}}


def test_same_seed_same_schedule():
    a = gen.request_schedule(CHAT, 11, 30.0)
    assert a == gen.request_schedule(CHAT, 11, 30.0)


def test_unused_seed_differs_in_order_not_in_work():
    a = gen.request_schedule(CLOSED, 11, None, count=128)
    b = gen.request_schedule(CLOSED, 12, None, count=128)
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    for key in ("prompt_len", "output_len"):     # whole blocks: the same set
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)


def test_seed_past_32_bits():
    s = 2 ** 31 + 12345
    a = gen.request_schedule(CHAT, s, 5.0)
    assert a and a == gen.request_schedule(CHAT, s, 5.0)
    assert gen.prompt_ids(s, 3, 40, 1000) == gen.prompt_ids(s, 3, 40, 1000)
    assert len(gen.synthetic_corpus(100, 64, s)) == 100


def test_open_schedule_rate_and_bounds():
    reqs = gen.request_schedule(CHAT, 5, 60.0)
    assert 0.85 * 600 < len(reqs) < 1.15 * 600
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and due[-1] < 60.0
    assert all(16 <= r["prompt_len"] <= 1024 for r in reqs)
    assert all(8 <= r["output_len"] <= 384 for r in reqs)
    med = np.median([r["prompt_len"] for r in reqs])
    assert 130 < med < 190


@pytest.mark.parametrize("spec,mean", [
    ({"dist": "uniform", "lo": 128, "hi": 512}, 320.0),
    ({"dist": "uniform", "lo": 512, "hi": 1536}, 1024.0),
    ({"dist": "lognormal", "median": 96, "sigma": 0.7}, 96 * np.exp(0.245)),
    ({"dist": "lognormal", "median": 160, "sigma": 0.9, "lo": 16, "hi": 1024},
     230.5)])
def test_block_mean(spec, mean):
    assert gen.block_values(spec).mean() == pytest.approx(mean, rel=0.03)


def test_poisson_arrivals_are_independent_exponential_gaps():
    """A Poisson process: the count in a window varies from seed to seed
    with variance about its mean, the gaps' deviation is about their mean,
    and gaps far over the mean occur (no grid caps them)."""
    counts, gaps = [], []
    for seed in range(60):
        due = [r["due_s"] for r in gen.request_schedule(CHAT, seed, 16.0)]
        counts.append(len(due))
        gaps.extend(np.diff(due))
    assert 150 < np.mean(counts) < 170          # 10 req/s x 16 s
    assert 0.5 * 160 < np.var(counts) < 2.0 * 160
    assert len(set(counts)) > 10
    assert 0.9 < np.std(gaps) / np.mean(gaps) < 1.1
    assert max(gaps) > 6 * np.mean(gaps)


def test_unknown_arrival_process_is_an_error():
    with pytest.raises(ValueError):
        gen.request_schedule(dict(CHAT, arrivals={"process": "gamma",
                                                  "rate_per_s": 1.0}), 1, 5.0)


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        gen.block_values({"dist": "zipf"})


def test_prompt_ids_never_pad():
    ids = gen.prompt_ids(3, 0, 5000, 50)
    assert min(ids) >= 1 and max(ids) < 50


def test_corpus_is_a_copy_of_the_programs():
    from paddle_tpu.io.token_loader import synthetic_corpus
    assert np.array_equal(gen.synthetic_corpus(3000, 512, 9),
                          synthetic_corpus(3000, 512, 9))
    assert not np.array_equal(gen.synthetic_corpus(3000, 512, 9),
                              gen.synthetic_corpus(3000, 512, 10))
