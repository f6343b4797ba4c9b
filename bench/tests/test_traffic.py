"""Seeded traffic: the same seed repeats exactly, and every seed gets
the same amount of work in each segment, in another order."""
import numpy as np
import pytest

from bench import harness, traffic

CHAT = {"arrivals": {"kind": "poisson", "rate_per_s": 3.0},
        "prompt": {"kind": "lognormal", "median": 768, "sigma": 0.5,
                   "min": 256, "max": 1536, "multiple": 256},
        "output": {"kind": "lognormal", "median": 192, "sigma": 0.6,
                   "min": 32, "max": 512}}
BURST = dict(CHAT, arrivals={"kind": "mmpp2", "rate_per_s": 4.0,
                             "high_s": 2, "low_s": 6, "high_factor": 4})
DOCS = dict(CHAT, prompt={"kind": "uniform", "min": 3072, "max": 7680,
                          "multiple": 512},
            output={"kind": "uniform", "min": 16, "max": 128})
BIG_SEED = 2 ** 31 + 12345


def schedule(tr, seed, duration=48.0, vocab=1000, preroll=8.0):
    return traffic.generate_requests(tr, [(0.0, preroll), (preroll, duration)],
                                     vocab, np.random.default_rng(seed))


@pytest.mark.parametrize("tr", [CHAT, BURST, DOCS], ids=["poisson", "mmpp2",
                                                          "uniform"])
def test_same_seed_repeats_exactly(tr):
    a, b = schedule(tr, BIG_SEED), schedule(tr, BIG_SEED)
    assert len(a) == len(b) > 0
    for (ta, pa, oa), (tb, pb, ob) in zip(a, b):
        assert ta == tb and oa == ob and np.array_equal(pa, pb)


@pytest.mark.parametrize("tr", [CHAT, BURST, DOCS], ids=["poisson", "mmpp2",
                                                          "uniform"])
def test_seeds_share_the_work_in_another_order(tr):
    a, b = schedule(tr, 1), schedule(tr, BIG_SEED)
    for lo, hi in [(0.0, 8.0), (8.0, 48.0)]:
        wa = [(len(p), o) for t, p, o in a if lo <= t < hi]
        wb = [(len(p), o) for t, p, o in b if lo <= t < hi]
        assert len(wa) == len(wb) > 0
        for k in (0, 1):
            assert sorted(x[k] for x in wa) == sorted(x[k] for x in wb)
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]


def test_lengths_are_clipped_and_rounded():
    for _, p, o in schedule(CHAT, 7):
        assert 256 <= len(p) <= 1536 and len(p) % 256 == 0
        assert 32 <= o <= 512
    for _, p, o in schedule(DOCS, 7):
        assert 3072 <= len(p) <= 7680 and len(p) % 512 == 0


def test_poisson_rate_and_span():
    due = [t for t, *_ in schedule(CHAT, 3, duration=40.0)]
    assert len(due) == 120
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 40.0
    assert sum(8.0 <= t < 40.0 for t in due) == 96


@pytest.mark.parametrize("preroll", [0.0, 8.0, 5.0])
def test_mmpp2_high_phase_runs_four_times_the_low_rate(preroll):
    due = np.array([t for t, *_ in schedule(BURST, 5, duration=80.0,
                                            preroll=preroll)])
    assert len(due) == pytest.approx(4.0 * 80, abs=2)
    phase = due % 8.0
    low = np.sum(phase < 6.0) / (6.0 * 10)
    high = np.sum(phase >= 6.0) / (2.0 * 10)
    assert high == pytest.approx(4 * low, rel=0.05)


def test_chunk_lengths_cover_every_remainder():
    assert harness.chunk_lengths({256, 512, 768, 1536}, 512) == [256, 512]
    assert harness.chunk_lengths({128, 640}, 512) == [128, 512]
