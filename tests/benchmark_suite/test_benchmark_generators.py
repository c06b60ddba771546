"""Seeded traffic: reproducible from a seed, other contents under another
seed, and the same WORK (sizes, times, order) under every seed."""

import numpy as np
import pytest

from benchmark.lib import clock, generators as G

TRAFFIC = {
    "population_seed": 7, "arrivals": {"cv": 1.0, "rate_per_s": 10.0},
    "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                   "min": 32, "max": 2048},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 16, "max": 512},
}


def _sizes(reqs):
    return sorted((len(r.prompt), r.want) for r in reqs)


def test_same_seed_same_requests():
    a = G.requests(TRAFFIC, 2**31 + 11, 200, 32768, span_s=20.0)
    b = G.requests(TRAFFIC, 2**31 + 11, 200, 32768, span_s=20.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.want == y.want
               for x, y in zip(a, b))


def test_other_seed_other_tokens_same_schedule():
    a = G.requests(TRAFFIC, 1, 200, 32768, span_s=20.0)
    b = G.requests(TRAFFIC, 2, 200, 32768, span_s=20.0)
    assert [(len(r.prompt), r.want, r.due_s) for r in a] == \
        [(len(r.prompt), r.want, r.due_s) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # another traffic file (its population_seed) is another schedule
    c = G.requests(dict(TRAFFIC, population_seed=8), 1, 200, 32768,
                   span_s=20.0)
    assert _sizes(a) != _sizes(c)


def test_arrivals_fill_the_span_and_lengths_are_clipped():
    reqs = G.requests(TRAFFIC, 3, 500, 1000, span_s=50.0)
    due = np.array([r.due_s for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 50.0
    lens = np.array([len(r.prompt) for r in reqs])
    want = np.array([r.want for r in reqs])
    assert lens.min() >= 32 and lens.max() <= 2048
    assert want.min() >= 16 and want.max() <= 512
    assert 200 < np.median(lens) < 330 and 100 < np.median(want) < 160
    assert all(r.prompt.min() >= 1 and r.prompt.max() < 1000 for r in reqs)


def test_burstier_gaps_have_the_asked_variation():
    rng = G.rng_for(5, 0)
    for cv in (1.0, 3.0):
        g = G.gaps({"cv": cv}, 20000, 2000.0, rng)
        assert g.sum() == pytest.approx(2000.0)
        assert g.std() / g.mean() == pytest.approx(cv, rel=0.1)


def test_closed_loop_population_and_shared_prefix():
    t = dict(TRAFFIC, prefix={"groups": 2, "len": {"dist": "fixed",
                                                   "value": 24}})
    reqs = G.requests(t, 9, 40, 500)
    assert all(r.due_s == 0.0 for r in reqs)
    heads = {tuple(r.prompt[:24]) for r in reqs}
    assert len(heads) == 2
    plain = G.requests(TRAFFIC, 9, 40, 500)
    assert len({tuple(r.prompt[:24]) for r in plain}) == 40


@pytest.mark.parametrize("dist,spec", [
    ("uniform", {"dist": "uniform", "min": 32, "max": 128}),
    ("fixed", {"dist": "fixed", "value": 77}),
])
def test_other_length_distributions(dist, spec):
    x = G.lengths(spec, 1000, G.rng_for(1, 0))
    if dist == "fixed":
        assert set(x) == {77}
    else:
        assert x.min() == 32 and x.max() == 128
    with pytest.raises(ValueError):
        G.lengths({"dist": "zipf"}, 3, G.rng_for(1, 0))


@pytest.mark.parametrize("q", [0, 5, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 10, 137])
def test_percentile_is_numpys(q, n):
    xs = np.random.default_rng(n).gamma(2.0, 3.0, n)
    assert clock.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_edges():
    assert clock.percentile([], 50) is None
    assert clock.median([3, 1, 2]) == 2
    with pytest.raises(ValueError):
        clock.percentile([1], 101)
    # ten samples beyond the 90th percentile need a hundred requests
    assert clock.samples_beyond(100, 90) == 10
    assert clock.samples_beyond(99, 95) == 4
    assert clock.process_age_s() > 0


# -- the tail mean (``itl_tail_mean_ms``, PR 33) -------------------------------
def _gaps(n):
    """``n`` gaps of ONE distribution, by its quantile function: a plain
    step of about 20 ms and a tenth of the steps behind a prefill."""
    u = (np.arange(n) + 0.5) / n
    return np.where(u < 0.9, 18.0 + 4.0 * u, 30.0 + 400.0 * (u - 0.9) ** 2)


def _two_populations(n, upper_share, lower=24.0, upper=34.0):
    k = int(round(n * upper_share))
    return [lower] * (n - k) + [upper] * k


@pytest.mark.parametrize("n", [100, 1000, 2700])
def test_tail_mean_is_the_plain_mean_of_the_ranks_90_to_99(n):
    xs = np.random.default_rng(n).gamma(2.0, 3.0, n)
    plain = np.sort(xs)[n * 90 // 100:n * 99 // 100].mean()
    assert clock.tail_mean(xs) == pytest.approx(plain, rel=1e-9)
    assert clock.tail_mean(xs[::-1], 90.0, 99.0) == pytest.approx(plain)


@pytest.mark.parametrize("n", [999, 1001, 27183])
def test_tail_mean_is_continuous_in_the_sample_size(n):
    assert clock.tail_mean(_gaps(n)) == pytest.approx(
        clock.tail_mean(_gaps(1000)), rel=0.01)


@pytest.mark.parametrize("n", [2000, 20000])
def test_tail_mean_does_not_step_where_the_percentile_does(n):
    """The share of gaps behind a prefill crosses 5 %: the 95th percentile
    moves by the whole distance between the two populations, the tail mean
    by a ninth of it (1 % of the sample over the 9 % the band is wide)."""
    below, above = _two_populations(n, 0.045), _two_populations(n, 0.055)
    step = 34.0 - 24.0
    assert clock.percentile(above, 95) - clock.percentile(below, 95) == \
        pytest.approx(step)
    moved = clock.tail_mean(above) - clock.tail_mean(below)
    assert 0 < moved < step / 8
    assert moved == pytest.approx(step / 9, rel=1e-6)


@pytest.mark.parametrize("n", [100, 1000, 20000])
def test_tail_mean_leaves_out_the_slowest_hundredth(n):
    """A freeze of the shared host owns the slowest gaps of a run: a
    hundredth of the sample at 1000 times its value moves nothing."""
    xs = np.sort(np.random.default_rng(n).gamma(2.0, 3.0, n))
    frozen = xs.copy()
    frozen[n - n // 100:] *= 1000.0
    assert clock.tail_mean(frozen) == pytest.approx(clock.tail_mean(xs),
                                                    rel=1e-9)
    # and it is a tail: above the median, below the 99th percentile
    assert clock.median(xs) < clock.tail_mean(xs) < clock.percentile(xs, 99)


def test_tail_mean_edges():
    assert clock.tail_mean([]) is None
    assert clock.tail_mean([5.0]) == 5.0
    assert clock.tail_mean([1, 2, 3], 0.0, 100.0) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        clock.tail_mean([1, 2], 99.0, 90.0)


def test_end_to_end_prints_the_tail_mean_beside_every_percentile():
    from benchmark.lib import serving

    gaps = _gaps(5000).tolist()
    out = serving.end_to_end({"ttft_ms": [100.0, 140.0], "itl_ms": gaps},
                             tokens_completed=800, seconds=40.0)
    assert out["itl_tail_mean_ms"] == pytest.approx(clock.tail_mean(gaps))
    assert out["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95))
    assert out["itl_p90_ms"] < out["itl_tail_mean_ms"] < out["itl_p99_ms"]
    assert {f"{k}_p{q}_ms" for k in ("ttft", "itl")
            for q in (50, 75, 90, 95, 99)} <= set(out)
    assert out["ttft_mean_ms"] == 120.0 and out["serve_tok_s"] == 20.0
    empty = serving.end_to_end({"ttft_ms": [], "itl_ms": []}, 0, 40.0)
    assert empty["itl_tail_mean_ms"] is None and empty["itl_p95_ms"] is None
