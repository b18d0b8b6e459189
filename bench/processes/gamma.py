"""Bursty arrivals: gamma gaps of mean 1/rate_rps and coefficient of
variation `cv` (the BurstGPT shape, arXiv:2401.17644; the law of
`repro.serving.workload.gamma_bursty_arrivals`, copied)."""
from bench.arrivals import renewal


def arrivals(mix, rng):
    shape = 1.0 / float(mix.params["cv"]) ** 2
    return renewal(mix, lambda n: rng.gamma(
        shape, 1.0 / (mix.rate_rps * shape), n))
