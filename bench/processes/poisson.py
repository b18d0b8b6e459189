"""Poisson arrivals: exponential gaps of mean 1/rate_rps (the law of
`repro.serving.workload`, copied)."""
from bench.arrivals import renewal


def arrivals(mix, rng):
    return renewal(mix, lambda n: rng.exponential(1.0 / mix.rate_rps, n))
