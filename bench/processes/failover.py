"""Poisson arrivals (the law of `poisson.py`) through a repeating cycle
of instance failures on the simulated clock: fail-stop, restart, and a
hidden slowdown.

Cycle k starts at fill_s + first_s + k * cycle_s (the mix's keys). At
its start the instances at roster positions (k + stride * j) mod I,
j < fail_count, fail (`Instance.fail()`); recover_s later they restart
on a clean slate (`Instance.recover`); from slow_from_s to slow_until_s
into the cycle the instances at (k + slow_offset + stride * j) mod I,
j < slow_count, run `slowdown` times slower, unseen by telemetry, then
at speed again. The targets depend on k alone, never on the seed, so
the failures' weight adds no spread across seeds.

The guarantee the cell holds is that every request of the window is
served exactly once: a failure's victims are retried and a hedge's
loser is cancelled by the program's recovery stack (`sim.recovery`).
Without it the victims would fail terminally, so `schedule` refuses a
sim that carries none.
"""
from bench.arrivals import renewal

KINDS = ("fail", "recover", "slow", "speed")


def arrivals(mix, rng):
    return renewal(mix, lambda n: rng.exponential(1.0 / mix.rate_rps, n))


def events(mix, n_instances: int):
    """(time, kind, roster positions) of every event before horizon_s,
    in time order; kind is one of `KINDS`."""
    p = mix.params
    stride = int(p["stride"])

    def at(first: int, count: int):
        return tuple((first + stride * j) % n_instances
                     for j in range(count))

    out = []
    k = 0
    while True:
        t0 = mix.fill_s + float(p["first_s"]) + k * float(p["cycle_s"])
        if t0 >= mix.horizon_s:
            break
        failed = at(k, int(p["fail_count"]))
        slow = at(k + int(p["slow_offset"]), int(p["slow_count"]))
        out += [(t0, "fail", failed),
                (t0 + float(p["recover_s"]), "recover", failed),
                (t0 + float(p["slow_from_s"]), "slow", slow),
                (t0 + float(p["slow_until_s"]), "speed", slow)]
        k += 1
    return sorted((e for e in out if e[0] < mix.horizon_s),
                  key=lambda e: e[0])


def schedule(sim, mix, rng):
    """Push the cycle's events onto the simulator."""
    if getattr(sim, "recovery", None) is None:
        raise RuntimeError(
            "failover: the sim carries no recovery stack (sim.recovery), "
            "so a failure's victims would fail instead of being served "
            "exactly once; arm it from the configuration's "
            "decision.recovery block")
    slowdown = float(mix.params["slowdown"])
    act = {"fail": lambda inst, t: inst.fail(),
           "recover": lambda inst, t: inst.recover(t),
           "slow": lambda inst, t: inst.set_slowdown(slowdown),
           "speed": lambda inst, t: inst.set_slowdown(1.0)}
    for t, kind, positions in events(mix, len(sim.instances)):
        for pos in positions:
            sim.push(t, lambda tt, inst=sim.instances[pos], f=act[kind]:
                     f(inst, tt))
