"""Multi-turn chat sessions: the law of a mix's `sessions` block.

A mix (`bench/traffic/<name>.json`) may hold, beside its one-shot
stream (its top-level `process` and `rate_rps`), a chat stream:

    "sessions": {"process": "gamma", "cv": 2.0, "rate_rps": 10.0,
                 "turns": 5, "base_len": 48, "extend": [12, 28],
                 "think_s": 5.0}

    process    how conversations start: an arrival process of
               `bench/processes/`, reading this block's own parameters
               (`cv` for `gamma`), at rate_rps / turns
    rate_rps   turns per second of the chat stream
    turns      turns per conversation; turns past `horizon_s` are
               dropped
    think_s    mean of the exponential think time from one turn's
               arrival to the next's
    base_len   turn 1 is the first base_len tokens of a test prompt
               dealt by the seed
    extend     [lo, hi]: turn u is turn u-1's tokens plus lo to hi
               (inclusive) fresh tokens drawn from [1, VOCAB), cut to
               the first MAX_TOKENS

So a follow-up's prompt begins with its predecessor's, and its prefix
signatures with its predecessor's: a router that sends it where the
conversation's prefix is cached skips most of its prefill. Turn spacing
follows the user (the think time), not the length of the trace.

The turn law is that of `repro.serving.scenarios._session_prompts`
(base prompt, growth, cap), copied here so that the yardstick cannot
move with the program; its spacing is the think time above in place of
that function's round-robin over the whole trace.

The spacing law is the closed-loop user of web benchmarks (TPC-W's
emulated browsers think for a negative-exponential time). No measured
law or mean of the gap between two turns of one LLM chat is in this
repository: a mix that uses this block has to name the source of its
`think_s` (such as the conversation traces of Mooncake, arXiv:2407.00079,
or the KV-cache reuse-time study, arXiv:2506.02634), and a source whose
gaps are not exponential needs a law of its own here.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from . import arrivals as traffic

VOCAB = 4096          # the world's token ids (repro.serving.world.VOCAB)
MAX_TOKENS = 128      # the world's embedding window: longest prompt


def chat_turns(mix: traffic.Mix, prompts: list, rng: np.random.Generator
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                          List[np.ndarray]]:
    """Every turn of the chat stream that arrives by `horizon_s`:
    (arrival (n,), conversation (n,), base row into `prompts` (n,),
    tokens per turn), conversation by conversation, turns in order.
    `prompts` are the dealt test prompts (anything with `.tokens`).
    A mix without a `sessions` block has no turns and draws nothing."""
    spec = mix.params.get("sessions")
    if spec is None:
        return (np.zeros(0, np.float64), np.zeros(0, np.int64),
                np.zeros(0, np.int64), [])
    turns = int(spec["turns"])
    lo, hi = (int(v) for v in spec["extend"])
    starts_mix = dataclasses.replace(
        mix, process=spec["process"],
        rate_rps=float(spec["rate_rps"]) / turns, params=spec)
    starts = traffic.process(spec["process"]).arrivals(starts_mix, rng)
    n = len(starts)
    base = rng.integers(0, len(prompts), n)
    gaps = rng.exponential(float(spec["think_s"]), (n, turns - 1))
    grow = rng.integers(lo, hi + 1, (n, turns - 1))
    fresh = rng.integers(1, VOCAB, (n, turns - 1, hi), dtype=np.int32)
    at = np.concatenate([starts[:, None],
                         starts[:, None] + np.cumsum(gaps, axis=1)], axis=1)
    arrival, convs, rows, tokens = [], [], [], []
    for c in range(n):
        toks = np.asarray(prompts[base[c]].tokens[:int(spec["base_len"])],
                          np.int32)
        for u in range(turns):
            if u:
                toks = np.concatenate(
                    [toks, fresh[c, u - 1, :grow[c, u - 1]]])[:MAX_TOKENS]
            if at[c, u] > mix.horizon_s:
                break
            arrival.append(at[c, u])
            convs.append(c)
            rows.append(int(base[c]))
            tokens.append(toks)
    return (np.array(arrival, np.float64), np.array(convs, np.int64),
            np.array(rows, np.int64), tokens)


def validate(spec: dict, where: str) -> None:
    """Refuse a `sessions` block that lacks a key or names no process."""
    need = ("process", "rate_rps", "turns", "base_len", "extend", "think_s")
    missing = [k for k in need if k not in spec]
    if missing:
        raise ValueError(f"{where}: sessions lacks {missing}")
    traffic.process(spec["process"])
    lo, hi = spec["extend"]
    if not (int(spec["turns"]) >= 1 and 1 <= lo <= hi
            and float(spec["think_s"]) > 0 and float(spec["rate_rps"]) > 0):
        raise ValueError(f"{where}: need turns >= 1, 1 <= extend[0] <= "
                         f"extend[1], think_s > 0, rate_rps > 0")
