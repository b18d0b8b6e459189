"""The comparison that decides `correct`.

After the window closes, a sample of its decision windows, drawn from
the seed (all of them when they hold at most `cap` requests, and always
the five largest), is decided again by the plain reference
(`bench/reference.py`) from the inputs the program was handed, with
the program's own picks and predicted lengths forced along the scan.
Three numbers are held to limits:

    decide_p99  99th percentile over the compared requests of each
                request's gap: 1 where the program's pick is not the
                reference's pick at the same dead-reckoned state (Eq. 1
                scoring, admission, the scan), else the larger relative
                gap of the predicted output length (KNN neighbours,
                weights, label mix) and of the predicted latency (GBM
                TPOT, wait, dead reckoning, and the prefix-affinity
                discount where the configuration weighs it) at the pick
    slot_miss   share of the compared windows whose dead-reckoned batch
                or free slots after the scan differ from the
                reference's anywhere: whole numbers, compared exactly
                (limit 0)
    work_gap    widest relative gap, over the compared windows and their
                live instances, of the pending work the scan added to an
                instance (the dead-reckoned pending work after the scan
                less the telemetry before it)

The two state numbers also hold the telemetry the program synced to the
device to the host's: both are taken against the host's telemetry.

Where the configuration weighs prefix affinity, the reference hashes
each compared request's prompt tokens itself and matches them against
the instances' prefix sketches as the host held them at the decision;
`hit_share`, the share of compared requests with a prefix hit at the
program's pick, is printed beside the numbers (not held): it shows
that the term did work.

A percentile and not the widest gap for the requests: float32 rounding
resolves a neighbour near-tie one way on the chip and the other in numpy
on about one request in two thousand, which moves that request's length
by a few per cent and can move its pick. Such requests are counted
(`len_flip`, `pick_miss`, printed beside the numbers) but sit above the
99th percentile; a fault that touches one request in a hundred or more
does not. The pending work is free of them, since the scan is forced
with the program's own lengths: what is left is float32 summation. The
limits are in `bench/limits/<workload>.json` (or `default.json`);
PERF.md gives the readings each was set from.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from . import reference as ref
from .stats import pct

BENCH = Path(__file__).resolve().parent
NUMBERS = ("decide_p99", "slot_miss", "work_gap")
FLIP = 1e-3           # a length gap beyond rounding: another neighbour set


def fleet_of(bundle, instances, config: dict) -> ref.Fleet:
    """The reference's inputs from the raw estimator data: the KNN rows
    and labels, and each tier's fitted trees — not the tables the
    decision program packs from them."""
    knn = bundle.knn
    tier_names: List[str] = []
    for inst in instances:
        if inst.tier.name not in tier_names:
            tier_names.append(inst.tier.name)
    trees = []
    for name in tier_names:
        m = bundle.heads[name].model
        trees.append(ref.Trees(
            feature=np.stack([t.feature for t in m.trees]).astype(np.int64),
            threshold=np.stack([t.threshold for t in m.trees]).astype(
                np.float32),
            leaf=np.stack([t.leaf for t in m.trees]).astype(np.float32),
            depth=int(m.depth), base=float(m.base), lr=float(m.lr)))
    f32 = np.float32
    return ref.Fleet(
        index=np.asarray(knn._x, f32), quality=np.asarray(knn._quality, f32),
        length=np.asarray(knn._length, f32), k=int(knn.k),
        eps=float(knn.eps),
        model_of=np.array([i.model_idx for i in instances]),
        tier_of=np.array([tier_names.index(i.tier.name) for i in instances]),
        max_batch=np.array([i.tier.max_batch for i in instances], f32),
        price_in=np.array([i.tier.price_in for i in instances], f32),
        price_out=np.array([i.tier.price_out for i in instances], f32),
        trees=trees, weights=tuple(config["decision"]["weights"]),
        w_aff=float(config["decision"].get("affinity_weight", 0.0)))


def load_limits(workload: str) -> Dict[str, float]:
    path = BENCH / "limits" / f"{workload}.json"
    if not path.exists():
        path = BENCH / "limits" / "default.json"
    raw = json.loads(path.read_text())
    return {k: float(raw[k]) for k in NUMBERS}


def sample(captured: List[dict], rng: np.random.Generator,
           cap: int) -> List[dict]:
    """All windows when they hold at most `cap` requests; else the five
    largest and then windows in seeded random order up to `cap`."""
    sizes = np.array([len(c["rows"]) for c in captured])
    if sizes.sum() <= cap:
        return list(captured)
    largest = list(np.argsort(-sizes, kind="stable")[:5])
    rest = [int(j) for j in rng.permutation(len(captured))
            if j not in largest]
    keep, total = [], 0
    for j in [int(j) for j in largest] + rest:
        if total >= cap:
            break
        keep.append(j)
        total += int(sizes[j])
    return [captured[j] for j in sorted(keep)]


def window_inputs(c: dict, fleet: ref.Fleet) -> ref.Window:
    """A captured window as the reference reads it over `fleet`'s
    instances; the prompt tokens and prefix sketches only where the
    fleet weighs prefix affinity."""
    n_real = len(fleet.model_of)
    cols, rows, tel = c["cols"], c["rows"], c["tel"]
    prow = cols.prompt_row[rows]
    w = ref.Window(
        emb=np.asarray(cols.emb[prow], np.float32),
        budget=np.asarray(cols.budget[rows], np.float64),
        len_in=np.asarray(cols.len_in[rows], np.float32),
        pending=tel["pending"][:n_real], batch=tel["batch"][:n_real],
        free=tel["free"][:n_real], ctx=tel["ctx"][:n_real],
        alive=tel["alive"][:n_real].astype(bool))
    if fleet.w_aff > 0.0:
        w.tokens = np.asarray(cols.tokens[prow])
        w.tok_len = np.asarray(cols.tok_len[prow])
        w.sketch = tel["prefix_sig"][:n_real]
    return w


def compare(fleet: ref.Fleet, captured: List[dict],
            rng: np.random.Generator, cap: int,
            precision: str = "highest", outputs=None) -> Dict[str, float]:
    """The numbers over a seeded sample of the captured windows.
    `outputs`, when given, replaces the program's outputs per window
    (a control of `CONTROLS` puts the reference's own answers there)."""
    import jax
    picked = sample(captured, rng, cap)
    if not picked:
        return {"compared": 0}
    got = jax.device_get([c["out"] for c in picked])
    I = len(fleet.model_of)
    wins = [window_inputs(c, fleet) for c in picked]
    emb = np.concatenate([w.emb for w in wins])
    qual, leng = ref.knn_labels(emb, fleet, precision)
    if outputs is not None:
        got = outputs(fleet, wins)
    req_gaps, work_gaps = [], []
    misses = flips = slot_misses = hits = 0
    at = 0
    for w, out in zip(wins, got):
        choice, est, l_chosen, d1, b1, f1 = (np.asarray(o, np.float64)
                                             for o in out)
        R = w.emb.shape[0]
        picks = choice[:R].astype(np.int64)
        dec = ref.decide(fleet, w, qual[at:at + R], leng[at:at + R], picks,
                         l_chosen[:R])
        at += R
        miss = dec.pick != picks
        g_len = _rel(l_chosen[:R], dec.length_at)
        g_lat = _rel(est[:R], dec.latency_at)
        misses += int(miss.sum())
        flips += int((g_len > FLIP).sum())
        hits += int((dec.hit_at > 0).sum())
        req_gaps.append(np.where(miss, 1.0, np.maximum(g_len, g_lat)))
        live = w.alive
        before = w.pending.astype(np.float64)
        work = (np.abs((d1[:I] - before) - (dec.pending - before))
                / np.maximum(dec.pending - before, 1.0))
        work_gaps.append(float(np.max(work[live])))
        slot_misses += int(np.any((b1[:I] != dec.batch)[live])
                           or np.any((f1[:I] != dec.free)[live]))
    gaps = np.concatenate(req_gaps)
    out = {"compared": int(len(gaps)), "windows": len(wins),
           "decide_p99": pct(gaps, 99), "slot_miss": slot_misses / len(wins),
           "pick_miss": misses / len(gaps), "len_flip": flips / len(gaps),
           "decide_max": float(gaps.max()),
           "work_gap": float(max(work_gaps))}
    if fleet.w_aff > 0.0:
        out["hit_share"] = hits / len(gaps)
    return out


def _rel(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    want = np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(np.abs(want), 1e-9)


def reference_outputs(fleet: ref.Fleet, wins: List[ref.Window],
                      precision: str):
    """The reference's own answers per window, shaped as the program's
    (choice, est, l_chosen, d1, b1, f1)."""
    emb = np.concatenate([w.emb for w in wins])
    qual, leng = ref.knn_labels(emb, fleet, precision)
    out, at = [], 0
    for w in wins:
        R = w.emb.shape[0]
        dec = ref.decide(fleet, w, qual[at:at + R], leng[at:at + R])
        at += R
        out.append((dec.pick, dec.latency_at, dec.length_at, dec.pending,
                    dec.batch, dec.free))
    return out


# the controls, each the reference's own answers put in the program's
# place (`bench/control.py --control <name>`): `knn_high` with the KNN
# distance one precision step down (three-pass bfloat16); `affinity_off`
# at the stated precision with the prefix-affinity term dropped
CONTROLS = {
    "knn_high": lambda fleet, wins: reference_outputs(fleet, wins, "high"),
    "affinity_off": lambda fleet, wins: reference_outputs(
        dataclasses.replace(fleet, w_aff=0.0), wins, "highest"),
}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each number beside its limit; nothing compared fails them all."""
    out = {}
    for k in NUMBERS:
        v = numbers.get(k)
        ok = v is not None and numbers.get("compared", 0) > 0 \
            and v <= limits[k]
        out[k] = {"value": v, "limit": limits[k], "ok": bool(ok)}
    return out
