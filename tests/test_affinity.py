"""Prefix-cache / session-affinity term (`repro.serving.affinity` +
``RBConfig.affinity_weight``): signature math, sketch lifecycle,
backend-exact hit scoring, decision steering across all three
backends, the zero-recompile pin through session churn, and the
SoA-ingest re-entrancy fixes that rode along (stale retry row stamps,
all-or-nothing embedding resume)."""
import dataclasses

import numpy as np
import pytest

from repro.core import RBConfig, RouteBalance
from repro.serving.affinity import (PREFIX_BLOCK, SIG_WIDTH, SKETCH_SLOTS,
                                    PrefixSketch, hit_fraction,
                                    prefix_signatures, prompt_signatures)
from repro.serving.cluster import ClusterSim
from repro.serving.request import Request, RequestColumns, batch_columns
from repro.serving.scenarios import (Scenario, TenantSpec, get_scenario,
                                     randomize_prefix_state,
                                     randomize_telemetry)
from repro.serving.world import Prompt

BACKENDS = ("numpy", "jax", "fused")


def _prompt(pid, toks):
    toks = np.asarray(toks, np.int32)
    return Prompt(pid=pid, topic=0, difficulty=0.5, verbosity=0.5,
                  tokens=toks, len_in=int(toks.size))


def _req(rid, prompt, arrival=0.0):
    return Request(rid=rid, prompt=prompt, arrival=arrival,
                   true_quality=np.full(8, 0.5),
                   true_length=np.full(8, 40.0))


# -- signatures ---------------------------------------------------------------

def test_signatures_are_int32_with_zero_sentinel():
    rng = np.random.default_rng(0)
    toks = rng.integers(1, 4096, (4, 128)).astype(np.int32)
    lens = np.array([128, 40, 16, 7])
    sig = prefix_signatures(toks, lens)
    assert sig.dtype == np.int32 and sig.shape == (4, SIG_WIDTH)
    # column d is 0 exactly where the prompt does not reach block d
    blocks = np.minimum(-(-lens // PREFIX_BLOCK), SIG_WIDTH)
    for p in range(4):
        assert (sig[p, :blocks[p]] != 0).all(), (p, sig[p])
        assert (sig[p, blocks[p]:] == 0).all(), (p, sig[p])


def test_signatures_shared_prefix_shares_leading_columns():
    rng = np.random.default_rng(1)
    a = rng.integers(1, 4096, 128).astype(np.int32)
    b = a.copy()
    b[48:] = rng.integers(1, 4096, 80)       # diverge inside block 3
    sig = prefix_signatures(np.stack([a, b]), np.array([128, 128]))
    assert (sig[0, :3] == sig[1, :3]).all()  # blocks 0..2 identical
    assert (sig[0, 3:] != sig[1, 3:]).all()  # divergence cascades


def test_signatures_padding_invariant():
    """The SoA scoring path hashes the zero-padded column matrix; the
    dispatch path hashes the raw per-prompt array. Identical results
    required — the masked update makes padding invisible."""
    rng = np.random.default_rng(2)
    raw = rng.integers(1, 4096, 37).astype(np.int32)
    padded = np.zeros((1, 128), np.int32)
    padded[0, :37] = raw
    s_raw = prefix_signatures(raw[None, :], np.array([37]))
    s_pad = prefix_signatures(padded, np.array([37]))
    np.testing.assert_array_equal(s_raw, s_pad)
    p = _prompt(0, raw)
    np.testing.assert_array_equal(prompt_signatures(p), s_raw[0])
    assert prompt_signatures(p) is prompt_signatures(p)   # memoized


def test_columns_prefix_sig_matches_prompt_signatures():
    rng = np.random.default_rng(3)
    reqs = [_req(i, _prompt(i, rng.integers(1, 4096, int(n))))
            for i, n in enumerate(rng.integers(5, 128, 12))]
    cols = RequestColumns.from_requests(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            cols.prefix_sig[cols.prompt_row[r.row]],
            prompt_signatures(r.prompt))


# -- sketch -------------------------------------------------------------------

def test_sketch_insert_hit_and_leading_run():
    sig = prefix_signatures(np.arange(1, 129)[None, :].astype(np.int32),
                            np.array([128]))[0]
    sk = PrefixSketch()
    sk.insert(sig[:3])                       # first 48 tokens cached
    assert sk.hit_tokens(sig, 128) == 3 * PREFIX_BLOCK
    assert sk.hit_tokens(sig, 40) == 40      # capped at the prompt len
    # a hole in the run stops the trie walk
    sk2 = PrefixSketch()
    sk2.insert([int(sig[0]), int(sig[2])])
    assert sk2.hit_tokens(sig, 128) == PREFIX_BLOCK


def test_sketch_lru_eviction_and_mirror():
    sk = PrefixSketch(capacity=4)
    sk.insert([1, 2, 3, 4])
    sk.insert([1])                           # touch 1: now 2 is LRU
    sk.insert([5])
    assert set(sk.slots) == {1, 3, 4, 5}
    row = sk.mirror()
    assert row.dtype == np.int32 and row.shape == (4,)
    assert set(row.tolist()) == {1, 3, 4, 5}
    sk.clear()
    assert len(sk) == 0 and (sk.mirror() == 0).all()


def test_hit_fraction_numpy_jax_bitwise():
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    toks = rng.integers(1, 4096, (6, 128)).astype(np.int32)
    lens = rng.integers(4, 129, 6)
    req_sig = prefix_signatures(toks, lens)
    plane = np.zeros((5, SKETCH_SLOTS), np.int32)
    for i in range(5):                       # partial-prefix caches
        sk = PrefixSketch()
        sk.insert(req_sig[i % 6, :rng.integers(1, SIG_WIDTH + 1)])
        sk.mirror(out=plane[i])
    lenf = lens.astype(np.float32)
    h_np = hit_fraction(req_sig, lenf, plane.T, np)
    h_j = np.asarray(hit_fraction(jnp.asarray(req_sig),
                                  jnp.asarray(lenf),
                                  jnp.asarray(plane).T, jnp))
    np.testing.assert_array_equal(h_np, h_j)          # bitwise
    assert h_np.dtype == np.float32
    assert (h_np >= 0).all() and (h_np <= 1).all()
    assert h_np.max() > 0                    # the caches really match
    # scalar sketch walk agrees with the vectorized form
    for i in range(5):
        sk = PrefixSketch()
        sk.insert(plane[i])
        for r in range(6):
            frac = sk.hit_tokens(req_sig[r], int(lens[r])) \
                / max(float(lens[r]), 1.0)
            assert h_np[r, i] == pytest.approx(frac), (r, i)


# -- dead reckoning on dispatch / finish / fail -------------------------------

@pytest.fixture(scope="module")
def chat_run():
    run = get_scenario("session_chat").build(dataset_n=300)
    run.bundle()
    return run


def _mini_sim(n_tiers=1, n_instances=3, seed=0):
    from repro.serving.scenarios import synthetic_pool
    tiers, names, _ = synthetic_pool(n_tiers, n_instances, seed=seed)
    return ClusterSim(tiers, names, seed=0)


def test_submit_stamps_hit_inserts_and_mirrors():
    sim = _mini_sim()
    inst = sim.instances[0]
    rng = np.random.default_rng(5)
    p = _prompt(0, rng.integers(1, 4096, 64))
    sig = prompt_signatures(p)
    inst.submit(_req(0, p), 0.0, 10.0, None)
    # cold cache: no hit, but the prompt is credited and mirrored
    assert sim.completed == []
    assert inst.sketch.hit_tokens(sig, 64) == 64
    assert set(sig[sig != 0].tolist()) <= set(
        sim.tel.prefix_sig[inst.slot].tolist())
    v = sim.tel.prefix_version
    r2 = _req(1, p)
    inst.submit(r2, 0.1, 10.0, None)
    assert r2.prefix_hit == pytest.approx(1.0)   # warm: full-prefix hit
    assert sim.tel.prefix_version > v
    # sketch writes must NOT look like telemetry heartbeats
    assert sim.tel.prefix_hit[inst.slot] > 0


def test_prefill_discount_shortens_admission():
    """`Instance._admit` discounts prefill by the matched fraction —
    the cache physics exists whether or not the router scored for it."""
    sim = _mini_sim()
    inst = sim.instances[0]
    rng = np.random.default_rng(6)
    p = _prompt(0, rng.integers(1, 4096, 128))
    cold = _req(0, p)
    inst.submit(cold, 0.0, 10.0, None)
    sim.run()
    assert cold.finish_time is not None and cold.prefix_hit == 0.0
    t1 = sim.now + 1.0
    warm = _req(1, p, arrival=t1)
    inst.submit(warm, t1, 10.0, None)
    sim.run()
    assert warm.prefix_hit == pytest.approx(1.0)
    # the warm admit skipped (1 - hit) of the prefill
    cold_prefill = cold.first_token_time - cold.dispatch_time
    warm_prefill = warm.first_token_time - warm.dispatch_time
    assert cold_prefill > 0.0
    assert warm_prefill < 0.5 * cold_prefill


def test_requeue_resets_prefix_hit():
    rng = np.random.default_rng(7)
    r = _req(0, _prompt(0, rng.integers(1, 4096, 64)))
    r.prefix_hit = 0.75
    r.requeue(2.0)
    assert r.prefix_hit == 0.0


def test_fail_clears_sketch_and_mirror_for_retries():
    """Dead-reckoned credit dies with the instance: a retry or hedge
    re-dispatch must never score affinity against a cache the victim
    lost. `recover()` re-enters cold."""
    sim = _mini_sim()
    inst = sim.instances[0]
    rng = np.random.default_rng(8)
    p = _prompt(0, rng.integers(1, 4096, 64))
    inst.submit(_req(0, p), 0.0, 10.0, None)
    assert len(inst.sketch) > 0
    inst.fail()
    assert len(inst.sketch) == 0
    assert (sim.tel.prefix_sig[inst.slot] == 0).all()
    inst.recover(1.0)
    assert len(inst.sketch) == 0             # cold re-entry
    assert (sim.tel.prefix_sig[inst.slot] == 0).all()
    assert inst.sketch.hit_tokens(prompt_signatures(p), 64) == 0


# -- decision steering: all three backends ------------------------------------

@pytest.fixture(scope="module")
def steer_run():
    sc = Scenario(name="steer", pool="synthetic", n_tiers=1,
                  n_instances=4, tenants=(TenantSpec("all", 8.0),),
                  seed=7)
    run = sc.build(dataset_n=220)
    run.bundle()
    return run


def test_affinity_steers_to_warm_instance_all_backends(steer_run):
    """Four identical idle replicas; one holds the request's full
    prefix. Affinity on must route the request to the warm cache —
    identically in every backend — while w=0 must ignore the sketch."""
    run = steer_run
    target = run.requests(4, seed=0)[0]
    target.arrival = 0.0
    sig = prompt_signatures(target.prompt)

    def pick(w, be, warm_slot=None):
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   affinity_weight=w),
                          run.bundle(), run.tiers)
        sim = ClusterSim(run.tiers, run.names, seed=0)
        if warm_slot is not None:
            warm = sim.instances[warm_slot]
            warm.sketch.insert(sig)
            sim.tel.write_prefix(warm.slot, warm.sketch)
        rb.sim = sim
        instances, choice, _ = rb._decide_core([target])
        return instances[int(choice[0])].iid

    base = {be: pick(0.0, be) for be in BACKENDS}
    assert len(set(base.values())) == 1, base
    iids = [i.iid for i in ClusterSim(run.tiers, run.names,
                                      seed=0).instances]
    # warm a replica the cold tie-break does NOT pick
    warm_slot = next(s for s in range(len(iids))
                     if iids[s] != base["numpy"])
    for be in BACKENDS:
        assert pick(0.6, be, warm_slot) == iids[warm_slot], be
        assert pick(0.0, be, warm_slot) == base[be], \
            (be, "sketch must be inert at w=0")


def test_weight_zero_is_bitwise_inert(steer_run):
    """affinity_weight=0 must leave decisions AND est latencies exactly
    the legacy values even with warm sketches everywhere (the discount
    multiplies by an exact 1.0)."""
    run = steer_run
    reqs = run.requests(12, seed=1)[:12]
    for r in reqs:
        r.arrival = 0.0
    cols = reqs[0].cols
    out = {}
    for arm in ("legacy", "zero_w"):
        rb = RouteBalance(RBConfig(decision_backend="fused",
                                   affinity_weight=0.0),
                          run.bundle(), run.tiers)
        sim = randomize_telemetry(
            ClusterSim(run.tiers, run.names, seed=0), 3)
        if arm == "zero_w":
            randomize_prefix_state(sim, cols, seed=3, frac=1.0)
        rb.sim = sim
        instances, choice, l_chosen = rb._decide_core(reqs)
        out[arm] = ([instances[int(i)].iid for i in choice],
                    np.asarray(l_chosen))
    assert out["legacy"][0] == out["zero_w"][0]
    np.testing.assert_array_equal(out["legacy"][1], out["zero_w"][1])


def test_zero_recompiles_through_session_churn(chat_run):
    """Session traffic (multi-turn prefix churn, sketch writes every
    dispatch) must ride the compiled programs: one XLA compile per pow2
    R bucket, exactly as without the affinity term."""
    from repro.core.decision_jax import bucket_pow2
    run = chat_run
    reqs = run.requests(120, seed=0)
    rb = RouteBalance(RBConfig(decision_backend="fused",
                               affinity_weight=0.35,
                               charge_compute=False),
                      run.bundle(), run.tiers)
    m = run.run_cell(rb, reqs, seed=0)
    assert m["cache_hit_rate"] > 0
    buckets = {bucket_pow2(s) for s, _ in rb.compute_log}
    assert rb._fused.compile_count() == len(buckets)
    # a second cell over fresh sessions adds zero compiles
    reqs2 = run.requests(120, seed=1)
    rb2 = RouteBalance(RBConfig(decision_backend="fused",
                                affinity_weight=0.35,
                                charge_compute=False),
                       run.bundle(), run.tiers)
    run.run_cell(rb2, reqs2, seed=0)
    buckets |= {bucket_pow2(s) for s, _ in rb2.compute_log}
    assert rb2._fused.compile_count() == len(buckets)


def test_session_chat_turns_share_prefixes(chat_run):
    reqs = chat_run.requests(80, seed=0)
    cols = reqs[0].cols
    chat = [r for r in reqs if r.tenant == "chat"]
    assert len(chat) > 20
    sig = cols.prefix_sig[cols.prompt_row[[r.row for r in chat]]]
    first = sig[:, 0]
    # conversations: many turns share their first block hash
    _, counts = np.unique(first[first != 0], return_counts=True)
    assert (counts > 1).any()
    # follow-up turns really extend (longer len_in than the base turn)
    lens = np.array([r.prompt.len_in for r in chat])
    assert lens.max() > lens.min()


# -- SoA ingest re-entrancy fixes (the retry-path correctness sweep) ----------

class _StubEncoder:
    dim = 8
    max_len = 128

    def __init__(self, fail_at_call=None):
        self.calls = 0
        self.fail_at = fail_at_call

    def encode(self, toks, lens):
        self.calls += 1
        if self.calls == self.fail_at:
            self.fail_at = None
            raise RuntimeError("encoder died mid-chunk")
        out = np.zeros((len(toks), self.dim), np.float32)
        out[:, 0] = toks[:, 0]
        out[:, 1] = np.asarray(lens, np.float32)
        return out


def _many_prompt_reqs(n=300, seed=9):
    rng = np.random.default_rng(seed)
    return [_req(i, _prompt(i, rng.integers(1, 4096, 12)))
            for i in range(n)]


def test_ensure_embeddings_all_or_nothing_and_resume():
    """A mid-chunk encoder raise must leave `emb` unset (no garbage
    rows can ever be served) and a retry must resume from the first
    unencoded row — not recompute, not concatenate a fresh pad block."""
    reqs = _many_prompt_reqs()
    cols = RequestColumns.from_requests(reqs)
    flaky = _StubEncoder(fail_at_call=2)     # 300 prompts = 2 chunks
    with pytest.raises(RuntimeError):
        cols.ensure_embeddings(flaky)
    assert cols.emb is None                  # all-or-nothing
    assert cols._emb_partial is not None
    assert cols._emb_partial[1] == 256       # chunk 1 retained
    pad_cache = cols._toks_padded
    retry = _StubEncoder()
    cols.ensure_embeddings(retry)
    assert retry.calls == 1                  # resumed, not recomputed
    assert cols._toks_padded is pad_cache    # pad matrix built once
    assert cols.emb is not None and cols._emb_partial is None
    ref = RequestColumns.from_requests(reqs, stamp=False)
    ref.ensure_embeddings(_StubEncoder())
    np.testing.assert_array_equal(cols.emb, ref.emb)
    # idempotent re-entry after success
    emb = cols.emb
    cols.ensure_embeddings(_StubEncoder(fail_at_call=1))
    assert cols.emb is emb


def test_batch_columns_rejects_foreign_and_stale_rows():
    """The satellite-1 pin: a retry that crossed streams (or carries a
    stale row stamp) must degrade the batch to the AoS path — never
    gather another request's tokens/embedding row."""
    a = _many_prompt_reqs(6, seed=10)
    b = _many_prompt_reqs(6, seed=11)
    cols_a = RequestColumns.from_requests(a)
    RequestColumns.from_requests(b)
    got_cols, got_rows = batch_columns(a[:4])
    assert got_cols is cols_a
    np.testing.assert_array_equal(got_rows, [r.row for r in a[:4]])
    # mixed streams: retry from stream B lands in a stream-A batch
    b[0].requeue(5.0)
    assert batch_columns([a[0], b[0]]) == (None, None)
    # stale stamp pointing out of bounds: refuse the columnar path
    rogue = a[1]
    rogue.row = cols_a.n + 7
    assert batch_columns([a[0], rogue]) == (None, None)


def test_retry_across_two_streams_decides_safely(steer_run):
    """End-to-end satellite-1 regression: a requeued request from one
    `RequestColumns` stream joins a batch of another stream's requests;
    the decision core must fall back to per-request staging and assign
    every request to an alive instance of its own roster."""
    run = steer_run
    stream_a = run.requests(8, seed=2)
    stream_b = run.requests(8, seed=3)
    retry = stream_b[0]
    retry.requeue(0.0)
    batch = stream_a[:4] + [retry]
    for r in batch:
        r.arrival = 0.0
    out = {}
    for be in BACKENDS:
        rb = RouteBalance(RBConfig(decision_backend=be,
                                   affinity_weight=0.35),
                          run.bundle(), run.tiers)
        rb.sim = randomize_telemetry(
            ClusterSim(run.tiers, run.names, seed=0), 5)
        instances, choice, _ = rb._decide_core(batch)
        assert len(choice) == len(batch)
        out[be] = [instances[int(i)].iid for i in choice]
        alive = {i.iid for i in rb.sim.instances if i.alive}
        assert set(out[be]) <= alive
    assert out["numpy"] == out["jax"] == out["fused"]
