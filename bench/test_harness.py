"""The harness at a tiny size on the CPU: every cell loads by name, a
whole run prints the contract's keys, and off the chip it refuses."""
import json
import os
import subprocess
import sys

import pytest

from bench import arrivals, cell as cellmod
from bench.conftest import FAKE_DEVICE, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    c = cellmod.load_cell(name)
    assert c.chips == 1
    assert c.config["name"] == c.config_name
    tiers = cellmod.fleet_tiers(c.config)
    assert sum(t.n_instances for t in tiers) == c.config["instances"]
    t = arrivals.arrivals(c.mix, __import__("numpy").random.default_rng(0))
    assert len(t) and t[-1] <= c.mix.horizon_s
    for m in c.end_to_end + c.per_layer:
        assert callable(cellmod.load_reader(m["name"]))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_seeds_take_any_integer():
    a = cellmod.traffic_seed(2 ** 31 + 7)
    assert a == cellmod.traffic_seed(2 ** 31 + 7)
    assert a != cellmod.traffic_seed(7)
    assert cellmod.traffic_seed(-3) != cellmod.traffic_seed(3)


def test_same_seed_same_traffic():
    c = cellmod.load_cell(CELLS[0])
    seed = cellmod.traffic_seed(99)
    import numpy as np
    t1 = arrivals.arrivals(c.mix, np.random.default_rng(seed))
    t2 = arrivals.arrivals(c.mix, np.random.default_rng(seed))
    assert np.array_equal(t1, t2)


def test_peak_window_count():
    import numpy as np
    t = np.array([0.0, 0.1, 0.2, 0.25, 1.0, 1.05])
    assert arrivals.peak_window_count(t, 0.3) == 4
    assert arrivals.peak_window_count(t, 0.01) == 1


def test_last_line_has_the_contract_keys(monkeypatch, capsys, small):
    from bench import run as runmod
    cell, setup, mix = small
    monkeypatch.setattr(runmod, "device_or_exit",
                        lambda chips: dict(FAKE_DEVICE))
    monkeypatch.setattr(cellmod, "load_cell", lambda name: cell)
    monkeypatch.setattr(runmod, "place_cache", lambda: None)
    monkeypatch.setattr(cellmod, "build", lambda c: setup)
    assert runmod.main(["--workload", "paper_steady", "--seed",
                        str(2 ** 31 + 5), "--seconds", "0.5",
                        "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert list(res) == CONTRACT_KEYS
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for v in res["metrics"].values():
        assert set(v) == {"value", "unit"}
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["attempted"] > 0
    # the numbers compared close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [ln.split()[1] for ln in tail] == list(res["checks"])
    assert all(" limit " in ln for ln in tail)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_decision_block_sets_the_backend():
    c = cellmod.load_cell(CELLS[0])
    block = dict(c.config["decision"], decision_backend="megakernel")
    cfg = cellmod.decision_config(dict(c.config, decision=block))
    assert cfg.decision_backend == "megakernel"
    assert cfg.weights == tuple(block["weights"])
    assert cellmod.decision_config(c.config).decision_backend == "fused"
    with pytest.raises(SystemExit, match="lpt"):
        cellmod.decision_config(dict(
            c.config, decision=dict(block, lpt=False)))


def test_decision_block_takes_an_affinity_weight_in_range():
    c = cellmod.load_cell(CELLS[0])
    block = dict(c.config["decision"], affinity_weight=0.35)
    cfg = cellmod.decision_config(dict(c.config, decision=block))
    assert cfg.affinity_weight == 0.35
    with pytest.raises(SystemExit, match=r"affinity_weight = 1\.5"):
        cellmod.decision_config(dict(
            c.config, decision=dict(block, affinity_weight=1.5)))


def _session_mix(tmp_path, **over):
    from bench.conftest import SESSION_MIX
    raw = json.loads(json.dumps(SESSION_MIX))
    raw["sessions"].update(over)
    (tmp_path / "chat.json").write_text(json.dumps(raw))
    return arrivals.Mix.load(tmp_path / "chat.json")


def test_session_turns_grow_from_their_predecessor(tmp_path):
    """A follow-up's tokens begin with the turn before's, it arrives
    later, its tokens stop at 128, and the seed fixes the traffic."""
    import numpy as np
    from types import SimpleNamespace
    from bench import sessions
    mix = _session_mix(tmp_path, base_len=100, extend=[20, 28])
    rng0 = np.random.default_rng(5)
    prompts = [SimpleNamespace(tokens=rng0.integers(1, 4096, n))
               for n in (8, 60, 128, 128)]
    at, conv, base, toks = sessions.chat_turns(
        mix, prompts, np.random.default_rng(11))
    assert len(at) > 20 and len(set(conv)) > 5
    assert all(len(t) <= sessions.MAX_TOKENS for t in toks)
    assert any(len(t) == sessions.MAX_TOKENS for t in toks)
    follow = 0
    for k in range(len(at)):
        if k and conv[k] == conv[k - 1]:
            follow += 1
            prev = toks[k - 1]
            assert at[k] > at[k - 1]
            assert np.array_equal(toks[k][:len(prev)], prev)
            grew = len(toks[k]) - len(prev)
            assert 20 <= grew <= 28 or len(toks[k]) == 128
            assert base[k] == base[k - 1]
        else:
            first = prompts[base[k]].tokens[:100]
            assert np.array_equal(toks[k], first)
    assert follow > 10
    again = sessions.chat_turns(mix, prompts, np.random.default_rng(11))
    assert np.array_equal(again[0], at) and np.array_equal(again[2], base)
    assert all(np.array_equal(a, b) for a, b in zip(again[3], toks))


@pytest.mark.parametrize("mix_name", ["poisson_53rps", "gamma_cv3_40rps"])
def test_a_mix_without_sessions_gives_the_same_requests(mix_name, small):
    """The requests of a mix without sessions are those the harness
    built before sessions existed: arrivals, dealt prompts, budgets."""
    import dataclasses
    import numpy as np
    from repro.core import make_requests
    ds = small[1].dataset
    mix = arrivals.Mix.load(ROOT / "bench" / "traffic" / f"{mix_name}.json")
    seed = cellmod.traffic_seed(2 ** 33 + 1)
    drawn = np.random.default_rng(seed)
    t, reqs = cellmod.requests_for(mix, ds, None, drawn)
    rng = np.random.default_rng(seed)
    t0 = arrivals.arrivals(mix, rng)
    order = rng.permutation(len(ds.test_idx))
    dealt = dataclasses.replace(ds, test_idx=ds.test_idx[order])
    want = make_requests(dealt, "test", t0,
                         budgets=arrivals.budgets(mix, len(t0), rng))
    assert np.array_equal(t, t0) and len(reqs) == len(want)
    assert all(a.prompt is b.prompt and a.arrival == b.arrival
               and a.rid == b.rid and a.budget == b.budget
               for a, b in zip(reqs, want))
    assert np.array_equal(reqs[0].cols.prompt_row, want[0].cols.prompt_row)
    assert np.array_equal(reqs[0].cols.budget, want[0].cols.budget,
                          equal_nan=True)
    # the same draws, no more
    assert drawn.bit_generator.state == rng.bit_generator.state


def test_a_session_mix_merges_both_streams(tmp_path, small):
    """Both streams, in arrival order; each turn a prompt of its own
    whose length is its token count; turns share their base row's
    labels."""
    import numpy as np
    ds = small[1].dataset
    mix = _session_mix(tmp_path)
    t, reqs = cellmod.requests_for(mix, ds, None, np.random.default_rng(4))
    assert np.all(np.diff(t) >= 0)
    assert [r.arrival for r in reqs] == list(t)
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    test_prompts = {id(ds.prompts[i]) for i in ds.test_idx}
    turns = [r for r in reqs if id(r.prompt) not in test_prompts]
    assert 0.5 < len(turns) / len(reqs) < 0.9      # 10 of 14 req/s
    assert all(r.prompt.len_in == len(r.prompt.tokens) <= 128
               for r in turns)
    cols = reqs[0].cols
    assert all(r.cols is cols for r in reqs)


def test_unknown_process_is_refused(tmp_path):
    (tmp_path / "m.json").write_text(json.dumps({
        "process": "no_such_law", "rate_rps": 1.0, "budget_share": 0.0,
        "budget_usd": [1e-5, 1e-4], "fill_s": 1.0, "horizon_s": 2.0}))
    with pytest.raises(ValueError, match="no_such_law"):
        arrivals.Mix.load(tmp_path / "m.json")


def test_a_new_process_is_a_new_file(tmp_path, monkeypatch, drive, small):
    """A process module found by name drives the run, and its
    `schedule` is handed the simulator before the fill."""
    import dataclasses
    (tmp_path / "every_tenth.py").write_text(
        "import numpy as np\n"
        "calls = []\n"
        "def arrivals(mix, rng):\n"
        "    return np.arange(0.1, mix.horizon_s, 0.1)\n"
        "def schedule(sim, mix, rng):\n"
        "    calls.append(len(sim.instances))\n")
    monkeypatch.setattr(arrivals, "PROCESS_DIR", tmp_path)
    mod = arrivals.process("every_tenth")
    mix = dataclasses.replace(small[2], process="every_tenth")
    assert len(arrivals.arrivals(mix, None)) == 199
    monkeypatch.setattr(arrivals, "process", lambda name: mod)
    res, rec = drive(mix=mix, seconds=0.5)
    assert mod.calls == [13] and res["attempted"] > 0 and res["correct"]


def test_window_compiles_nothing_and_compiles_are_counted(drive):
    import jax
    import numpy as np
    _, rec = drive(seconds=1.0)
    assert rec.compiles_in_window == 0
    with cellmod.counting_compiles() as seen:
        jax.jit(lambda x: x * 3 + 1)(np.ones(5))
    assert len(seen) >= 1
