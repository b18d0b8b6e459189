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
    with pytest.raises(SystemExit, match="affinity_weight"):
        cellmod.decision_config(dict(
            c.config, decision=dict(block, affinity_weight=0.5)))


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
