"""The chip benchmark's harness rehearsed on the CPU at a tiny size: cells
found by name, per-call seeds, the window over whole calls, and a last
line that holds exactly the contract's keys.  The command itself must
refuse a CPU platform."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import compare  # noqa: E402  (perfbench is on the path via conftest)
import run  # noqa: E402

PERFBENCH = Path(__file__).resolve().parents[2] / "perfbench"

BENCH = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def rows_per_call(cell):
    t = cell.traffic
    return len(t["policies"]) * len(t["loads"]) * t["seeds_per_call"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = run.load_cell(name)
    w = {w["name"]: w for w in BENCH["workloads"]}[name]
    assert cell.chips == w["chips"]
    assert cell.config["name"] == w["config"]
    assert set(cell.traffic["check"]["limits"]) <= set(compare.NUMBERS)
    assert rows_per_call(cell) >= 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_matches_its_entry(metric):
    mod = run.load_reader(metric["name"])
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        metric["layer"], metric["unit"], metric["source"], metric["moves"])


def test_unknown_cell_is_refused(capsys):
    assert run.main(["--workload", "no.such", "--seed", "1", "--seconds",
                     "1"]) == 2
    assert capsys.readouterr().out == ""


def test_call_seeds_follow_the_run_seed():
    big = 2 ** 31 + 12345
    a = run.call_seeds(big, 1, 0, 4)
    assert a == run.call_seeds(big, 1, 0, 4)
    assert a != run.call_seeds(big, 1, 1, 4)
    assert a != run.call_seeds(big + 1, 1, 0, 4)
    assert all(0 <= s < 2 ** 31 for s in a)
    assert run.call_seeds(-7, 0, 0, 1)[0] >= 0


def test_command_refuses_a_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(PERFBENCH / "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=PERFBENCH.parent, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_prints_the_contract_line(tiny, capsys, trace):
    name = "testbed.switch5"
    rc = run.main(["--workload", name, "--seed", str(2 ** 31 + 9),
                   "--seconds", "0.5", "--trace", str(trace)],
                  require_tpu=False)
    assert rc == 0
    res = _last_line(capsys)
    keys = KEYS + (["breakdown"] if "breakdown" in res else []) + ["checks"]
    assert list(res) == keys
    rows = rows_per_call(tiny(name))
    assert res["attempted"] >= rows and res["attempted"] % rows == 0
    assert res["failed"] == 0
    assert res["correct"] is True
    cell = tiny(name)
    want = cell.per_layer if trace else cell.end_to_end
    # the CPU trace holds no chip plane: device-trace metrics stay out
    have = {m["name"] for m in want if not (trace and m["source"] ==
                                            "device_trace")}
    assert set(res["metrics"]) == have
    assert res["device"]["platform"] == "cpu"
    assert set(res["checks"]) == set(compare.NUMBERS) | {"failed_rows"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_window_counts_whole_calls(tiny):
    cell = tiny("testbed.switch5")
    spec, overrides = run.build_sweep(cell)
    calls, window_s, reduced = run.measure(cell, spec, overrides, seed=3,
                                           seconds=0.0, trace=False)
    assert len(calls) == 1 and reduced is None
    assert window_s >= calls[0].wall_s
    assert calls[0].seeds == run.call_seeds(3, 1, 0, 1)
    assert len(calls[0].sweep.results) == rows_per_call(cell)


def test_reference_pool_spawns_workers():
    from reference import pool

    cell = run.load_cell("testbed.switch5")
    tasks = [pool.task(cell.config, dict(cell.traffic, n_ticks=300),
                       policy="netclone", load=0.3, seed=s,
                       rate_per_us=1.0, hist=(1.0, 1.06, 256))
             for s in (1, 2)]
    spawned = pool.run(tasks, workers=2)
    inline = pool.run(tasks, workers=0)
    for a, b in zip(spawned, inline):
        assert vars(a).keys() == vars(b).keys()
        for k, v in vars(a).items():
            assert str(v) == str(vars(b)[k])
