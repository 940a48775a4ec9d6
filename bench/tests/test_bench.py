"""Tests of the benchmark's own code, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The cells run cut down (`tiny.py`) with the harness's look for a chip
skipped; everything else about a run is as on the chip.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

import devtrace  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402

ROOT = os.path.dirname(tiny.BENCH)
RECORDED = os.path.join(tiny.BENCH, "testdata", "trace_small.json")


# --------------------------------------------------------------- the trace

def test_reducer_busy_union_idle_and_gaps():
    trace = {"devices": {"0": [["fusion", 0, 100], ["dot", 50, 100],
                               ["copy", 400, 100], ["late", 950, 100]]},
             "host": [["bench/trace_window", 0, 1000],
                      ["bench/learner_batch", 150, 250],
                      ["bench/train_step", 500, 300]]}
    r = devtrace.reduce(trace, [0])
    # busy: [0,150) + [400,500) + [950,1000) = 300 ns of a 1000 ns window
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_pct"] == pytest.approx(70.0)
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench/train_step", "bench/learner_batch"]
    assert r["idle_gaps"][0][1] == pytest.approx(450e-9)
    assert dict(r["device_ops"])["late"] == pytest.approx(50e-9)


def test_reducer_on_recorded_chip_trace():
    with open(RECORDED) as f:
        trace = json.load(f)
    r = devtrace.reduce(trace, [0])
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_pct"] < 100
    assert r["device_ops"] and len(r["device_ops"]) <= devtrace.TOP
    assert {g[0] for g in r["idle_gaps"]} <= {
        "bench/train_step", "bench/learner_batch", "host:other"}


def test_reducer_reads_nothing_without_device_events():
    assert devtrace.reduce({"devices": {}, "host": []}, [0]) is None


# ------------------------------------------------------ FLOPs and the peaks

def test_r2d2_forward_macs_match_the_hand_count():
    cell = harness.load_cell("r2d2_atari.inproc")
    macs = flops.r2d2_layer_macs(cell["config"]["model"])
    assert macs == {"conv0": 3276800, "conv1": 2654208, "conv2": 1806336,
                    "torso": 1605632, "lstm": 2097152, "heads": 9728}
    assert sum(macs.values()) == 11449856
    assert flops.r2d2_forward_flops(cell["config"]["model"]) == 22899712


def test_r2d2_step_flops():
    model = harness.load_cell("r2d2_atari.inproc")["config"]["model"]
    fwd = 11449856
    want = 2 * 64 * 120 * (2 * fwd + 2 * fwd - 3276800)
    assert flops.r2d2_step_flops(model, 64) == want
    assert 0.65e12 < want < 0.66e12


def test_peak_table_refuses_an_unknown_chip():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak("TPU v9 imaginary")


# ------------------------------------------------- the command's refusals

def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vtrace_mlp.device",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------------------- finding by name

def test_a_dropped_in_workload_is_found_by_name(tmp_path):
    """A later cell is data only: a traffic file and an entry in
    BENCHMARK.json; no existing file of the harness changes."""
    shutil.copytree(os.path.join(tiny.BENCH, "workloads"),
                    tmp_path / "bench" / "workloads")
    shutil.copytree(os.path.join(tiny.BENCH, "configs"),
                    tmp_path / "bench" / "configs")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["workloads"].append({"name": "r2d2_atari.many_small",
                              "config": "r2d2_atari",
                              "traffic": "many_small", "chips": 1,
                              "why": "16 actors x 4 lanes"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    traffic = json.load(open(os.path.join(
        tiny.BENCH, "workloads", "r2d2_atari.inproc.json")))
    traffic.update(num_actors=16, envs_per_actor=4)
    (tmp_path / "bench" / "workloads" / "r2d2_atari.many_small.json"
     ).write_text(json.dumps(traffic))
    cell = harness.load_cell("r2d2_atari.many_small", root=tmp_path)
    assert cell["traffic"]["num_actors"] == 16
    assert cell["config"]["learner_batch"] == 64
    names = {m["name"] for m in cell["end_to_end"]}
    assert names == {"train_frames_per_s", "setup_s"}


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(
            tiny.BENCH, "metrics", m["name"] + ".py")), m["name"]
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(
            tiny.BENCH, "workloads", w["name"] + ".json")), w["name"]


# ------------------------------------------------------------ the cells

@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_cell_reaches_its_window_and_is_correct(name):
    r = tiny.run(name)
    assert r["correct"], r["checks"]
    assert not r["_errors"]
    assert r["_compiles_in_window"] == 0
    assert r["attempted"] > 0
    assert "train_frames_per_s" in r["metrics"]
    assert r["metrics"]["setup_s"]["value"] > 0
    if "ledger_gap" in r["checks"]:
        assert r["checks"]["ledger_gap"]["value"] == 0.0
    if name.startswith("r2d2"):
        assert r["_actor_samples"] >= 20
        assert r["metrics"]["actor_step_p95_ms"]["value"] > 0


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_fault_in_the_timed_path_is_not_correct(name, fault):
    r = tiny.run(name, fault=fault)
    assert not r["correct"], r["checks"]


def test_an_altered_action_is_not_correct():
    """R2D2's policy calls on the initial weights are replayed through the
    reference: an action altered where it is served fails the check."""
    r = tiny.run("r2d2_atari.inproc", fault="token_altered")
    assert not r["correct"], r["checks"]
    assert r["checks"]["explore_draw_gap"]["value"] > 0


def test_policy_numbers_replay_the_exploration_draws():
    from compare import exploration, lane_histories, policy_numbers
    obs = np.arange(6, dtype=np.uint8).reshape(6, 1)
    calls = [(np.array([0, 1]), obs[:2], np.array([0, 0])),
             (np.array([1]), obs[2:3], np.array([1])),
             (np.array([0, 1]), obs[3:5], np.array([2, 2]))]
    served = lane_histories(calls, lane_block=4, step_pad=4)
    assert served["obs"].shape == (4, 4, 1)
    assert list(served["lane"]) == [0, 1, 1, 0, 1]
    assert list(served["step"]) == [0, 0, 1, 1, 2]
    assert served["obs"][1, 2, 0] == 4 and served["obs"][0, 1, 0] == 3
    explore, drawn = exploration(7, served["sizes"], 0.5, 3)
    rng = np.random.default_rng(7)
    want = np.concatenate([rng.random(n) < 0.5 for n in (2,)])
    assert list(explore[:2]) == list(want)
    q = np.tile(np.array([[0.0, 1.0, 2.0]]), (5, 1))
    best = np.where(explore, drawn, 2)
    numbers = policy_numbers(best, q, explore, drawn)
    assert numbers["policy_gap"][0] == 0.0
    assert numbers["explore_draw_gap"][0] == 0.0
    worse = np.where(explore, (drawn + 1) % 3, 1)
    numbers = policy_numbers(worse, q, explore, drawn)
    assert numbers["policy_gap"][0] == (0.5 if (~explore).any() else 0.0)
    assert numbers["explore_draw_gap"][0] == explore.sum()


@pytest.mark.parametrize("name", sorted(tiny.CELLS))
def test_the_bfloat16_control_is_not_correct(name):
    """The reference computed in bfloat16, put in the program's place,
    fails one of the cell's limits."""
    r = tiny.run(name, controls=True)
    limits = tiny.CELLS[name]()["traffic"]["limits"]
    control = r["_readings"]["control"]
    assert any(v > limits[k] for k, (v, _) in control.items()
               if k in limits), control


def test_actor_window_counts_gaps_inside_the_window():
    lane = np.array([[0.0, 0.1], [1.0, 1.1], [2.0, 2.2], [3.0, 3.1]])
    w = harness.actor_window([lane, lane], 0.5, 2.5, lanes_per_actor=2)
    assert w["steps"] == 4
    assert w["iterations"] == 2
    assert list(w["gaps_s"]) == [1.0, 1.0]
    assert w["env_s"] == pytest.approx(0.6)
