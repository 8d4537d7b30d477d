"""``BENCHMARK.json`` resolves by name, keeps to the benchmark's contract,
and ``bench/run.py`` refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
# what a traffic file of each kind of cell must give
TRAFFIC = {
    "campaign": lambda t: t["families"] and t["pool_campaigns"] > 0,
}


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves_to_its_files(name):
    cell = harness.Cell(name, SPEC)
    assert cell.config["name"] == cell.workload["config"]
    assert (ROOT / "bench" / "reference" / f"{cell.config['kind']}.py"
            ).is_file()
    assert TRAFFIC[cell.config["kind"]](cell.traffic)
    e2e = cell.readers(trace=False)
    layers = cell.readers(trace=True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layers
    for _, mod in list(e2e.values()) + list(layers.values()):
        assert callable(mod.read)
    assert hasattr(cell.kind(), "check")


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(WORKLOADS)
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", WORKLOADS)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)


def _assert_cuts_stated(spec):
    """Each configuration file names itself, states the cut that
    ``BENCHMARK.json`` lists, its source and what it assumed, and the chips
    of every cell that runs it."""
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] and cfg["assumed"]
        assert {w["chips"] for w in spec["workloads"]
                if w["config"] == c["name"]} == {cfg["chips"]}


def test_configuration_files_state_their_cut():
    _assert_cuts_stated(SPEC)


def test_a_four_chip_configuration_resolves(tmp_path):
    base = json.loads(
        (ROOT / "bench" / "configs" / "paper_e1e4_n40_p100.json").read_text())
    cfg = dict(base, name="four_chip_probe", chips=4, n=160, p=1000,
               n_bounds=8, reduced=["n_bounds"])
    (tmp_path / "four_chip_probe.json").write_text(json.dumps(cfg))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "four_chip_probe",
                            "source": "https://arxiv.org/abs/0706.4009",
                            "file": str(tmp_path / "four_chip_probe.json"),
                            "reduced": ["n_bounds"], "why": "four chips"})
    spec["workloads"].append({"name": "campaign_probe4",
                              "config": "four_chip_probe",
                              "traffic": "paper_mixed", "chips": 4,
                              "why": "a configuration cut for four chips"})
    _assert_cuts_stated(spec)
    cell = harness.Cell("campaign_probe4", spec)
    assert cell.chips == cell.config["chips"] == 4
    assert TRAFFIC[cell.config["kind"]](cell.traffic)
    assert "setup_s" in cell.readers(trace=False)
    assert hasattr(cell.kind(), "check")
    spec["workloads"][-1]["chips"] = 1
    with pytest.raises(AssertionError):
        _assert_cuts_stated(spec)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run(["--workload", WORKLOADS[0], "--seed", str(2 ** 31 + 7),
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "no CPU path" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = _run(["--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no_such_cell" in p.stderr


def test_benchmark_files_alone_do_not_make_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", WORKLOADS[0], "--seed", "3", "--seconds", "1",
              "--trace", "0"], tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
