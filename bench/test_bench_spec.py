"""``BENCHMARK.json`` resolves by name, keeps to the benchmark's contract,
a new kind of cell resolves from new files alone, and ``bench/run.py``
refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

import bench.kinds
import bench.reference
from bench import harness

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def _assert_kind(cell):
    """The cell's kind module states what its traffic files must give,
    accepts the cell's traffic and has the steps of a run; the kind's plain
    reference is there beside it."""
    kind_name = cell.config["kind"]
    kind = cell.kind()
    assert callable(getattr(kind, "traffic_ok", None)), (
        f"bench/kinds/{kind_name}.py has no traffic_ok(traffic): each kind "
        "states what its traffic files must give")
    assert kind.traffic_ok(cell.traffic), (
        f"traffic {cell.workload['traffic']!r} is not what kind "
        f"{kind_name!r} takes")
    for step in ("setup", "window", "check"):
        assert callable(getattr(kind, step, None)), (
            f"bench/kinds/{kind_name}.py has no {step}")
    assert (harness.BENCH / "reference" / f"{kind_name}.py").is_file()


def _assert_resolves(cell):
    assert cell.config["name"] == cell.workload["config"]
    _assert_kind(cell)
    e2e = cell.readers(trace=False)
    layers = cell.readers(trace=True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layers
    for _, mod in list(e2e.values()) + list(layers.values()):
        assert callable(mod.read)


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves_to_its_files(name):
    _assert_resolves(harness.Cell(name, SPEC))


@pytest.mark.parametrize("traffic,ok", [
    ({"families": ["E1"], "pool_campaigns": 4}, True),
    ({"families": [], "pool_campaigns": 4}, False),
    ({"families": ["E1"], "pool_campaigns": 0}, False),
    ({"pool_campaigns": 4}, False),
])
def test_campaign_traffic_check(traffic, ok):
    from bench.kinds import campaign

    assert campaign.traffic_ok(traffic) is ok


def _assert_names_units_and_bounds(spec):
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    workloads = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(workloads)
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", workloads)
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)


def test_names_units_and_bounds():
    _assert_names_units_and_bounds(SPEC)


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
    _assert_kind(cell)
    assert "setup_s" in cell.readers(trace=False)
    spec["workloads"][-1]["chips"] = 1
    with pytest.raises(AssertionError):
        _assert_cuts_stated(spec)


# A kind that no file of the repository knows, written whole by the test:
# its module, its plain reference, a configuration, a traffic file and the
# readers of its own metrics.
PROBE_KIND = '''
import time

from bench.reference import probe_kind as reference


def traffic_ok(traffic):
    return traffic.get("items", 0) > 0


def setup(cell, seed):
    return {"items": int(cell.traffic["items"]), "seed": seed}


def window(st, seconds, tracer):
    t0 = time.perf_counter()
    st["answers"] = [k * k for k in range(st["items"])]
    return {"window_s": time.perf_counter() - t0 + 1e-9,
            "attempted": st["items"], "failed": 0,
            "answered": len(st["answers"])}


def check(st, record):
    want = reference.squares(st["items"])
    return [("answers_differing",
             sum(a != b for a, b in zip(st["answers"], want)), 0)]
'''
PROBE_FILES = {
    "reference/probe_kind.py": """
        def squares(n):
            return [k * k for k in range(n)]
        """,
    "metrics/probe_answers_per_s.py": """
        def read(record):
            return record["answered"] / record["window_s"]
        """,
    "layers/probe_answered.py": """
        def read(record):
            return float(record["answered"])
        """,
    "traffic/probe_mix.json": '{"items": 50}',
    "configs/probe_config.json": json.dumps({
        "name": "probe_config", "kind": "probe_kind", "chips": 1,
        "source": "a test", "reduced": [], "assumed": {"items": "50"}}),
}


@pytest.mark.parametrize("with_traffic_ok", [True, False])
def test_a_new_kind_resolves_from_new_files_alone(tmp_path, monkeypatch,
                                                  request, with_traffic_ok):
    from repro.core import fused

    tree = tmp_path / "bench"
    for rel, text in PROBE_FILES.items():
        (tree / rel).parent.mkdir(parents=True, exist_ok=True)
        (tree / rel).write_text(textwrap.dedent(text))
    shutil.copy(ROOT / "bench" / "metrics" / "setup_s.py",
                tree / "metrics" / "setup_s.py")
    kind = PROBE_KIND if with_traffic_ok else PROBE_KIND.replace(
        "def traffic_ok(", "def _unused(")
    (tree / "kinds").mkdir()
    (tree / "kinds" / "probe_kind.py").write_text(kind)
    monkeypatch.setattr(harness, "BENCH", tree)
    for pkg, folder in ((bench.kinds, "kinds"),
                        (bench.reference, "reference")):
        monkeypatch.setattr(pkg, "__path__",
                            [*pkg.__path__, str(tree / folder)])

    def forget_probe():
        for pkg in (bench.kinds, bench.reference):
            sys.modules.pop(f"{pkg.__name__}.probe_kind", None)
            vars(pkg).pop("probe_kind", None)

    request.addfinalizer(forget_probe)
    monkeypatch.setattr(fused, "enable_persistent_cache", lambda: "off")

    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "probe_config", "source": "a test",
                            "file": str(tree / "configs" /
                                        "probe_config.json"),
                            "reduced": [], "why": "a new kind"})
    spec["workloads"].append({"name": "probe_cell", "config": "probe_config",
                              "traffic": "probe_mix", "chips": 1,
                              "why": "a new kind from new files alone"})
    spec["end_to_end"].append({"name": "probe_answers_per_s",
                               "unit": "answers/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["probe_cell"]})
    spec["per_layer"].append({"name": "probe_answered", "unit": "answers",
                              "better": "higher", "source": "program_counter",
                              "layer": "probe", "moves": "probe_answers_per_s",
                              "workloads": ["probe_cell"]})
    _assert_names_units_and_bounds(spec)
    _assert_cuts_stated(spec)
    cell = harness.Cell("probe_cell", spec)
    if not with_traffic_ok:
        with pytest.raises(AssertionError, match="has no traffic_ok"):
            _assert_resolves(cell)
        return
    _assert_resolves(cell)
    for trace, metric in ((False, "probe_answers_per_s"),
                          (True, "probe_answered")):
        out = harness.run(cell, 2 ** 31 + 5, 0.01, trace, time.perf_counter())
        assert out["correct"] and out["checked"] == {
            "answers_differing": {"value": 0, "limit": 0}}
        assert metric in out["metrics"]


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
