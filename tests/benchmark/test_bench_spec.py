"""BENCHMARK.json against the contract's limits, and the harness's promise
that a cell, a configuration and a metric are added as FILES."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny import REPO, tiny_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    names = []
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in {e["name"] for e in doc["end_to_end"]}
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in doc["workloads"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 4)
    assert len(json.dumps(doc)) < 64 * 1024


def test_every_cell_finds_its_files(doc):
    from benchmark.harness.spec import Spec

    spec = Spec(REPO)
    e2e = {m["name"] for m in doc["end_to_end"]}
    for w in doc["workloads"]:
        cell = spec.cell(w["name"])
        cell.check_files()
        assert os.path.isfile(os.path.join(cell.config_dir, "reference.py"))
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:  # each moves a metric the cell reports
            assert m["moves"] in reported and m["moves"] in e2e


def test_reference_imports_nothing_of_the_program(doc):
    for c in doc["configs"]:
        path = os.path.join(REPO, os.path.dirname(c["file"]), "reference.py")
        with open(path) as f:
            src = f.read()
        assert "genrec_tpu" not in src.replace("``genrec_tpu``", "")


def test_missing_metric_file_is_an_error(tmp_path):
    from benchmark.harness.spec import Spec, SpecError

    root = tiny_root(tmp_path)
    os.remove(os.path.join(root, "benchmark", "metrics", "pack_occupancy.train.py"))
    with pytest.raises(SpecError):
        Spec(root).cell("tiger_train_packed").check_files()
    with pytest.raises(SpecError):
        Spec(root).cell("no_such_cell")


def test_new_config_cell_and_metric_are_files_only(tmp_path):
    """A dummy configuration, traffic mix, kind and metric added as new files
    and entries run through run.py untouched."""
    from benchmark import run as brun
    from benchmark.harness.spec import Spec

    root = tiny_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    cdir = os.path.join(bench, "configs", "dummy_cfg")
    os.makedirs(cdir)
    with open(os.path.join(cdir, "config.json"), "w") as f:
        json.dump({"name": "dummy_cfg", "width": 4}, f)
    for stem in ("adapter", "reference", "flops"):
        with open(os.path.join(cdir, stem + ".py"), "w") as f:
            f.write("WIDTH = 4\n")
    with open(os.path.join(bench, "traffic", "dummy_mix.json"), "w") as f:
        json.dump({"kind": "dummy_kind", "work": 7}, f)
    with open(os.path.join(bench, "kinds", "dummy_kind.py"), "w") as f:
        f.write(
            "def run(cell, seed, seconds, trace, t_begin, control=False):\n"
            "    class T:\n"
            "        def reduce(self):\n"
            "            return None\n"
            "    ctx = {'cell': cell, 'kind': 'dummy', 'memory_peak_bytes': 1,\n"
            "           'work': cell.traffic['work'], 'trace': T(), 'spans': []}\n"
            "    return {'attempted': 1, 'failed': 0, 'ctx': ctx,\n"
            "            'checks': {'exact': {'value': 0.0, 'limit': 0.0}},\n"
            "            'e2e': {'dummy_rate': 3.5, 'setup_s': 0.25}}\n")
    with open(os.path.join(bench, "metrics", "dummy_work.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['work'] * 2\n")
    with open(os.path.join(bench, "metrics", "dummy_silent.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "dummy_cfg", "source": "test", "reduced": [],
                           "file": "benchmark/configs/dummy_cfg/config.json",
                           "why": "test"})
    doc["workloads"].append({"name": "dummy_cell", "config": "dummy_cfg",
                             "traffic": "dummy_mix", "chips": 1, "why": "test"})
    doc["end_to_end"].append({"name": "dummy_rate", "unit": "x/s", "better": "higher",
                              "bound": 0.01, "source": "host_clock",
                              "workloads": ["dummy_cell"]})
    for name in ("dummy_work", "dummy_silent"):
        doc["per_layer"].append({"name": name, "unit": "x", "better": "higher",
                                 "source": "program_counter", "layer": "dummy",
                                 "moves": "dummy_rate", "workloads": ["dummy_cell"]})
    with open(path, "w") as f:
        json.dump(doc, f)
    cell = Spec(root).cell("dummy_cell")
    cell.check_files()
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = brun.run_cell(cell, 1, 1.0, False, dev, 0.0)
    assert line["correct"] is True
    assert line["metrics"] == {"dummy_rate": {"value": 3.5, "unit": "x/s"},
                               "setup_s": {"value": 0.25, "unit": "s"}}
    traced = brun.run_cell(cell, 1, 1.0, True, dev, 0.0)
    assert traced["metrics"] == {"dummy_work": {"value": 14.0, "unit": "x"}}
    assert list(traced)[-1] == "checks"


def test_result_line_keys_and_types(tmp_path):
    from benchmark import run as brun
    from benchmark.harness.spec import Spec

    cell = Spec(tiny_root(tmp_path)).cell("tiger_train_packed")

    class NoTrace:
        def reduce(self):
            return None

    result = {
        "attempted": 12, "failed": 0,
        "checks": {"loss_gap_step1": {"value": 0.5, "limit": 0.1}},
        "e2e": {"train_tokens_per_s_per_chip": 123.456, "setup_s": 9.75},
        "ctx": {"memory_peak_bytes": 77, "trace": NoTrace(), "spans": [],
                "kind": "train"},
    }
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = json.loads(json.dumps(brun.result_line(cell, result, dev, False)))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is False  # 0.5 is over its limit of 0.1
    assert line["metrics"]["setup_s"] == {"value": 9.75, "unit": "s"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 77}
    result["checks"]["loss_gap_step1"]["value"] = 0.05
    assert brun.result_line(cell, result, dev, False)["correct"] is True
    del result["e2e"]["train_tokens_per_s_per_chip"]  # a metric not reported
    assert brun.result_line(cell, result, dev, False)["correct"] is False


def test_exits_nonzero_and_prints_nothing_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "tiger_train_packed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
