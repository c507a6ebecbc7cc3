"""BENCHMARK.json and the files its names lead to."""
import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest  # noqa: E402

DATA = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = DATA["end_to_end"] + DATA["per_layer"]
CELLS = [w["name"] for w in DATA["workloads"]]


def test_top_level_keys_and_paths():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert DATA["command"][:2] == ["python3", "bench/run.py"]
    for p in DATA["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= DATA["run_seconds"] <= 51


def test_names_and_units():
    names = ([m["name"] for m in METRICS] + CELLS
             + [c["name"] for c in DATA["configs"]]
             + [w["traffic"] for w in DATA["workloads"]])
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_files(cell):
    w = next(w for w in DATA["workloads"] if w["name"] == cell)
    man = manifest.Manifest()
    cfg = man.config(w["config"])
    traffic = man.traffic(w["traffic"])
    assert (ROOT / "bench" / "configs" / f"{cfg['model']}.py").is_file()
    assert (ROOT / "bench" / "loops" / f"{traffic['loop']}.py").is_file()
    limits = man.limits(cell)
    assert {"rel_err", "bad_outputs"} <= set(limits)
    e2e = man.metrics_of(cell, per_layer=False)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    per_layer = man.metrics_of(cell, per_layer=True)
    assert per_layer
    for m in e2e + per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert callable(man.reader(m["name"]).read)
    w_chips = w["chips"]
    assert w_chips in (1, 4) and len(w["why"]) <= 200


def test_per_layer_metrics_name_what_they_move():
    e2e = {m["name"]: m for m in DATA["end_to_end"]}
    for m in DATA["per_layer"]:
        assert m["moves"] in e2e, m
        assert m["workloads"], m
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in CELLS
            # each listed cell reports the end-to-end metric it moves
            assert cell in moved.get("workloads", CELLS), (m, cell)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in DATA["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_configs_list_their_files_and_reductions():
    for c in DATA["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert c["reduced"] == []


def test_new_config_is_found_with_no_edit(tmp_path):
    """A configuration, traffic mix and metric added as files plus manifest
    entries are found by name: no harness file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/gcn2-e128.json").read_text())
    cfg.update(name="gcn3-e128", layers=3,
               params={f"l{i}.W": [128, 128] for i in range(3)})
    (root / "bench/configs/gcn3-e128.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (ROOT / "bench/traffic/rgg45k-full.json").read_text())
    traffic.update(name="rgg-half", vertices=22646, undirected_edges=54274)
    (root / "bench/traffic/rgg-half.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/forward_per_s.py").write_text(
        "def read(run):\n    return run.window['n'] / run.window['seconds']\n")
    (root / "bench/limits/gcn3-half.json").write_text(
        json.dumps({"rel_err": 1e-5, "bad_outputs": 0}))
    data["configs"].append({"name": "gcn3-e128", "source": "test",
                            "file": "bench/configs/gcn3-e128.json",
                            "reduced": [], "why": "test"})
    data["workloads"].append({"name": "gcn3-half", "config": "gcn3-e128",
                              "traffic": "rgg-half", "chips": 1,
                              "why": "test"})
    data["end_to_end"].append({"name": "forward_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["gcn3-half"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    man = manifest.Manifest(root)
    assert man.cell("gcn3-half")["config"] == "gcn3-e128"
    assert man.config("gcn3-e128")["layers"] == 3
    assert man.traffic("rgg-half")["vertices"] == 22646
    assert man.limits("gcn3-half")["rel_err"] == 1e-5
    names = [m["name"] for m in man.metrics_of("gcn3-half", False)]
    assert sorted(names) == ["forward_per_s", "setup_s"]
    run = type("R", (), {"window": {"n": 10, "seconds": 2.0}})()
    assert man.reader("forward_per_s").read(run) == 5.0
    model = man.model(man.config("gcn3-e128")["model"])
    assert model.model_flops(man.config("gcn3-e128"), 10, 20) > 0
    with pytest.raises(manifest.BenchError):
        man.cell("no-such-cell")
