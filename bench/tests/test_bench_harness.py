"""The harness finds cells by name from files alone, runs them end to
end, and refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_a_cell_added_as_files_and_entries_is_found(root):
    c = harness.load_cell(root, "train.tiny.realign")
    assert c.config["ivector_dim"] == 8 and c.traffic["realign"] is True
    assert [m["name"] for m in c.end_to_end] == ["em_utts_per_s", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["mfu.train"]
    assert harness.driver(c).__name__ == "bench.drivers.train"
    s = harness.load_cell(root, "serve.tiny.open")
    assert [m["name"] for m in s.per_layer] == ["padded_share.serve"]
    with pytest.raises(KeyError):
        harness.load_cell(root, "no.such.cell")


def test_the_real_cells_and_their_readers_are_found():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = harness.load_cell(REPO, w["name"])
        assert c.limits_file.is_file()
        assert harness.driver(c).__name__.startswith("bench.drivers.")
        for m in c.per_layer:
            assert callable(harness.metric_reader(m["name"]).read)
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert len(c.end_to_end) >= 2 and c.per_layer


def test_peaks_of_an_unknown_device_are_an_error():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("no such chip")


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_a_tiny_cell_runs_correct_end_to_end(root, cell):
    res = tiny.run(root, cell, seconds=0.3)
    assert res.correct and res.failed == 0 and res.attempted > 0
    line = json.loads(res.line())
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    want = {m["name"] for m in harness.load_cell(root, cell).end_to_end}
    assert set(line["metrics"]) == want and "setup_s" in want
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]


def test_a_traced_tiny_run_reports_per_layer_metrics(root):
    res = tiny.run(root, "serve.tiny.open", trace=True)
    assert res.correct
    assert set(res.metrics) == {"padded_share.serve"}
    assert 0.0 < res.metrics["padded_share.serve"]["value"] < 100.0


def _run_py(cwd: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.vox-d72-r400.realign", "--seed", str(2 ** 31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_before_any_work():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no run" in p.stderr and "TPU" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_its_format():
    import re
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    assert all(_line(w) for w in spec["command"])
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.match(NAME, c["name"]) and _line(c["why"])
        assert _line(c["source"]) and (REPO / c["file"]).is_file()
        assert all(re.match(NAME, k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.match(NAME, w["name"]) and _line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in metrics:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert _line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", list(cells))
        assert set(m["workloads"]) <= set(moved)
    for name in cells:
        c = harness.load_cell(REPO, name)
        assert "setup_s" in {m["name"] for m in c.end_to_end}
        assert len(c.end_to_end) >= 2 and c.per_layer
