"""The harness finds every piece of a cell by name, from data files, and
``BENCHMARK.json`` keeps to the contract's shape."""

import json
import re

import pytest

from bench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_pieces_found_by_name(wl):
    cell = harness.load_cell(wl["name"], BENCH)
    assert cell.config["name"] == wl["config"]
    assert callable(harness.runner_for(cell.traffic["kind"]))
    if "arrivals" in cell.traffic:
        assert callable(harness.load_piece("arrivals",
                                           cell.traffic["arrivals"]).offsets)
    assert cell.limits
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))


def test_names_units_and_entry_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (harness.ROOT / c["file"]).is_file()
        with open(harness.ROOT / c["file"]) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        assert entry["name"] not in seen
        seen.add(entry["name"])
        if "unit" in entry:
            assert UNIT.match(entry["unit"])
            assert entry["better"] in ("lower", "higher")


def test_roofline_names_are_percent():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", BENCH)
    with pytest.raises(KeyError):
        harness.load_piece("arrivals", "no-such-process")
    from bench.peaks import peaks_for
    with pytest.raises(KeyError):
        peaks_for("TPU v0 imaginary")
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_means_no_result():
    """On the CPU the command exits non-zero and prints no result line."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=str(harness.ROOT), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_does_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    (no program) exits non-zero and prints no result."""
    import os
    import shutil
    import subprocess
    import sys
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
