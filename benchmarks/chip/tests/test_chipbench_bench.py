"""BENCHMARK.json: every entry resolves to its files by name, and names,
units, bounds and the run length keep to the benchmark's contract."""
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if word.endswith(".py"):
            assert word.startswith("benchmarks/chip/")
            assert (ROOT / word).is_file()


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + CELLS + [
        c["name"] for c in BENCH["configs"]]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.fullmatch(name), name
    for m in METRICS:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)
    for text in [w["why"] for w in BENCH["workloads"] + BENCH["configs"]] + [
            m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    spec = run.resolve(cell)
    config, traffic = spec["config"], spec["traffic"]
    assert (HERE / "drivers" / f"{traffic['driver']}.py").is_file()
    entry = {c["name"]: c for c in BENCH["configs"]}[spec["cell"]["config"]]
    assert set(entry["reduced"]) == set(config["reduced"])
    assert spec["cell"]["chips"] in (1, 4)
    assert set(config["limits"]) == {"score_gap", "short_results",
                                     "flag_errors", "entity_misses",
                                     "stale_reads"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        mod = run.load_module(HERE / "metrics" / f"{m['name']}.py", "m")
        assert callable(mod.read)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]


def test_metrics_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_configs_used_and_files_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/")
        assert c["source"].startswith("https://")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_run_length_fits_the_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peaks_table_keyed_by_device_kind():
    peaks = run.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in peaks["source"]
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9 imaginary")
