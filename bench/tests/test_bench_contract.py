"""BENCHMARK.json names only what exists: every cell's configuration,
traffic mix, runner and limits, every per-layer metric's reader, names
and units in the allowed characters; and the harness refuses a machine
without a TPU."""

import json
import re

import pytest

import _paths
from bench import harness

ROOT = _paths.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_found_by_name(w):
    cell = harness.load_cell(ROOT, BENCH, w["name"])
    assert (ROOT / "bench/runners" / f"{cell.kind}.py").is_file()
    assert (ROOT / "bench/configs" / f"{cell.config}_ref.py").is_file()
    assert set(harness.limits(cell))
    assert w["chips"] in (1, 4)
    assert harness.cell_per_layer(BENCH, w["name"])
    e2e = {m["name"] for m in harness.cell_e2e(BENCH, w["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_names_units_and_readers():
    items = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
        + BENCH["per_layer"]
    for item in items:
        assert NAME.match(item["name"]), item["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len({m["name"] for m in BENCH["per_layer"]} | e2e) == \
        len(BENCH["per_layer"]) + len(e2e)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v9 imaginary")


def test_refuses_a_machine_without_a_tpu(capsys):
    from bench import run
    code = run.main(["--workload", "quadconv_ae.train", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0
    assert out.out == ""
    assert "no TPU" in out.err
