"""The harness is driven by data: every entry of BENCHMARK.json resolves by
name to files that exist, stays inside the contract's limits, and a run prints
the contract's last line (rehearsed at toy size on the CPU)."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import cells

ROOT = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = cells.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(*argv, cwd=ROOT, env=None):
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    base.update(JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *argv],
        cwd=cwd, env=base, capture_output=True, text=True, timeout=600)


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert all(not a.startswith("/") and ".." not in a for a in BENCH["command"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_names_and_units_use_only_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        got = [e["name"] for e in BENCH[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for dirpath, _, files in os.walk(cells.HERE):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), os.path.join(dirpath, fn)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_files_that_exist(name):
    cell = cells.load_cell(name)
    assert cell.name == f"{cell.config_name}.{cell.traffic_name}"
    entry = next(c for c in BENCH["configs"] if c["name"] == cell.config_name)
    assert entry["file"].startswith("benchmarks/")
    assert cell.config["source"] == entry["source"]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.traffic["chips"] == cell.chips
    assert cell.facts.get("loss_band"), "a listed cell needs its loss band"
    for kind, key in (("data", cell.data_spec(None)["recipe"]),
                      ("flops", cell.config["flops"]),
                      ("reference", cell.config["reference"]),
                      ("drivers", cell.traffic["kind"])):
        assert os.path.isfile(os.path.join(cells.HERE, kind, key + ".py"))
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported, m


def test_every_config_is_used_by_some_cell():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_its_file_and_reader(entry):
    spec = cells.layer_metric(entry["name"])
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert spec.get("workloads") == entry.get("workloads")
    assert set(entry.get("workloads", [])) <= set(CELLS)
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    reader = importlib.import_module(
        "benchmarks.trace.readers." + spec["reader"])
    assert callable(reader.read)
    # a reader that finds nothing to read returns nothing
    from benchmarks.run import Ctx

    assert reader.read(Ctx(None, None, {}), **spec["args"]) is None


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    assert all(layer in perf for layer in layers), layers


def test_only_listed_workloads_resolve():
    with pytest.raises(KeyError):
        cells.load_cell("icalstm-hcp32.no-such-mix")
    out = run_cell("--workload", "no-such-config.dsgd", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--rehearse", "tiny")
    assert out.returncode != 0 and "correct" not in out.stdout


def test_no_chip_and_no_rehearsal_switch_fails_without_a_result():
    out = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0")
    assert out.returncode != 0
    assert "correct" not in out.stdout and "not a TPU" in out.stderr


def test_a_directory_with_only_the_benchmark_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(cells.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0", "--rehearse", "tiny", cwd=str(tmp_path))
    assert out.returncode != 0 and "correct" not in out.stdout


def check_last_line(out, traced: bool):
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) == LAST_LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # a rehearsal carries counts, never a device number
    for metric in line["metrics"]:
        assert cells.layer_metric(metric)["source"] == "program_counter"
    if traced:
        assert line["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("name,trace", [(CELLS[0], "0"), (CELLS[0], "1"),
                                        ("icalstm-hcp32.rankdad", "1")])
def test_rehearsal_prints_the_contracts_last_line(name, trace):
    out = run_cell("--workload", name, "--seed", "5", "--seconds", "1",
                   "--trace", trace, "--rehearse", "tiny")
    check_last_line(out, trace == "1")


def test_a_later_pr_adds_a_cell_with_new_files_and_an_entry_only(tmp_path):
    """A copy of the benchmark plus ONE new traffic file and ONE new
    ``workloads`` entry (no file that is there is edited): the packed site
    mesh, 4 devices x 2 sites at toy size on four host devices. It also runs
    the driver's mesh path (``auto_site_mesh``, ``parallel/``), which no
    one-chip cell executes."""
    shutil.copytree(cells.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "icalstm-hcp32.dsgd-packed4", "config": "icalstm-hcp32",
        "traffic": "dsgd-packed4", "chips": 4, "why": "a test's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks" / "traffic" / "dsgd-packed4.json").write_text(
        json.dumps({
            "name": "dsgd-packed4", "kind": "train", "chips": 4,
            "train_config": {"agg_engine": "dSGD", "sites_per_device": 8},
            "data": {"subjects_per_site": 2048},
            "rehearse": {"tiny": {"train_config": {"sites_per_device": 2},
                                  "data": {"subjects_per_site": 32}}}}))
    out = run_cell(
        "--workload", "icalstm-hcp32.dsgd-packed4", "--seed", "5", "--seconds",
        "1", "--trace", "1", "--rehearse", "tiny", cwd=str(tmp_path),
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "PYTHONPATH": ROOT})
    check_last_line(out, True)
    mesh_line = next(l for l in out.stdout.splitlines() if "[mesh]" in l)
    assert "4 of 4" in mesh_line, mesh_line
