"""The benchmark finds every configuration, cell and metric by name, and
BENCHMARK.json keeps to the shape its readers expect."""
import json
import os
import re

import pytest

import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)), p


def test_names_units_and_sources(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in bench[k]]
        assert len(ns) == len(set(ns))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_config_is_used_and_has_its_files(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used, c["name"]
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert run.reference_model(cfg).flops_per_token(cfg, 1024) > 0


@pytest.mark.parametrize("kind", ["workloads", "per_layer"])
def test_every_entry_is_found_by_name(bench, kind):
    for entry in bench[kind]:
        if kind == "per_layer":
            mod = run.reader(entry["name"])
            assert callable(mod.read), entry["name"]
            for w in entry.get("workloads", []):
                assert w in {x["name"] for x in bench["workloads"]}
            assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
            continue
        files = run.cell_files(entry["name"], bench)
        assert files["job"]["nodes"] >= 1 and entry["chips"] in (1, 4)
        assert files["end_to_end"] and files["per_layer"]


def test_an_unknown_cell_is_refused(bench):
    with pytest.raises(run.BenchError):
        run.cell_files("no-such-cell", bench)


def test_limits_name_known_numbers(bench):
    import check
    for w in bench["workloads"]:
        lim = dict(run.cell_files(w["name"], bench)["limits"])
        why = lim.pop("not_compared", {})
        assert set(lim) <= set(check.NUMBERS), w["name"]
        assert set(lim) | set(why) == set(check.NUMBERS), w["name"]
        for n, v in lim.items():
            # a limit lies between the sound runs' and the control's
            assert v["lower"] <= v["limit"] < v["upper"], n
