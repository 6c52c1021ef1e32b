"""BENCHMARK.json against the rules the benchmark is held to, and every name in
it against the files the harness finds it by."""

import json
import math
import os
import re

import pytest

from bench import harness
from bench.tests.conftest import MANIFEST, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
# The widths a configuration may never cut (its shapes, for a system that runs
# no model): the columns of the SVD's matrix and every dimension of the GEMM
# but the ones listed, its rank and its oversampling.
WIDTHS = {"n", "k", "oversample"}


@pytest.fixture(scope="module")
def manifest():
    with open(MANIFEST) as f:
        return json.load(f)


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape(manifest):
    assert set(manifest) == TOP_KEYS
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd:
        if os.sep in word or word.endswith(".py"):
            assert any(word == p or word.startswith(p + "/") for p in manifest["paths"]), word
            assert os.path.exists(os.path.join(ROOT, word))


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_have_exactly_their_keys(manifest, section):
    allowed = ENTRY_KEYS[section]
    for entry in manifest[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert allowed <= set(entry) <= allowed | extra, entry


def test_names_and_units_use_the_allowed_characters(manifest):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
            for key in ("why", "layer"):
                if key in entry:
                    assert _line(entry[key]), (entry["name"], key)
            if section == "configs":
                assert _line(entry["source"])
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in manifest["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    metrics = [n for s, n in names if s in ("end_to_end", "per_layer")]
    assert len(set(metrics)) == len(metrics)
    for section in ("configs", "workloads"):
        got = [n for s, n in names if s == section]
        assert len(set(got)) == len(got)


def test_every_cell_names_files_that_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.exists(harness.reader_path(m["name"])), m


@pytest.mark.parametrize(
    "name, reader",
    [("job_s.svd", "job_s"), ("device.idle_pct.gemm", "device.idle_pct"), ("wire.send_GBps", None)],
)
def test_a_split_metric_reads_with_its_quantity(name, reader):
    assert harness.reader_path(name) == os.path.join(ROOT, "bench", "metrics", f"{reader or name}.py")


def test_config_files_state_their_cuts(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not WIDTHS & set(c["reduced"])
        for key in c["reduced"]:
            assert key in cfg["published"] and cfg["published"][key] != cfg[key]
        assert os.path.exists(os.path.join(ROOT, "bench", "problems", f"{cfg['problem']}.py"))
        for collect, limits in cfg["limits"].items():
            assert collect in ("outputs", "normest") and limits["malformed"] == 0


def test_every_cell_reports_what_it_must(manifest):
    e2e = manifest["end_to_end"]
    assert "setup_s" in {m["name"] for m in e2e}
    for w in manifest["workloads"]:
        name = w["name"]

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        reported = {m["name"] for m in e2e if applies(m)}
        assert "setup_s" in reported and len(reported) >= 2, name
        layer = [m for m in manifest["per_layer"] if applies(m)]
        assert layer, name
        for m in layer:
            assert m["moves"] in reported, (name, m["name"])


def test_bounds_and_sources(manifest):
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all(len(set(v)) == len(v) for v in layers.values())


def test_a_full_check_fits_its_time(manifest):
    # 2 + 14 runs per cell of run_seconds + 60 s each, 2 x 90 s per cell to
    # compile and 1200 s spare, for the 24 cells later PRs may reach.
    cells = 24
    seconds = (2 + 14 * cells) * (manifest["run_seconds"] + 60) + cells * 180 + 1200
    assert seconds <= 43200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, math.floor(len(manifest["workloads"]) / 2))
