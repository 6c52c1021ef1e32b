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


# A cell's set-up as measured on the chip, where it is longer than the 60 s a
# run is allowed for it: ``bench/setup_s/<cell>.json`` holds ``{"setup_s": s}``
# (with where it was measured); the benchmark's ledger, once it holds the cell,
# gives its newest median too. A cell of more than one chip needs one of them.
SETUP_DIR = os.path.join(ROOT, "bench", "setup_s")
LEDGER = os.path.join(ROOT, "PERF_LEDGER.jsonl")


def measured_setup_s(name: str, setup_dir: str = SETUP_DIR, ledger: str = LEDGER):
    """The larger of the recorded and the ledger's newest set-up of cell
    ``name``, in seconds; None where neither holds it."""
    found = []
    path = os.path.join(setup_dir, f"{name}.json")
    if os.path.exists(path):
        with open(path) as f:
            found.append(float(json.load(f)["setup_s"]))
    newest = None
    if os.path.exists(ledger):
        with open(ledger) as f:
            for line in f:
                entry = json.loads(line)
                value = ((entry.get("end_to_end") or {}).get("setup_s") or [None])[-1]
                if entry.get("workload") == name and value is not None:
                    newest = float(value)
    if newest is not None:
        found.append(newest)
    return max(found) if found else None


def full_check_s(run_seconds: int, cells: list) -> float:
    """Chip seconds of a full check of ``cells`` [(chips, setup_s or None)],
    padded with one-chip cells to 24: 2 + 14 runs per cell, each of
    run_seconds plus 60 s or the cell's own set-up where that is longer, and
    2 x 90 s per cell to compile; a four-chip cell's count four times. 1200 s
    spare. A cell of several chips with no measured set-up is refused."""
    total = 0.0
    for chips, setup_s in cells + [(1, None)] * (24 - len(cells)):
        if chips > 1 and setup_s is None:
            raise LookupError(f"a cell of {chips} chips needs its measured set-up in {SETUP_DIR}")
        total += chips * (14 * (run_seconds + max(60.0, setup_s or 0.0)) + 2 * 90)
    return total + 2 * (run_seconds + 60) + 1200


def test_a_full_check_fits_its_time(manifest):
    cells = [(c["chips"], measured_setup_s(c["name"])) for c in manifest["workloads"]]
    assert full_check_s(manifest["run_seconds"], cells) <= 43200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, math.floor(len(manifest["workloads"]) / 2))


def test_a_cell_of_several_chips_is_priced_at_its_measured_setup(tmp_path):
    (tmp_path / "big.json").write_text(json.dumps({"setup_s": 441.5}))
    none = str(tmp_path / "no_ledger.jsonl")
    assert measured_setup_s("big", str(tmp_path), none) == 441.5
    assert measured_setup_s("small", str(tmp_path), none) is None
    with pytest.raises(LookupError):
        full_check_s(20, [(4, measured_setup_s("small", str(tmp_path), none))])
    # Four chips at 441.5 s of set-up beside 23 one-chip cells overrun the check.
    assert full_check_s(20, [(4, 441.5)]) > 43200
    assert full_check_s(20, [(4, 60.0)]) <= 43200


def test_the_ledgers_newest_setup_counts(tmp_path):
    ledger = tmp_path / "ledger.jsonl"
    lines = [
        {"workload": "c", "end_to_end": {"setup_s": [None, 90.0]}},
        {"workload": "d", "end_to_end": {"setup_s": [1.0, 500.0]}},
        {"workload": "c", "end_to_end": {"setup_s": [90.0, 70.0]}},
        {"workload": None},
    ]
    ledger.write_text("".join(json.dumps(x) + "\n" for x in lines))
    assert measured_setup_s("c", str(tmp_path), str(ledger)) == 70.0
    (tmp_path / "c.json").write_text(json.dumps({"setup_s": 80.0}))
    assert measured_setup_s("c", str(tmp_path), str(ledger)) == 80.0
