"""BENCHMARK.json against the rules it is checked by, and every name in it
against the files the harness finds by that name."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

MANIFEST_PATH = ROOT / "BENCHMARK.json"
MANIFEST = json.loads(MANIFEST_PATH.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST_PATH.stat().st_size <= 64 * 1024
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(c) for c in cmd)
    paths = MANIFEST["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert not p.startswith("/") and (ROOT / p).is_dir()
    # the command names only files under the benchmark's own paths
    for word in cmd[1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in paths)
            assert (ROOT / word).is_file()


def test_run_seconds_fits_the_check_with_24_cells():
    r = MANIFEST["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MANIFEST[group]:
            assert NAME.match(e["name"]), e["name"]
    for names in ([c["name"] for c in MANIFEST["configs"]], CELLS,
                  [m["name"] for m in METRICS]):
        assert len(names) == len(set(names))


def test_configs():
    paths = MANIFEST["paths"]
    assert 1 <= len(MANIFEST["configs"]) <= 24
    files = set()
    used = {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert c["file"] not in files and (ROOT / c["file"]).is_file()
        files.add(c["file"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert len(c["reduced"]) <= 16
        assert c["name"] in used
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]


def test_cells():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert 1 <= len(CELLS) <= 24
    assert four <= max(1, len(CELLS) // 2)
    pairs = set()
    configs = {c["name"] for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["loop"] in ("closed", "open")
        limits = traffic["check"]["limits"]
        assert limits and set(limits) <= {"max_logit_gap", "mean_logit_gap",
                                         "kv_rel_err"}
        assert all(v > 0 for v in limits.values())
        config = json.loads(
            (ROOT / "bench" / "configs" / f"{w['config']}.json").read_text())
        assert int(config["tp"]) <= w["chips"]


def test_metrics_and_their_readers():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128
    for m in METRICS:
        allowed = {"name", "unit", "better", "source", "workloads"}
        if m in MANIFEST["end_to_end"]:
            allowed |= {"bound"}
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            allowed |= {"layer", "moves"}
            assert _line(m["layer"]) and m["moves"] in e2e
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        assert set(m) <= allowed and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        src = (ROOT / "bench" / "metrics" / f"{m['name']}.py").read_text()
        assert "def read(rec)" in src


def _reports(cell, group):
    return [m["name"] for m in MANIFEST[group]
            if cell in m.get("workloads", CELLS)]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_enough(cell):
    e2e = _reports(cell, "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = [m for m in MANIFEST["per_layer"]
                 if cell in m.get("workloads", CELLS)]
    assert per_layer
    # a per-layer metric names cells that report the metric it moves
    for m in per_layer:
        assert m["moves"] in e2e
