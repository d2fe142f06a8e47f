"""BENCHMARK.json against the benchmark's contract, and every name in it
resolving to a file of the harness."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for group in (manifest["configs"], manifest["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_cell_reports_what_its_metrics_move(manifest):
    for w in manifest["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(manifest, w["name"], "end_to_end")}
        layer = harness.metrics_of(manifest, w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_name_resolves_to_a_file(manifest):
    for c in manifest["configs"]:
        path = harness.ROOT / c["file"]
        assert path.is_file() and json.loads(path.read_text())["name"] == c["name"]
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
    for w in manifest["workloads"]:
        assert callable(harness.driver(harness.traffic_for(w)["driver"]))
    for path in (harness.BENCH / "traffic").glob("*.json"):
        assert callable(harness.driver(json.loads(path.read_text())["driver"]))
    for m in manifest["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for path in (harness.BENCH / "metrics").glob("*.py"):
        assert callable(harness.reader(path.stem))


def test_a_full_check_fits_at_24_cells(manifest):
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
