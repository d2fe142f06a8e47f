"""A new configuration, traffic mix, kind of traffic or per-layer metric is
added as files and manifest entries; the harness finds each by name,
unedited."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from tnnbench import harness  # noqa: E402


def test_new_files_are_found_by_name(tmp_path):
    for d in ("metrics", "traffic", "drivers"):
        (tmp_path / d).mkdir()
    (tmp_path / "metrics" / "stall_ms.py").write_text(
        "def read(run):\n    return run.counters.get('stall_ms')\n")
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"driver": "serve_burst", "rate_hz": 10.0}))
    (tmp_path / "drivers" / "serve_burst.py").write_text(
        "def run(ctx):\n    return ('burst', ctx.traffic['rate_hz'])\n")
    cfg_file = tmp_path / "tnn-wide.json"
    cfg_file.write_text(json.dumps({"name": "tnn-wide", "sites": 900}))
    manifest = {
        "configs": [{"name": "tnn-wide", "file": str(cfg_file)}],
        "workloads": [{"name": "wide-burst", "config": "tnn-wide",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "setup_s"},
                       {"name": "serve_p95_ms", "workloads": ["wide-burst"]},
                       {"name": "train_img_s", "workloads": ["other"]}],
        "per_layer": [{"name": "stall_ms.burst", "workloads": ["wide-burst"]}],
    }
    cell = harness.cell_entry(manifest, "wide-burst")
    assert harness.config_for(manifest, cell)["sites"] == 900
    traffic = harness.traffic_for(cell, bench=tmp_path)
    assert traffic["rate_hz"] == 10.0
    drive = harness.driver(traffic["driver"], bench=tmp_path)

    class Ctx:
        pass

    Ctx.traffic = traffic
    assert drive(Ctx) == ("burst", 10.0)
    read = harness.reader("stall_ms.burst", bench=tmp_path)

    class Run:
        counters = {"stall_ms": 4.5}

    assert read(Run()) == 4.5
    assert [m["name"] for m in harness.metrics_of(manifest, "wide-burst",
                                                  "end_to_end")] == [
        "setup_s", "serve_p95_ms"]


def test_a_number_without_a_limit_is_refused(tmp_path):
    limits = tmp_path / "limits.json"
    limits.write_text(json.dumps({"w_mismatch": 0, "gap": 0.5}))
    ok, checks = harness.judge({"w_mismatch": 0, "gap": 0.25}, limits)
    assert ok and checks["gap"] == {"value": 0.25, "limit": 0.5}
    assert not harness.judge({"w_mismatch": 3}, limits)[0]
    try:
        harness.judge({"unknown": 0}, limits)
    except KeyError:
        pass
    else:
        raise AssertionError("a number without a limit passed")
