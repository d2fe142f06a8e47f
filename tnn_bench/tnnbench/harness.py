"""One run of one cell: ``python3 tnn_bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (a file of sizes under ``tnn_bench/configs/``) and its traffic
(``tnn_bench/traffic/<traffic>.json``, whose ``driver`` names the module
``tnn_bench/drivers/<driver>.py`` that runs it); each per-layer metric has a
reader ``tnn_bench/metrics/<name>.py``, or ``tnn_bench/metrics/<stem>.py``
for a name ``<stem>.<cells>``. Adding a cell, a configuration, a mix, a
kind of traffic or a metric adds files and entries; nothing here changes.

The run sets up from the seed, measures for ``--seconds``, checks what the
timed path produced against the plain reference, prints each compared
number beside its limit on standard error, and prints one JSON line last on
standard output. It exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
LIMITS = BENCH / "limits.json"


def load_manifest(path: Path = MANIFEST) -> Dict[str, Any]:
    return json.loads(Path(path).read_text())


def cell_entry(manifest, name: str) -> Dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_for(manifest, cell) -> Dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == cell["config"]:
            return json.loads((ROOT / c["file"]).read_text())
    raise KeyError(f"no configuration {cell['config']!r} in BENCHMARK.json")


def traffic_for(cell, bench: Path = BENCH) -> Dict[str, Any]:
    return json.loads((bench / "traffic" / f"{cell['traffic']}.json").read_text())


def metrics_of(manifest, cell_name: str, section: str) -> List[Dict[str, Any]]:
    """The section's metrics this cell reports: those without a
    ``workloads`` list, and those whose list names the cell."""
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"tnnbench_{prefix}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: Path = BENCH):
    """The per-layer reader for ``name``: ``metrics/<name>.py``, else
    ``metrics/<stem>.py`` for ``<stem>.<suffix>``."""
    for stem in (name, name.split(".")[0]):
        path = bench / "metrics" / f"{stem}.py"
        if path.is_file():
            return _module(path, "metric").read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{bench / 'metrics'}")


def driver(name: str, bench: Path = BENCH):
    """The driver a traffic file names: ``drivers/<name>.py``'s ``run``."""
    path = bench / "drivers" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no driver {name!r} under {bench / 'drivers'}")
    return _module(path, "driver").run


def judge(numbers: Dict[str, float], limits_path: Path = LIMITS):
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit. A number without a limit is an error."""
    limits = json.loads(Path(limits_path).read_text())
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"compared number {name!r} has no limit in "
                           f"{limits_path}")
        checks[name] = {"value": value, "limit": limits[name]}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU; JAX sees {len(devs)} "
                         f"{devs[0].platform!r} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, manifest=None, cfg=None, traffic=None,
             require_chip: bool = True) -> Dict[str, Any]:
    """Run one cell and return its result line as a dict. ``cfg`` and
    ``traffic`` replace the cell's files (the harness's own tests drive a
    small configuration on the CPU that way, with ``require_chip=False``)."""
    from tnnbench import common, peaks, program, trace as trace_mod

    manifest = manifest or load_manifest()
    cell = cell_entry(manifest, cell_name)
    cfg = cfg or config_for(manifest, cell)
    traffic = traffic or traffic_for(cell)
    e2e = metrics_of(manifest, cell_name, "end_to_end")
    per_layer = metrics_of(manifest, cell_name, "per_layer")
    readers = {m["name"]: reader(m["name"]) for m in per_layer} if trace else {}
    drive = driver(traffic["driver"])

    program.import_program()
    if require_chip:
        program.enable_compile_cache()
    import jax

    devs = require_chips(int(cell["chips"])) if require_chip else jax.devices()
    kind = devs[0].device_kind
    table = peaks.peaks_for(kind) if require_chip else None

    with tempfile.TemporaryDirectory() as trace_dir:
        ctx = common.Ctx(cfg=cfg, traffic=traffic, seed=seed,
                         seconds=seconds, trace=trace, t_process=t_process,
                         trace_dir=trace_dir)
        run = drive(ctx)
    run.peaks = table

    correct, checks = judge(run.numbers)
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    out: Dict[str, Any] = {"correct": correct, "attempted": run.attempted,
                           "failed": run.failed}
    if trace:
        metrics = {}
        for m in per_layer:
            value = readers[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_mod.busy_s(run.view)
        device["window_s"] = run.view.window_s
        out["metrics"] = metrics
        out["breakdown"] = {"device_ops": trace_mod.top_ops(run.view),
                            "idle_gaps": trace_mod.idle_gaps(run.view)}
    else:
        values = dict(run.e2e, setup_s=run.setup_s)
        missing = [m["name"] for m in e2e if m["name"] not in values]
        if missing:
            raise KeyError(f"driver {traffic['driver']!r} gives no {missing}")
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]} for m in e2e}
    out["device"] = device
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]], t_process: float) -> int:
    ap = argparse.ArgumentParser(prog="tnn_bench/run.py", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_process)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
