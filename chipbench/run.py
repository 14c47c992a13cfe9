"""Run one cell of BENCHMARK.json once, on the accelerator of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``configs/<name>.json``, with its plain
reference in ``reference/``) and a traffic mix (``traffic/<name>.json``,
read by ``traffic/generator.py``); the mix's ``kind`` names the driver in
``kinds/``, and ``cells/<cell>.json`` holds the limits of the correctness
comparison.  Each per-layer metric is read by ``metrics/<name>.py``.  So
a configuration, a mix, a cell or a metric is added by adding files and
entries; nothing here names one.

Set-up (weights and inputs made from the seed, capture, planning, every
compilation) is timed as ``setup_s``; then the driver measures for
``--seconds``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window and from the program's counters.  After the
window the driver compares what the timed path produced with the plain
reference.  Progress goes to standard error, which ends with each compared
number beside its limit; the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` when traced) and, last, ``checks``.

Exits non-zero, with no result line, when JAX finds no accelerator, fewer
chips than the cell asks for, a device that ``peaks.json`` does not list,
or when the program is not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

GIB = 2 ** 30


class NoAccelerator(RuntimeError):
    """JAX found no usable accelerator for the cell."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    from chipbench.traffic import generator
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = load_json(root / entry["file"])
    bench_dir = root / bench["paths"][0]

    def applies(m):
        return name in m.get("workloads", [name])

    return SimpleNamespace(
        name=name, workload=w, entry=entry, config=config,
        model=config["model"],
        traffic=generator.load(w["traffic"], bench_dir / "traffic"),
        limits=load_json(bench_dir / "cells" / f"{name}.json")["limits"],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        bench_dir=bench_dir)


def find_devices(chips: int, peaks: dict, require_accelerator: bool = True):
    """The devices to run on and the peaks of their kind."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if not require_accelerator:
        return devs[:chips], peaks["devices"]["TPU v5 lite"]
    if dev.platform == "cpu":
        raise NoAccelerator("JAX finds no accelerator, only the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX finds "
                            f"{len(devs)} {dev.device_kind!r}")
    row = peaks["devices"].get(dev.device_kind)
    if row is None:
        raise NoAccelerator(f"{dev.device_kind!r} is not in peaks.json")
    return devs[:chips], row


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR when
    set, else ``.jax_cache`` at the root of the checkout.  Every program
    is cached, however quick to compile, so that the executor's
    per-equation programs come from the cache on later runs."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def load_reader(bench_dir: Path, name: str):
    """``metrics/<name>.py``'s ``read(run)``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_metrics(cell, layer_run) -> dict:
    """Each per-layer metric whose reader finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell.bench_dir, m["name"])(layer_run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_accelerator: bool = True) -> dict:
    """Run the cell once; return the result object."""
    cell = load_cell(workload, root)
    peaks = load_json(cell.bench_dir / "peaks.json")
    devices, peak = find_devices(cell.workload["chips"], peaks,
                                 require_accelerator)
    log(f"[device] {devices[0].platform} {devices[0].device_kind!r} x "
        f"{len(devices)}; peaks: {peak} ({peaks['source']})")
    log(f"[cache] {setup_compile_cache()}")
    driver = importlib.import_module(
        f"chipbench.kinds.{cell.traffic['kind']}")
    res = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     devices=devices, peak=peak, log=log)

    if trace:
        metrics = per_layer_metrics(cell, res.layer)
    else:
        values = dict(res.end_to_end,
                      setup_s=res.t_window_start - T_START)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(res.memory_peak_bytes)}
    out = {"correct": bool(res.correct), "attempted": int(res.attempted),
           "failed": int(res.failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = res.trace.busy_s
        device["window_s"] = res.trace.window_s
        out["breakdown"] = res.trace.breakdown
    out["compiles_in_window"] = res.compiles_in_window
    out["checks"] = res.checks
    for name, c in res.checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        log(f"chipbench: {e}; nothing was run")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
