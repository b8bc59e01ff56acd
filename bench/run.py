"""Run one benchmark cell on the chips of this machine.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its parameters in
``bench/workloads/<cell>.json``, its deployment in
``bench/configs/<config>.json`` and its driver in
``bench/drivers/<driver>.py``.  The driver builds the cell's data on the
device from ``--seed`` and warms its shapes (set-up), then measures for
``--seconds`` (the window); once the window has closed and the peak
memory has been read, it checks every answer the window produced.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its
limit.  The same numbers close standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from bench import tracing  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


# --------------------------------------------------------------------------
# the manifest and the files it names
# --------------------------------------------------------------------------

def load_manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _read_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def workload_file(name: str, bench: pathlib.Path = BENCH) -> dict:
    return _read_json(bench / "workloads" / f"{name}.json")


def lookup(manifest: dict, workload: str, root: pathlib.Path = ROOT):
    """The cell's manifest entry, its workload file and its config file."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _read_json(root / configs[entry["config"]]["file"])
    return entry, workload_file(workload, root / "bench"), config


def load_driver(name: str, bench: pathlib.Path = BENCH):
    """``bench/drivers/<name>.py`` as a module."""
    return _load_file(bench / "drivers" / f"{name}.py")


def load_reader(name: str, bench: pathlib.Path = BENCH):
    """The ``read(cell, trace)`` function of ``bench/metrics/<name>.py``."""
    return _load_file(bench / "metrics" / f"{name}.py").read


def _load_file(path: pathlib.Path):
    """The module in ``path``, loaded once per path."""
    path = path.resolve()
    modname = "bench_file_" + re.sub(r"\W", "_", str(path))
    if modname in sys.modules:
        return sys.modules[modname]
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_for(manifest: dict, workload: str, section: str) -> list:
    """The metrics of ``section`` that the cell reports: those without a
    ``workloads`` key, and those that list the cell."""
    return [m for m in manifest[section]
            if workload in m.get("workloads", [workload])]


# --------------------------------------------------------------------------
# one cell's run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """What a driver is given and what it fills in."""
    name: str
    config: dict
    workload: dict
    chips: int
    seed: int
    devices: list
    seconds: float = 0.0
    attempted: int = 0
    failed: int = 0
    metrics: dict = dataclasses.field(default_factory=dict)
    readings: dict = dataclasses.field(default_factory=dict)
    state: dict = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts XLA compiles (cache misses of the persistent cache too)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def run_cell(entry: dict, workload: dict, config: dict, *, seed: int,
             seconds: float, trace: bool, manifest: dict,
             devices=None, t_start: float = T_START,
             bench: pathlib.Path = BENCH) -> dict:
    """Set up, measure and check one cell; returns the result line."""
    import jax

    devices = list(devices if devices is not None else jax.devices())
    chips = entry["chips"]
    cell = Cell(entry["name"], config, workload, chips, seed,
                devices[:chips], seconds)
    driver = load_driver(workload["driver"], bench)
    compiles = CompileCounter()

    with tracing.span("setup"):
        driver.prepare(cell)
    setup_s = time.perf_counter() - t_start

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir,
                                 profiler_options=tracing.profiler_options())
    before = compiles.count
    try:
        with tracing.span("window"):
            driver.measure(cell, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles_in_window = compiles.count - before

    memory_peak = max(_peak_bytes(d) for d in cell.devices)
    with tracing.span("release"):
        driver.release(cell)
    checks = driver.verify(cell)
    correct = cell.failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    d0 = cell.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": cell.attempted,
           "failed": cell.failed}
    if trace:
        reduced = tracing.load(tracing.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = tracing.mean_busy_s(reduced)
        device["window_s"] = reduced.window_s
        values = {}
        for m in metrics_for(manifest, cell.name, "per_layer"):
            v = load_reader(m["name"], bench)(cell, reduced)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        out.update(metrics=values, device=device, breakdown={
            "device_ops": tracing.top_ops(reduced),
            "idle_gaps": tracing.idle_gaps(reduced)})
    else:
        values = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in metrics_for(manifest, cell.name, "end_to_end"):
            if m["name"] in cell.metrics:
                values[m["name"]] = {"value": cell.metrics[m["name"]],
                                     "unit": m["unit"]}
        out.update(metrics=values, device=device)
    out["compiles_in_window"] = compiles_in_window
    out["checks"] = checks
    return out


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# --------------------------------------------------------------------------
# the command
# --------------------------------------------------------------------------

def setup_jax_cache() -> None:
    """JAX's persistent compilation cache where the library places it
    (``repro.compile_cache.enable()``: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache`` in this checkout), keeping every program
    however fast it compiled, so that only a checkout's first run
    compiles.  Call before the first compile."""
    import jax
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_manifest()
    entry, workload, config = lookup(manifest, args.workload)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the library is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < entry["chips"]:
        print(f"bench: {args.workload} needs {entry['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    setup_jax_cache()

    out = run_cell(entry, workload, config, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   manifest=manifest, devices=devices)
    print_result(out)
    return 0


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
