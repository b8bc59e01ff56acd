"""Helpers of the benchmark's tests: the cells at a size a CPU holds."""
import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run  # noqa: E402

MANIFEST = run.load_manifest()


def tiny(name):
    """The cell's manifest entry, workload and config, cut to a CPU size."""
    entry, workload, config = run.lookup(MANIFEST, name)
    workload, config = copy.deepcopy(workload), copy.deepcopy(config)
    if "n" in config:
        config["n"] = 256
    return entry, workload, config


def run_tiny(name, seconds=1.0, trace=False, seed=2**33 + 5, entry=None,
             workload=None, config=None, devices=None, **kw):
    """Run a cell on the CPU through the harness (no look for a chip)."""
    import jax
    if None in (entry, workload, config):
        e, w, c = tiny(name)
        entry, workload, config = entry or e, workload or w, config or c
    if devices is None:
        devices = jax.devices()[:1] * entry["chips"]
    return run.run_cell(entry, workload, config, seed=seed, seconds=seconds,
                        trace=trace, manifest=kw.pop("manifest", MANIFEST),
                        devices=devices, **kw)
