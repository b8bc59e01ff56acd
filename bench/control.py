"""Readings that the limits of ``correct`` are set from.

    python -m bench.control --workload <cell> [--seeds 1,2,...] \
        [--control high|default --control-seeds 7,8,9] [--harness-seeds 4,5]

In one process (set-up is paid once per mode), solves the cell's timed
path once on each of ``--seeds`` and then its control once on each of
``--control-seeds``, and prints one JSON line per solve with every number
that ``correct`` compares.  With ``--harness-seeds`` it then runs the
cell with the control on through ``bench.run.run_cell`` once per seed,
the benchmark's own run and comparison, and prints each result line.
The benchmark's runs never run this.

The controls are the program's own precision switch,
``repro.core.api.MATMUL_PRECISION``, one step below what the
configuration states (``highest``, six bf16 passes per float32 product):
``high`` (three passes), the step that would tempt a later change, and
``default`` (XLA's one pass).
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import run

CONTROLS = ("high", "default")


def set_control(kind: str | None) -> None:
    from repro.core import api
    api.MATMUL_PRECISION = kind or "highest"


def readings(entry, workload, config, seeds, control, devices):
    driver = run.load_driver(workload["driver"])
    set_control(control)
    cell = run.Cell(entry["name"], config, workload, entry["chips"],
                    seeds[0], devices[:entry["chips"]])
    driver.prepare(cell)
    for seed in seeds:
        cell.seed = seed
        driver.measure(cell, 0.0)
        checks = driver.verify(cell)
        line = {"mode": control or "program", "seed": seed,
                "checks": {k: v["value"] for k, v in checks.items()},
                "metrics": cell.metrics}
        print(json.dumps(line), flush=True)
    driver.release(cell)


def harness(entry, workload, config, seed, seconds, control, manifest,
            devices):
    """One run of the cell through ``bench.run`` with the control on."""
    set_control(control)
    try:
        out = run.run_cell(entry, workload, config, seed=seed,
                           seconds=seconds, trace=False, manifest=manifest,
                           devices=devices)
    finally:
        set_control(None)
    checks = out.pop("checks")
    run.print_result(dict(out, control=control, seed=seed, checks=checks))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", choices=CONTROLS)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--harness-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window of each --harness-seeds run")
    args = ap.parse_args(argv)

    manifest = run.load_manifest()
    entry, workload, config = run.lookup(manifest, args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        print("bench.control: needs the cell's TPU chips", file=sys.stderr)
        return 2
    run.setup_jax_cache()

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    if seeds(args.seeds):
        readings(entry, workload, config, seeds(args.seeds), None, devices)
    if args.control and seeds(args.control_seeds):
        readings(entry, workload, config, seeds(args.control_seeds),
                 args.control, devices)
    for seed in seeds(args.harness_seeds):
        harness(entry, workload, config, seed, args.seconds, args.control,
                manifest, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
