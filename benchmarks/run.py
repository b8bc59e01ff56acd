"""Benchmark entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--sections a,b]
                                            [--json-dir DIR]

Sections:
  solvers      — §4 direct-vs-iterative method table (wall + residual)
  solvers_spmd — CA-Krylov (ca_cg/ca_gmres) wall vs device count (1→8)
  direct       — factor GFLOP/s vs jax.scipy + unrolled-vs-fori compile time
  direct_spmd  — block-cyclic distributed LU GFLOP/s vs device count (1→8)
  eigls        — QR GFLOP/s vs jnp.linalg.qr, LSQR/CGLS wall, Lanczos it/s
  eigls_spmd   — TSQR GFLOP/s vs device count (1→8)
  sparse       — BSR SpMV GB/s + sparse-vs-dense CG wall time at matched n
  scaling      — Figs. 3/4: speedup vs node count (modeled v5e + emulated)
  local_accel  — §4 CUDA↔ATLAS ablation (Pallas↔jnp correctness + model)
  serve        — solve server requests/sec + p50/p99 (cold vs warm cache,
                 repeated-A factor reuse)

``--json-dir`` writes one ``BENCH_<section>.json`` per section (the CI
smoke artifacts; ``benchmarks.check_regression`` gates them against the
checked-in ``benchmarks/reference/`` numbers) plus a ``TELEM_<section>
.json`` sibling — the telemetry session (span timings, per-site
communication volume, solver convergence records) captured while the
section ran.  Render one with ``python -m repro.telemetry.report``.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / skip subprocess scaling runs")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to run "
                         "(default: all)")
    ap.add_argument("--json-dir", default=None,
                    help="also write BENCH_<section>.json files here")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "experiments", "bench.csv"))
    args = ap.parse_args(argv)
    known = {"solvers", "solvers_spmd", "direct", "direct_spmd", "eigls",
             "eigls_spmd", "sparse", "local_accel", "scaling", "serve"}
    enabled = None
    if args.sections:
        enabled = {s.strip() for s in args.sections.split(",") if s.strip()}
        unknown = enabled - known
        if unknown:
            raise SystemExit(f"unknown sections {sorted(unknown)}; "
                             f"known: {sorted(known)}")

    from repro import compile_cache
    compile_cache.enable()

    from benchmarks import (bench_direct, bench_eigls, bench_local_accel,
                            bench_scaling, bench_serve, bench_solvers,
                            bench_sparse)
    from benchmarks.common import ROWS

    failures = []
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)

    def section(name, fn, *a, **kw):
        if enabled is not None and name not in enabled:
            return
        print(f"== {name} ==", flush=True)
        sess = None
        try:
            if args.json_dir:
                # armed telemetry session per section: every
                # BENCH_<section>.json gains a TELEM_<section>.json
                # sibling (spans, per-site comm bytes, solve records,
                # and — perf=True — roofline-attributed perf records)
                from repro import telemetry
                with telemetry.session(name, perf=True) as sess:
                    fn(*a, **kw)
            else:
                fn(*a, **kw)
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
        finally:
            if sess is not None:
                path = os.path.join(args.json_dir, f"TELEM_{name}.json")
                sess.save(path)
                print(f"wrote {path}")

    section("solvers", bench_solvers.run,
            sizes=(256, 512) if args.quick else (512, 1024),
            dtypes=("float32",) if args.quick else ("float32", "float64"))
    section("direct", bench_direct.run,
            sizes=(256,) if args.quick else (512, 1024),
            compile_sizes=(256, 512) if args.quick else (256, 512, 1024),
            nb=64 if args.quick else 128)
    section("solvers_spmd", bench_solvers.run_spmd,
            device_counts=(1, 8) if args.quick else (1, 2, 4, 8),
            n=512 if args.quick else 1024)
    # n stays 1024 even under --quick: the monotonicity gate in
    # check_regression needs enough work per panel step to amortize the
    # broadcast (at n<=512 the sweep measures collective latency only).
    section("direct_spmd", bench_direct.run_spmd,
            device_counts=(1, 2, 8) if args.quick else (1, 2, 4, 8),
            n=1024, nb=64)
    if args.quick:
        section("eigls", bench_eigls.run, shapes=((512, 128),), nb=64,
                ls_shape=(1024, 128), grid=32, ncv=60)
    else:
        section("eigls", bench_eigls.run)
    section("eigls_spmd", bench_eigls.run_spmd,
            device_counts=(1, 2, 8) if args.quick else (1, 2, 4, 8),
            m=2048 if args.quick else 8192,
            n=128 if args.quick else 256)
    section("sparse", bench_sparse.run,
            grids=(32,) if args.quick else (48, 64),
            nb=32 if args.quick else 64)
    section("local_accel", bench_local_accel.run)
    if args.quick:
        section("serve", bench_serve.run, sizes=(40, 60), wave=8,
                warm_waves=2, repeats=3, distinct=3, max_batch=4)
    else:
        section("serve", bench_serve.run)
    if not args.quick:
        section("scaling", bench_scaling.run, n=2048,
                device_counts=(1, 2, 4, 8, 16))

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["bench", "name", "value", "unit", "note"])
        w.writerows(ROWS)
    print(f"wrote {len(ROWS)} rows to {args.out}")

    if args.json_dir:
        by_section: dict[str, list] = {}
        for bench, name, value, unit, note in ROWS:
            by_section.setdefault(bench, []).append(
                {"name": name, "value": value, "unit": unit, "note": note})
        for bench, rows in by_section.items():
            path = os.path.join(args.json_dir, f"BENCH_{bench}.json")
            with open(path, "w") as f:
                json.dump({"section": bench, "rows": rows}, f, indent=1)
            print(f"wrote {path}")

    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
