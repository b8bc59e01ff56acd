"""The chip benchmark of the solver library: one data-driven command.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root names the cells; each cell's
parameters live in ``bench/workloads/<cell>.json``, its deployment in
``bench/configs/<config>.json``, the code that drives it in
``bench/drivers/<driver>.py`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Adding a configuration, a cell or a
metric adds files and manifest entries only.
"""
