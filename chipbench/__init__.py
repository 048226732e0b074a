"""The chip benchmark: one harness driven by data.

A cell of ``BENCHMARK.json`` names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``); a
per-layer metric is a reader in ``metrics/<name>.py``; the limits that
decide ``correct`` for a cell are in ``limits/<workload>.json``. The
harness finds each by name, so a new cell adds files and entries only.

Run one cell once:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
