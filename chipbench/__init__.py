"""Chip benchmark of the HYLU solver: one cell of ``BENCHMARK.json`` per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration in
``configs/<config>.json`` (whose ``pattern.generator`` names the module
``generators/<generator>.py`` that builds its matrix, and whose
``rehearsal`` gives the small size the tests run it at on the CPU), its
traffic mix in ``traffic/<traffic>.json`` (whose ``kind`` names the
generator module ``traffic/<kind>.py``), and each per-layer metric's reader
in ``metrics/<metric>.py``.
"""
