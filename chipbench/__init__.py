"""Chip benchmark for the targetDP lattice codes: one cell per run.

Run one cell once from the root of a checkout::

    python3 chipbench/run.py --workload ludwig_lc_192.timesteps \
        --seed 1 --seconds 10 --trace 0

``BENCHMARK.json`` names the cells; each configuration, traffic mix and
metric is a file of its own under ``configs/``, ``traffic/`` and
``metrics/``, found by its name.
"""
