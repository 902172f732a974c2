"""Chip benchmark of DSLog: data-driven cells over the paper's Figs 8/9 pipelines.

Run one cell with ``python3 -m bench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; ``BENCHMARK.json``
names the cells.  ``bench/configs``, ``bench/traffic`` and ``bench/metrics``
hold one file per configuration, traffic mix and metric, and ``bench/ops``,
``bench/layouts`` and ``bench/loops`` one module per array operation, cell
layout and driving loop, each found by the name a data file gives it.
"""
