"""The benchmark's own machinery: loading a cell's files, the traffic
generator, the trace reduction, the peaks table, the per-kernel work
functions and the correctness comparisons. Nothing here imports the
program under test (``src/repro``); the kind drivers in ``kinds/`` do.
"""
