"""The benchmark: cells, metrics and the yardstick they are measured by.

Everything a later PR may not change lives here: traffic generation, the
reduction from traces and counters to metrics, the table of peaks, the
FLOP counts, each configuration's plain reference and the comparison
that decides ``correct``. See README.md for how to add a cell, a
configuration, a traffic mix or a per-layer metric as new files.
"""
