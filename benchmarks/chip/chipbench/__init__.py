"""On-chip training benchmark of the sparse-backprop CNN path.

``run.py`` (one directory up) is the entry point; this package holds the
yardstick: cell lookup, input generation, FLOP counting, the trace
reduction, the correctness comparison and the harness that drives one run.
"""
