"""chipbench: the benchmark's harness, data, yardstick and trace reduction.

Everything a later PR may NOT change lives here: traffic generation, the
reduction from traces and spans to metrics, the table of peaks, the functions
that count a kernel's operations and bytes, and the comparison that decides
``correct``. From the program it takes only the system under test and its
spans, counters and kernel names. ``run.py`` is the one command.
"""
