"""The benchmark: one cell of BENCHMARK.json per process, on the chip.

See ``benchmark/README.md``. Everything the yardstick needs lives in this
directory; from the program it takes only the system under test and its
spans, counters and kernel names.
"""
