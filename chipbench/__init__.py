"""The benchmark: BENCHMARK.json's harness, yardstick and data files.

Nothing here is imported by the program; the program is the system
under test.  See PERF.md for what is measured and why.
"""
