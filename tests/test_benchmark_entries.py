"""``BENCHMARK.json``'s ``per_layer`` entries held by the suite the driver
runs: the cases of ``benchmark/tests/test_benchmark_entries.py`` (one file
an entry, a reader that exists, cells that exist and report what the entry
moves, no two entries one measurement, at most 128), imported."""
from benchmark.tests.test_benchmark_entries import *  # noqa: F401,F403
