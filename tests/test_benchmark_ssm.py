"""The Jamba cell's benchmark code held by the suite the driver runs: the
cases of ``benchmark/tests/test_ssm.py`` (the cell's files, the
configuration against the catalog, ``flops_mamba1`` by hand, each new
metric through its reader, the cell's rehearsal on the CPU), imported."""
from benchmark.tests.test_ssm import *  # noqa: F401,F403
