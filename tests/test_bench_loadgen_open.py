"""Collects benchmarks/tests/test_loadgen_open.py under tier-1: its tests, cases and
fixtures, as they are (tests/conftest.py puts the benchmark on the path)."""
from test_loadgen_open import *  # noqa: F401,F403
