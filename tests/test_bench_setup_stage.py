"""Collects benchmarks/tests/test_setup_stage.py under tier-1: its tests, cases and
fixtures, as they are (tests/conftest.py puts the benchmark on the path)."""
from test_setup_stage import *  # noqa: F401,F403
