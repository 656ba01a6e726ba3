"""Every example runs end-to-end in CI — the reference executes all its
notebooks as jobs on every run (core/.../nbtest/DatabricksUtilities.scala:
26-341, NotebookTests via pipeline.yaml:116); an example that silently
breaks is a doc that lies.

Each example is run as a real subprocess on the CPU backend (the same
virtual 8-device mesh the suite uses); MMLSPARK_EXAMPLE_FAST=1 lets the
heavier ones shrink their workload.  The subprocesses run a few at a time
(the idiom of tests/test_sweep_contract.py): they are independent
processes, and run one after another they alone took a quarter of
tier-1's 870 s clock (ROADMAP D11) — each example is still its own test.
"""
import glob
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

EXAMPLES_DIR = os.path.join(os.path.dirname(__file__), "..", "examples")
EXAMPLES = sorted(glob.glob(os.path.join(EXAMPLES_DIR, "*.py")))
CONCURRENT = 3  # of 8 cores: XLA's own compile threads need the rest


def test_examples_exist():
    assert len(EXAMPLES) >= 6


def _run(script):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "MMLSPARK_EXAMPLE_FAST": "1",
    })
    return subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=420)


@pytest.fixture(scope="module")
def example_runs():
    """script -> Future[CompletedProcess], started in listing order."""
    with ThreadPoolExecutor(max_workers=CONCURRENT,
                            thread_name_prefix="example-run") as pool:
        yield {script: pool.submit(_run, script) for script in EXAMPLES}


@pytest.mark.parametrize("script", EXAMPLES,
                         ids=[os.path.basename(p) for p in EXAMPLES])
def test_example_runs(example_runs, script):
    proc = example_runs[script].result()
    assert proc.returncode == 0, (
        f"{os.path.basename(script)} failed:\n{proc.stderr[-2000:]}")
    assert proc.stdout.strip(), "examples should narrate what they did"
