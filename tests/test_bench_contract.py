"""bench.py's contract with whoever runs it: one process holds the chip;
without a chip it exits non-zero and prints no number; a device kind the
peaks table does not know raises; a phase that fails is named and the
exit code is non-zero.  Nothing is replayed and nothing is retried."""
import importlib.util
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture(scope="module")
def bench():
    # load once per module: exec'ing bench.py inserts the repo root into
    # sys.path, so re-loading per test would leak duplicate entries
    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_chip_exits_nonzero_and_prints_no_number(tmp_path):
    """`python bench.py` on a machine with no chip: non-zero exit, empty
    stdout (no metric, no replayed record), nothing written for the gate."""
    obs = tmp_path / "obs.json"
    proc = subprocess.run(
        [sys.executable, BENCH, "--obs-out", str(obs)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr
    assert not obs.exists()


def test_unknown_device_kind_raises(bench, monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v99 mega")])
    with pytest.raises(ValueError, match="unknown device_kind 'TPU v99 mega'"):
        bench._chip_peak_flops()
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    assert bench._chip_peak_flops() == 197e12


def test_failing_phase_is_named_and_fatal(bench, capsys):
    """The first phase that raises is named on stderr and the exception
    escapes (non-zero exit, no record); later phases do not run."""
    ran = []

    def boom():
        raise RuntimeError("Mosaic rejected the kernel")

    phases = (("first", lambda: ran.append("first") or {"a": 1}),
              ("vit", boom),
              ("never", lambda: ran.append("never") or {}))
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        bench._run_phases(phases)
    assert ran == ["first"]
    assert "phase 'vit' failed" in capsys.readouterr().err
