"""chip_smoke.py without the chip: it must FAIL here (no CPU mode, no
`"ok": true`), while every phase function passes at the tiny preset on
the virtual CPU mesh — the guide's first rehearsal, kept as a test so a
wrong path, argument or control flow costs no chip time.  Plus the rules
around it: `--chips 4` selects only the multi-chip phase, a failed phase
stops the run, and the compile cache can be placed from outside."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from mmlspark_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
ALL_PHASES = dict(chip_smoke.ONE_CHIP_PHASES + chip_smoke.FOUR_CHIP_PHASES)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_without_a_chip_exits_nonzero_and_prints_no_ok(argv, tmp_path):
    proc = subprocess.run(
        [sys.executable, SMOKE, *argv], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300,
        cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("name", list(ALL_PHASES))
def test_phase_passes_tiny_on_cpu(name):
    """Each phase, through the same entry points, at a size the CPU
    turns around in seconds.  No kernel is IN a CPU program (interpret
    mode), and the phase knows to expect that."""
    rec = ALL_PHASES[name](chip_smoke.SIZES["tiny"], seed=0)
    assert rec["ok"] is True, rec
    assert rec["max_diff"] <= rec["tol"]
    assert rec.get("kernels_expected", False) is False
    json.dumps(rec)  # every phase line must be one JSON object


def test_chips_4_selects_only_the_multichip_phase():
    assert [n for n, _ in chip_smoke.select_phases(4)] == ["lm3d"]
    assert [n for n, _ in chip_smoke.select_phases(1)] == [
        "featurize", "vit", "lm_train", "admit_attn", "lm_serve"]


def test_first_failing_phase_stops_the_run(capsys):
    ran = []

    def passes(size, seed):
        ran.append("passes")
        return {"ok": True}

    def raises(size, seed):
        raise RuntimeError("Mosaic rejected the kernel")

    def never(size, seed):
        ran.append("never")
        return {"ok": True}

    phases = (("a", passes), ("b", raises), ("c", never))
    assert chip_smoke.run_phases(phases, {}, 0) is False
    assert ran == ["passes"]
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["phase"], l["ok"]) for l in lines] == [("a", True),
                                                      ("b", False)]
    assert "Mosaic rejected" in lines[1]["error"]


def test_cache_dir_placed_from_outside_is_left_alone(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == os.path.join(REPO, compile_cache.CACHE_DIRNAME)
        assert jax.config.jax_compilation_cache_dir == first
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert compile_cache.CACHE_DIRNAME + "/" in f.read().split()
    finally:  # the suite itself runs with the cache off (conftest.py)
        jax.config.update("jax_compilation_cache_dir", before)
