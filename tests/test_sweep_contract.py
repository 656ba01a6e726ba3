"""Sweep-harness de-risk: every mfu_sweep mode must survive a CPU dry-run
before it is handed chip time — a typo or API drift in any sweep mode
would otherwise be discovered on the chip's clock.  Reference analogue:
the harness tests its own benchmark driver (Benchmarks.scala:36-80).

All five modes run CONCURRENTLY as subprocesses with the committed smoke
envs (MFU_SWEEP_SMOKE / ATTN_SWEEP_POINTS / DECODE_SWEEP_SMALL /
SERVING_SWEEP_SMALL), so wall time is bounded by the slowest mode, not
the sum."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SWEEP = os.path.join(REPO, "tools", "mfu_sweep.py")

MODES = {
    # mode-flag -> (extra env, min JSON lines expected on stdout)
    "--quick": ({"MFU_SWEEP_SMOKE": "1"}, 6),
    "--attn": ({"ATTN_SWEEP_POINTS": "128:64:2,196:64:2:0"}, 2),
    "--decode": ({"MFU_SWEEP_SMOKE": "1", "DECODE_SWEEP_SMALL": "1"}, 1),
    "--batcher": ({"DECODE_SWEEP_SMALL": "1"}, 1),
    "--serving": ({"SERVING_SWEEP_SMALL": "1"}, 1),
}


@pytest.fixture(scope="module")
def sweep_runs():
    """Launch every sweep mode concurrently; map mode -> (rc, stdout, stderr)."""
    procs = {}
    for flag, (env_extra, _n) in MODES.items():
        env = dict(os.environ, **env_extra)
        procs[flag] = subprocess.Popen(
            [sys.executable, SWEEP, flag], env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, cwd=REPO)
    out = {}
    for flag, p in procs.items():
        try:
            # generous: 5 concurrent JAX processes (one spawning 6 serial
            # cold-start children) contend for one core on the CI host
            stdout, stderr = p.communicate(timeout=1500)
        except subprocess.TimeoutExpired:
            p.kill()
            stdout, stderr = p.communicate()
            out[flag] = (-1, stdout, "TIMEOUT\n" + stderr[-2000:])
            continue
        out[flag] = (p.returncode, stdout, stderr)
    return out


def _json_lines(stdout: str):
    recs = []
    for line in stdout.strip().splitlines():
        recs.append(json.loads(line))  # every stdout line must be JSON
    return recs


@pytest.mark.parametrize("flag", list(MODES))
def test_mode_emits_parseable_json(sweep_runs, flag):
    rc, stdout, stderr = sweep_runs[flag]
    assert rc == 0, f"{flag} exited {rc}: {stderr[-2000:]}"
    recs = _json_lines(stdout)
    assert len(recs) >= MODES[flag][1], (flag, stdout)
    for rec in recs:
        assert "error" not in rec, (flag, rec)


def test_quick_covers_every_config(sweep_runs):
    rc, stdout, _ = sweep_runs["--quick"]
    assert rc == 0
    tags = {r["tag"] for r in _json_lines(stdout)}
    import importlib.util

    spec = importlib.util.spec_from_file_location("mfu_sweep_ut", SWEEP)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert tags == mod.QUICK, f"sweep ran {tags}, config table says {mod.QUICK}"
    for rec in _json_lines(stdout):
        assert rec["ips"] > 0 and rec["xla_flops"] > 0


def test_attn_parity_enforced(sweep_runs):
    _, stdout, _ = sweep_runs["--attn"]
    for rec in _json_lines(stdout):
        assert rec["parity_ok"] is True
        # CPU runs the interpret path; 'mosaic_validated' may only be set
        # on a real chip — asserting False here guards against the flag
        # lying when no TPU is present
        assert rec["mosaic_validated"] is False
        assert rec["pallas_path"] in ("interpret", "xla")


def test_decode_reports_all_variants(sweep_runs):
    (rec,) = _json_lines(sweep_runs["--decode"][1])
    for tag in ("f32", "int8", "int8_kv8", "gqa4"):
        assert rec[f"decode_tok_per_sec_{tag}"] > 0
    assert rec["paged_kernel_parity_ok"] is True
    assert rec["paged_kernel_validated"] is False  # no chip in CI


def test_batcher_reports_ratios(sweep_runs):
    (rec,) = _json_lines(sweep_runs["--batcher"][1])
    for key in ("batching_speedup", "paged_throughput_ratio",
                "spec_throughput_ratio", "paged_hbm_ratio"):
        assert rec[key] > 0, (key, rec)


def test_serving_reports_latency(sweep_runs):
    (rec,) = _json_lines(sweep_runs["--serving"][1])
    assert rec["serving_chip_p50_ms"] > 0
    assert rec["serving_chip_qps"] > 0
    assert rec["requests"] >= 8  # warm-up + both clients' requests landed


def test_roofline_modes_emit_json():
    """tools/roofline.py feeds docs/performance.md's pre-registered
    ceiling table; every mode must emit parseable JSON with physical
    (0, 1] MFU ceilings, or the table can silently rot."""
    roofline = os.path.join(REPO, "tools", "roofline.py")
    for model in ("resnet50", "vit_base", "lm_train", "decode", "all"):
        proc = subprocess.run(
            [sys.executable, roofline, "--model", model],
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert proc.returncode == 0, (model, proc.stderr[-500:])
        recs = [json.loads(l) for l in proc.stdout.strip().splitlines()]
        assert recs, model
        for rec in recs:
            if "mfu_ceiling" in rec:
                assert 0.0 < rec["mfu_ceiling"] <= 1.0, rec
        if model == "decode":
            (rec,) = recs
            assert rec["decode_tok_per_sec_ceiling_int8"] > \
                rec["decode_tok_per_sec_ceiling_f32"]
        if model == "all":
            assert len(recs) == 4


def test_lm_ablate_smoke_emits_json():
    """tools/lm_ablate.py is the LM-step perf-forensics tool (it found
    the 71%-of-step attention backward); its smoke mode must keep the
    whole path — model build, scanned epoch, fetch-blocked timing, JSON
    shape — runnable on CPU so API drift can't burn chip time."""
    tool = os.path.join(REPO, "tools", "lm_ablate.py")
    env = dict(os.environ, LM_ABLATE_SMOKE="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, tool], capture_output=True,
                          text=True, timeout=600, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    recs = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    assert len(recs) == 6, recs
    tags = {r["tag"] for r in recs}
    assert {"baseline_b16", "fwd_only_b16", "xla_attn_b16", "b32",
            "no_attn_b16", "h6_d128_b16"} == tags
    for rec in recs:
        assert rec["smoke"] is True
        assert rec["ms_per_step"] > 0
