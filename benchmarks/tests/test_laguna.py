"""lib/laguna.py against counts worked by hand for Laguna-S-2.1, and the
four reducers this configuration brings on made-up traces and counters:
what they read, that they read nothing where the program has no such
counter, and that consistent counters cannot read over 100%."""
import json
import os
import types

import pytest

from lib import laguna
from lib import trace as tr
from reducers import attn_roofline, kv_roofline, moe_roofline, serve_mfu

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
PEAKS = {"flops": 197e12, "hbm_bytes": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIGS, "laguna-s-2.1.json")) as f:
        return json.load(f)


def test_parameter_count_of_the_share(cfg):
    p = laguna.param_counts(cfg)
    # attention: full 3072*6144*2 + 2*3072*1024 + 3072*48, window with 72
    assert laguna.attention_params(laguna.sizes(cfg), 48) == 44_187_648
    assert laguna.attention_params(laguna.sizes(cfg), 72) == 63_135_744
    assert p["attention"] == 2 * 44_187_648 + 3 * 63_135_744
    assert p["dense"] == 3 * 3072 * 12288
    assert p["routed"] == 4 * 128 * 3 * 3072 * 1024
    assert p["shared"] == 4 * 3 * 3072 * 1024
    assert p["router"] == 4 * 3072 * 256
    assert p["vocab"] == 2 * 50176 * 3072
    assert round(p["total"] / 1e6) == 5572


def test_parameter_count_of_the_whole_model(cfg):
    assert laguna.param_counts(cfg, whole=True)["total"] / 1e9 == \
        pytest.approx(117.56, abs=0.005)


def test_the_model_built_has_the_counted_parameters(cfg):
    """The MoELM of the file's `rehearse` preset holds what param_counts
    says plus its norm scales."""
    import jax
    import jax.numpy as jnp

    small = {**cfg, **cfg["rehearse"]}
    model = laguna.build(small, small["context"])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    held = sum(a.size for a in jax.tree.leaves(shapes))
    norms = (2 * small["num_hidden_layers"] + 1) * small["hidden_size"]
    assert held == laguna.param_counts(small)["total"] + norms


def test_expert_work(cfg):
    assert laguna.expert_bytes(cfg) == 18_874_368          # 18.87 MB
    assert laguna.expert_flops_per_assignment(cfg) == 6 * 3072 * 1024
    w = laguna.moe_work(cfg, assignments=160, touched=91)
    assert w["flops"] == 160 * 6 * 3072 * 1024
    assert w["bytes"] == 91 * 18_874_368 + 160 * 2 * 3072 * 2
    # K and V of a position of a layer: 8 heads of 128 in bf16, twice
    assert laguna.kv_row_bytes(cfg) == 4096
    # two full layers and three window layers
    assert laguna.paged_attention_bytes(cfg, 10, 4, 64) == \
        (10 * 2 + 4 * 3) * 64 * 4096


def ctx_with(cfg, events, **slice_units):
    trace = tr.Trace({0: events}, [("bench.trace_slice", 0.0, 10.0, "py")])
    return types.SimpleNamespace(
        config=cfg, peaks=PEAKS, chips=1, trace=trace, counters={},
        params={"page_size": 64, "max_slots": 32},
        slice={"t0": 0.0, "t1": 10.0, "seconds": 10.0, **slice_units})


def test_moe_roofline_reads_the_slice(cfg, capsys):
    # 1000 touched experts and few assignments: bound by bytes
    a, t = 2000.0, 1000.0
    least = (t * 18_874_368 + a * 12288) / PEAKS["hbm_bytes"]
    events = [("_moe_gmm.3", 1.0, 2 * least, ""), ("fusion.1", 5.0, 1.0, "")]
    ctx = ctx_with(cfg, events, **{"serving.moe.assignments": a,
                                   "serving.moe.experts_touched": t})
    value = moe_roofline.reduce(ctx, "^_moe_gmm", "serving.moe.assignments",
                                "serving.moe.experts_touched")
    assert value == pytest.approx(50.0)
    assert '"bound": "bytes"' in capsys.readouterr().out
    # many assignments an expert: bound by FLOPs
    ctx.slice["serving.moe.assignments"] = 2e6
    least = 2e6 * 6 * 3072 * 1024 / PEAKS["flops"]
    ctx.trace = tr.Trace({0: [("_moe_gmm.3", 1.0, 4 * least, "")]}, [])
    assert moe_roofline.reduce(
        ctx, "^_moe_gmm", "serving.moe.assignments",
        "serving.moe.experts_touched") == pytest.approx(25.0)


def test_moe_roofline_cannot_pass_100_on_consistent_counters(cfg):
    """A kernel that reads every touched expert once at the peak rate
    and does nothing else reads 100% at most."""
    for a, t in ((160.0, 91.0), (40960.0, 128.0), (5e6, 512.0)):
        need = laguna.moe_work(cfg, a, t)
        fastest = max(need["bytes"] / PEAKS["hbm_bytes"],
                      need["flops"] / PEAKS["flops"])
        ctx = ctx_with(cfg, [("_moe_gmm.1", 0.0, fastest, "")],
                       **{"serving.moe.assignments": a,
                          "serving.moe.experts_touched": t})
        assert moe_roofline.reduce(
            ctx, "^_moe_gmm", "serving.moe.assignments",
            "serving.moe.experts_touched") <= 100.0 + 1e-9


def test_roofline_reducers_read_nothing_without_the_program(cfg):
    """The parent has neither the counters nor the kernels."""
    ctx = ctx_with(cfg, [("fusion.1", 0.0, 1.0, "")])
    args = ("^_moe_gmm", "serving.moe.assignments",
            "serving.moe.experts_touched")
    assert moe_roofline.reduce(ctx, *args) is None
    assert kv_roofline.reduce(ctx, "^_paged_gqa", "serving.batcher.pages.full",
                              "serving.batcher.pages.window") is None
    ctx.slice["serving.moe.assignments"] = 10.0     # counter, no kernel
    assert moe_roofline.reduce(ctx, *args) is None
    ctx.trace = None
    assert moe_roofline.reduce(ctx, *args) is None
    assert serve_mfu.reduce(ctx) is None


def test_attn_roofline_counts_the_prompts_own_pairs(cfg, capsys):
    """Two prompts of 1,000 tokens: 500,500 pairs a full layer, and on a
    window layer 512 * 513 / 2 + 488 * 512 each.  FLOPs bind; a kernel at
    the peak reads 100%, and the counters hold no padding, so a kernel
    that also computes the bucket's padding reads less."""
    tokens, full = 2000.0, 2 * 500_500.0
    window = 2 * (512 * 513 / 2 + 488 * 512)
    need = laguna.prefill_attention_work(cfg, tokens, full, window)
    # two full layers of 48 heads, three window layers of 72, head 128
    assert need["flops"] == 4 * 128 * (2 * 48 * full + 3 * 72 * window)
    assert need["bytes"] == tokens * (
        (2 * 48 + 3 * 72) * 128 * 6 + 5 * 2 * 8 * 128 * 2)
    least = need["flops"] / PEAKS["flops"]
    assert least > need["bytes"] / PEAKS["hbm_bytes"]
    names = {"tokens": "serving.batcher.prefill.tokens",
             "full": "serving.batcher.prefill.attended.full",
             "window": "serving.batcher.prefill.attended.window"}
    ctx = ctx_with(cfg, [("_prefill_attention_pallas.7", 0.0, least, ""),
                         ("_prefill_attention_pallas.9", 2.0, least, "")],
                   **{names["tokens"]: tokens, names["full"]: full,
                      names["window"]: window})
    assert attn_roofline.reduce(ctx, "^_prefill_attention_pallas",
                                **names) == pytest.approx(50.0)
    assert '"bound": "flops"' in capsys.readouterr().out
    ctx.slice.pop(names["full"])         # the parent has no such counter
    assert attn_roofline.reduce(ctx, "^_prefill_attention_pallas",
                                **names) is None


def test_kv_roofline(cfg):
    pages_full, pages_window = 5000.0, 2000.0
    need = (pages_full * 2 + pages_window * 3) * 64 * 4096
    least = need / PEAKS["hbm_bytes"]
    events = [("_paged_gqa_full.1", 0.0, 3 * least, ""),
              ("_paged_gqa_window.2", 5.0, least, ""),
              ("_paged_pallas.9", 8.0, 1.0, "")]
    ctx = ctx_with(cfg, events, **{"serving.batcher.pages.full": pages_full,
                                   "serving.batcher.pages.window":
                                   pages_window})
    assert kv_roofline.reduce(
        ctx, "^_paged_gqa_(window|full)", "serving.batcher.pages.full",
        "serving.batcher.pages.window") == pytest.approx(25.0)


def test_serve_mfu_counts_useful_tokens_only(cfg, capsys):
    ctx = ctx_with(cfg, [])
    ticks, fill = 100.0, 50.0            # half the slots live on average
    ctx.counters = {
        "window_s": 10.0, "serving.moe.assignments": 60000.0,
        "hist.serving.batcher.batch_fill.sum": fill,
        "hist.serving.batcher.batch_fill.count": ticks,
        "serving.batcher.prefill.tokens": 4000.0,
        "serving.batcher.prefill.padded_tokens": 8000.0,
        "hist.serving.batcher.queue_wait.count": 4.0,
        "serving.batcher.attended.full": 1e6,
        "serving.batcher.attended.window": 4e5}
    decoded = fill * 32
    useful, computed = decoded + 4000.0, ticks * 32 + 8000.0
    p = laguna.param_counts(cfg)
    want = (2.0 * (p["attention"] + p["dense"] + p["shared"] + p["router"])
            * useful
            + 2.0 * 3072 * 50176 * (decoded + 4.0)
            + 6 * 3072 * 1024 * 60000.0 * useful / computed
            + 4.0 * 128 * (2 * 48 * 1e6 + 3 * 72 * 4e5))
    assert serve_mfu.reduce(ctx) == pytest.approx(
        100.0 * want / (PEAKS["flops"] * 10.0))
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["useful_tokens"] == useful
    assert line["computed_tokens"] == computed


def test_serve_mfu_cannot_pass_100_at_the_peak(cfg):
    """Tokens computed at exactly the chip's peak, no padding: every part
    counted is work done, so the share is 100% at most."""
    ctx = ctx_with(cfg, [])
    tokens = 1e6
    flops = (laguna.token_flops(cfg) + laguna.head_flops(cfg)
             + 5 * laguna.expert_flops_per_assignment(cfg)) * tokens
    ctx.counters = {
        "window_s": flops / PEAKS["flops"],
        "serving.moe.assignments": 5 * tokens,
        "hist.serving.batcher.batch_fill.sum": tokens / 32,
        "hist.serving.batcher.batch_fill.count": tokens / 32}
    assert serve_mfu.reduce(ctx) == pytest.approx(100.0)


# ---- the controls: `verify` has to refuse a faulty program -----------------
# (control, the readings it must put over their limits, readings that must
# stay sound).  At the rehearsal's float32 a sound run reads rounding
# noise everywhere, so what a control moves is all its own.  `experts_bf16`
# at hidden 64 is ONE 128-deep pass, a bf16 rounding of each product and
# no more: 0.003, which is what the chip's sound bf16 run reads; the test
# holds it to having moved by five orders of magnitude.
CONTROLS = [
    (None, [], ["expert_err", "router_err", "p99_margin", "logit_rms"]),
    ("router_bf16", ["router_err"], ["p99_margin", "expert_err_prefill"]),
    ("window_short", ["p99_margin", "logit_rms", "route_deficit"],
     ["router_err", "expert_err"]),
    ("experts_bf16", [], ["router_err", "route_miss"]),
]


@pytest.mark.parametrize("control,over,sound", CONTROLS,
                         ids=[str(c[0]) for c in CONTROLS])
def test_verify_refuses_each_control(control, over, sound):
    import subprocess
    import sys

    bench = os.path.dirname(CONFIGS)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    env.pop("LAGUNA_CONTROL", None)
    if control:
        env["LAGUNA_CONTROL"] = control
    done = subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), "--workload",
         "laguna-serve-mixed", "--rehearse", "--trace", "0", "--seed", "77"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(bench))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.strip()]
    verdict = next(x for x in lines if x.get("line") == "verify")
    assert verdict["control"] == control
    assert set(over) <= set(verdict["over"])
    assert lines[-1]["correct"] is (not verdict["over"])
    assert verdict["correct"] is (control in (None, "experts_bf16"))
    for name in sound:
        assert verdict[name] < 1e-4, (name, verdict[name])
    if control == "experts_bf16":
        assert 1e-3 < verdict["expert_err"] < 1e-2
    if control == "router_bf16":       # the decode step's router alone
        assert verdict["router_err"] > 1e-3
        assert verdict["route_differ_prefill"] == 0.0
        assert verdict["route_differ"] == verdict["route_differ_decode"]
    # replayed, the programs choose the tokens that were served
    assert verdict["replay_agree_share"] == 1.0
