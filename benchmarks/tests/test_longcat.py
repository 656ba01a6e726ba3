"""lib/longcat.py against counts worked by hand for LongCat-Flash-Chat,
the reducers this configuration brings on made-up traces and counters
(what they read, that they read nothing where the program has no such
counter or kernel, that consistent counters cannot read over 100%), and
the cell's `verify` against each of its controls, and the stratified
draw of the cell's lengths (lib/loadgen_strata.py)."""
import json
import os
import types

import pytest

from lib import loadgen, loadgen_strata, longcat
from lib import trace as tr
from reducers import counter_share, family_roofline, family_serve_mfu

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
PEAKS = {"flops": 197e12, "hbm_bytes": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(CONFIGS, "longcat-flash-chat.json")) as f:
        return json.load(f)


def test_parameter_count_of_the_share(cfg):
    s = longcat.sizes(cfg)
    # Wqa 6144x1536, Wqb 1536x(64x192), Wkva 6144x576, Wkvb 512x(64x256),
    # Wo 8192x6144
    assert longcat.attention_params(s) == (
        9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648)
    assert longcat.expert_params(s) == 3 * 6144 * 2048 == 37_748_736
    p = longcat.param_counts(cfg)
    assert p["attention"] == 8 * 90_570_752
    assert p["dense"] == 8 * 3 * 6144 * 12288
    assert p["router"] == 4 * 6144 * 768
    assert p["routed"] == 4 * 16 * 37_748_736
    assert p["vocab"] == 2 * 16384 * 6144
    # the issue's count: 5,173M +- 1M, 10.35 GB in bf16
    assert abs(p["total"] / 1e6 - 5173) < 1
    assert round(p["total"] * 2 / 1e9, 2) == 10.35


def test_parameter_count_of_the_whole_model(cfg):
    """28 blocks of 512 experts and the whole vocabulary: the published
    560B."""
    assert longcat.param_counts(cfg, whole=True)["total"] / 1e9 == \
        pytest.approx(560.5, abs=1.0)


def test_the_file_keeps_every_published_width(cfg):
    """The catalog row's config, key for key, but for `reduced`."""
    row = {"attention_bias": False, "vocab_size": 131072,
           "hidden_size": 6144, "ffn_hidden_size": 12288,
           "expert_ffn_hidden_size": 2048, "num_layers": 28,
           "num_attention_heads": 64, "kv_lora_rank": 512,
           "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
           "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
           "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
           "n_routed_experts": 512, "max_position_embeddings": 131072,
           "rms_norm_eps": 1e-05, "rope_theta": 10000000,
           "attention_method": "MLA", "zero_expert_num": 256,
           "zero_expert_type": "identity", "moe_topk": 12}
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_layers",
                                      "vocab_size"]
    for key, value in row.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 16, 16384)


def test_the_model_built_has_the_counted_parameters(cfg):
    """The LongCatLM of the file's `rehearse` preset holds what
    param_counts says plus its norm scales and selection biases."""
    import jax
    import jax.numpy as jnp

    small = {**cfg, **cfg["rehearse"]}
    model = longcat.build(small, small["context"])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    held = sum(a.size for a in jax.tree.leaves(shapes))
    n = small["num_layers"]
    norms = (4 * n + 1) * small["hidden_size"] + 2 * n * (
        small["q_lora_rank"] + small["kv_lora_rank"])
    biases = n * (small["published"]["n_routed_experts"]
                  + small["zero_expert_num"])
    assert held == longcat.param_counts(small)["total"] + norms + biases


def test_work_of_the_new_kernels(cfg):
    assert longcat.expert_bytes(cfg) == 75_497_472
    w = longcat.moe_work(cfg, assignments=100, touched=16)
    assert w["flops"] == 100 * 6 * 6144 * 2048
    assert w["bytes"] == 16 * 75_497_472 + 100 * 2 * 6144 * 2
    assert longcat.latent_row_bytes(cfg) == 1152
    # 64 heads x 2 x (576 + 512) FLOPs a position, 1,152 B a row, 8
    # sublayers: 121 FLOPs a byte, under the v5e's ridge of 240
    d = longcat.latent_decode_work(cfg, attended=1000.0, pages=20.0, page=64)
    assert d["flops"] == 8 * 64 * 2 * (576 + 512) * 1000
    assert d["bytes"] == 8 * 20 * 64 * 1152
    assert 64 * 2 * (576 + 512) / 1152 == pytest.approx(120.9, abs=0.1)
    a = longcat.prefill_attention_work(cfg, tokens=10.0, attended=55.0)
    assert a["flops"] == 8 * 64 * 2 * (192 + 128) * 55
    assert a["bytes"] == 8 * 10 * 64 * (2 * 192 * 2 + 128 * 2 + 128 * 4)


def ctx_with(cfg, events, **slice_units):
    trace = tr.Trace({0: events}, [("bench.trace_slice", 0.0, 10.0, "py")])
    return types.SimpleNamespace(
        config=cfg, peaks=PEAKS, chips=1, trace=trace, counters={},
        params={"page_size": 64, "max_slots": 32},
        slice={"t0": 0.0, "t1": 10.0, "seconds": 10.0, **slice_units})


METRICS = os.path.join(os.path.dirname(CONFIGS), "metrics")


def _args(metric):
    """The metric file's own arguments: what the harness hands the
    reducer."""
    with open(os.path.join(METRICS, metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == "family_roofline"
    return spec["args"]


DECODE, MOE, ATTN = (_args(m) for m in (
    "mla_decode_roofline", "moe_roofline_family", "mla_prefill_roofline"))
ATTENDED, PREFILLED, PAGES = (DECODE["counts"][k] for k in (
    "attended", "prefill_attended", "pages"))


def test_latent_decode_roofline_takes_the_admissions_pairs_off(cfg, capsys):
    """Whole pages of live rows bind by bytes: 1,152 B against 0.7 ns of
    FLOPs a position."""
    pages = 5000.0
    attended, prefilled = pages * 64 + 7e6, 7e6
    least = 8 * pages * 64 * 1152 / PEAKS["hbm_bytes"]
    ctx = ctx_with(cfg, [("_paged_mla.4", 0.0, 4 * least, ""),
                         ("_paged_gqa_full.1", 6.0, 1.0, "")],
                   **{ATTENDED: attended, PREFILLED: prefilled,
                      PAGES: pages})
    assert family_roofline.reduce(ctx, **DECODE) == pytest.approx(25.0)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["bound"] == "bytes" and line["work"] == "latent_decode_work"
    assert longcat.latent_decode_work(
        cfg, attended, pages, 64, prefilled)["flops"] == \
        8 * 64 * 2 * (576 + 512) * pages * 64


def test_new_rooflines_cannot_pass_100_on_consistent_counters(cfg):
    for pages, attended in ((100.0, 100 * 64.0), (3500.0, 3500 * 40.0)):
        need = longcat.latent_decode_work(cfg, attended, pages, 64)
        fastest = max(need["flops"] / PEAKS["flops"],
                      need["bytes"] / PEAKS["hbm_bytes"])
        ctx = ctx_with(cfg, [("_paged_mla.1", 0.0, fastest, "")],
                       **{ATTENDED: attended, PAGES: pages})
        assert family_roofline.reduce(ctx, **DECODE) <= 100 + 1e-9
    need = longcat.moe_work(cfg, 4000.0, 64.0)
    fastest = max(need["flops"] / PEAKS["flops"],
                  need["bytes"] / PEAKS["hbm_bytes"])
    ctx = ctx_with(cfg, [("_moe_gmm_prefill.1", 0.0, fastest, "")],
                   **{"serving.moe.assignments": 4000.0,
                      "serving.moe.experts_touched": 64.0})
    assert family_roofline.reduce(ctx, **MOE) == pytest.approx(100.0)


def test_prefill_roofline_counts_the_prompts_own_pairs(cfg):
    """Two prompts of 2,000 tokens: FLOPs bind, and a kernel at the peak
    for half the time reads 50%."""
    tokens, pairs = 4000.0, 2 * 2000 * 2001 / 2
    need = longcat.prefill_attention_work(cfg, tokens, pairs)
    least = need["flops"] / PEAKS["flops"]
    assert least > need["bytes"] / PEAKS["hbm_bytes"]
    names = ATTN["counts"]
    ctx = ctx_with(cfg, [("_prefill_attention_pallas.7", 0.0, 2 * least, "")],
                   **{names["tokens"]: tokens, names["attended"]: pairs})
    assert family_roofline.reduce(ctx, **ATTN) == pytest.approx(50.0)


def test_new_reducers_read_nothing_without_the_program(cfg):
    """The parent has neither the counters nor the kernel: each reducer
    returns None and does not raise."""
    ctx = ctx_with(cfg, [("fusion.1", 0.0, 1.0, "")])
    for args in (DECODE, MOE, ATTN):
        assert family_roofline.reduce(ctx, **args) is None
    assert family_serve_mfu.reduce(ctx) is None
    assert counter_share.reduce(ctx, "serving.moe.zero_assignments",
                                ["serving.moe.live_assignments"]) is None
    for args in (DECODE, MOE, ATTN):     # the counter, and no kernel
        ctx.slice[args["counts"][args["require"]]] = 10.0
        assert family_roofline.reduce(ctx, **args) is None
    ctx.trace = None
    assert family_roofline.reduce(ctx, **DECODE) is None


def test_zero_share_of_the_live_assignments(cfg):
    ctx = ctx_with(cfg, [])
    ctx.counters = {"serving.moe.zero_assignments": 300.0,
                    "serving.moe.live_assignments": 100.0}
    assert counter_share.reduce(ctx, "serving.moe.zero_assignments",
                                ["serving.moe.live_assignments"]) == 75.0


def test_serve_mfu_counts_what_the_useful_tokens_need(cfg, capsys):
    ctx = ctx_with(cfg, [])
    fill = 50.0                          # half the slots live on average
    ctx.counters = {
        "window_s": 10.0, "serving.moe.live_assignments": 9000.0,
        "serving.moe.zero_assignments": 120000.0,
        "hist.serving.batcher.batch_fill.sum": fill,
        "hist.serving.batcher.batch_fill.count": 100.0,
        "serving.batcher.prefill.tokens": 4000.0,
        "hist.serving.batcher.queue_wait.count": 4.0,
        "serving.batcher.attended.latent": 9e6,
        "serving.batcher.prefill.attended.latent": 4e6}
    decoded = fill * 32
    p = longcat.param_counts(cfg)
    want = (2.0 * (p["attention"] + p["dense"] + p["router"])
            * (decoded + 4000.0)
            + 2.0 * 6144 * 16384 * (decoded + 4.0)
            + 6 * 6144 * 2048 * 9000.0
            + 8 * 64 * (2 * (192 + 128) * 4e6 + 2 * (576 + 512) * 5e6))
    assert family_serve_mfu.reduce(ctx) == pytest.approx(
        100.0 * want / (PEAKS["flops"] * 10.0))
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["useful_tokens"] == decoded + 4000.0
    # identity experts count nothing
    ctx.counters["serving.moe.zero_assignments"] = 0.0
    assert family_serve_mfu.reduce(ctx) == pytest.approx(
        100.0 * want / (PEAKS["flops"] * 10.0))


# ---- the controls: `verify` has to refuse a faulty program -----------------
# (control, the readings it must put over their limits, readings that must
# stay sound).  At the rehearsal's float32 a sound run reads rounding
# noise everywhere, so what a control moves is all its own.
CONTROLS = [
    (None, [], ["expert_err_prefill", "expert_err_decode",
                "ffn_err_prefill", "ffn_err_decode", "router_err",
                "p99_margin", "logit_rms", "absorb_err",
                "zero_share_diff"]),
    ("zero_off", ["expert_err_decode", "logit_rms"],
     ["router_err", "absorb_err", "expert_err_prefill"]),
    ("kv_scale_off", ["logit_rms"], ["router_err", "expert_err_prefill",
                                     "expert_err_decode", "absorb_err"]),
    ("absorb_bf16", ["absorb_err"], ["router_err", "expert_err_prefill",
                                     "expert_err_decode", "p99_margin"]),
    # at hidden 64 ONE 128-deep pass: a bf16 rounding of each product and
    # no more, 0.003 of the FFN part, which is what a sound bf16 run reads
    # on the chip; off the chip the fault is in every call
    ("experts_bf16", ["expert_err_decode"], ["router_err", "absorb_err"]),
    # the same in the admission programs only: the decode rows stay sound
    ("prefill_experts_bf16", ["expert_err_prefill"],
     ["router_err", "absorb_err", "ffn_err_decode", "expert_err_decode"]),
]


@pytest.mark.parametrize("control,over,sound", CONTROLS,
                         ids=[str(c[0]) for c in CONTROLS])
def test_verify_refuses_each_control(control, over, sound):
    import subprocess
    import sys

    bench = os.path.dirname(CONFIGS)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    env.pop("LONGCAT_CONTROL", None)
    if control:
        env["LONGCAT_CONTROL"] = control
    done = subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), "--workload",
         "longcat-serve-long", "--rehearse", "--trace", "0", "--seed", "77"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(bench))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.strip()]
    verdict = next(x for x in lines if x.get("line") == "verify")
    assert verdict["control"] == control
    assert set(over) <= set(verdict["over"])
    assert lines[-1]["correct"] is (not verdict["over"])
    assert verdict["correct"] is (control is None)
    for name in sound:
        assert verdict[name] < 1e-4, (name, verdict[name])
    # a third of the assignments fall on identity experts
    assert 0.2 < verdict["zero_share"] < 0.45
    if control == "zero_off":            # the decode step's sum alone
        assert verdict["expert_err_decode"] > 0.3
    if control == "absorb_bf16":
        assert verdict["absorb_err"] > 1e-3
    # ... which hides in the whole sum (at these sizes half of it is the
    # identity part's, on the chip twenty-nine thirtieths)
    if control == "experts_bf16":
        assert 1e-3 < verdict["ffn_err_decode"] < 1e-2
        assert verdict["expert_err_decode"] < verdict["ffn_err_decode"]
    if control == "prefill_experts_bf16":
        assert 1e-3 < verdict["ffn_err_prefill"] < 1e-2
        assert verdict["expert_err_prefill"] < verdict["ffn_err_prefill"]
    if control != "kv_scale_off":
        # replayed, the programs choose the tokens that were served
        assert verdict["replay_agree_share"] == 1.0


# ---------------------------------------------------------------------------
# the cell's traffic: lengths in strata
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def long_traffic():
    with open(os.path.join(os.path.dirname(CONFIGS), "traffic",
                           "closed-32-long-8k.json")) as f:
        return json.load(f)


def _stream(traffic, seed, n=64):
    s = loadgen_strata.Strata(seed, traffic)
    return [s.draw_request(None, traffic, 16384) for _ in range(n)]


def test_strata_stream_repeats_for_a_seed_and_differs_across_seeds(
        long_traffic):
    assert _stream(long_traffic, 2_400_000_011) == \
        _stream(long_traffic, 2_400_000_011)
    a, b = _stream(long_traffic, 7), _stream(long_traffic, 8)
    assert [(len(p), n) for p, n in a] != [(len(p), n) for p, n in b]
    assert a[0][0][:8] != b[0][0][:8]
    assert all(0 <= tok < 16384 for p, _ in a[:4] for tok in p)


@pytest.mark.parametrize("which", ["prompt_len", "output_len"])
def test_every_block_holds_one_length_of_each_stratum(long_traffic, which):
    """Whatever the seed: a block's lengths, sorted, lie one in each B-th
    of the file's lognormal (clipped to its limits)."""
    d, block = long_traffic[which], long_traffic["lengths"]["block"]
    edges = [loadgen_strata.stratum_length(i / block, d["median"],
                                           d["sigma"], d["min"], d["max"])
             for i in range(block + 1)]
    assert edges[0] == d["min"] and edges[-1] == d["max"]
    for seed in (1, 2_400_000_011):
        s = loadgen_strata.Strata(seed, long_traffic)
        orders = set()
        for b in range(6):
            got = [pair[which == "output_len"] for pair in s.lengths(b)]
            orders.add(tuple(got))
            for lo, n, hi in zip(edges, sorted(got), edges[1:]):
                assert lo <= n <= hi
        assert len(orders) == 6              # shuffled anew in every block


def test_strata_are_the_file_s_lognormals(long_traffic):
    """Over many blocks the lengths have the medians the file gives and
    keep to its limits; prompt and reply orders are shuffled apart."""
    s = loadgen_strata.Strata(5, long_traffic)
    pairs = [p for b in range(250) for p in s.lengths(b)]
    for k, d in enumerate((long_traffic["prompt_len"],
                           long_traffic["output_len"])):
        got = sorted(p[k] for p in pairs)
        assert d["min"] <= got[0] and got[-1] <= d["max"]
        assert abs(got[len(got) // 2] / d["median"] - 1) < 0.02
    import numpy as np

    r = np.corrcoef(np.log([p[0] for p in pairs]),
                    np.log([p[1] for p in pairs]))[0, 1]
    assert abs(r) < 0.1


def test_every_seed_offers_the_same_work(long_traffic):
    """The point of the strata: a window's worth of requests (64 here)
    carries nearly the same prompt and reply tokens whatever the seed,
    where independent draws of the same lognormals differ widely."""
    import numpy as np

    def totals(draws):
        return np.array([[sum(len(p) for p, _ in d), sum(n for _, n in d)]
                         for d in draws], float)

    seeds = range(100, 130)
    strata = totals([_stream(long_traffic, s) for s in seeds])
    iid = totals([[loadgen.draw_request(loadgen.client_rng(s, c),
                                        long_traffic, 16384)
                   for c in range(32) for _ in range(2)] for s in seeds])
    cv = lambda t: t.std(0) / t.mean(0)  # noqa: E731
    assert (cv(strata) < 0.035).all(), cv(strata)
    assert (cv(iid) > 2 * cv(strata)).all(), cv(iid)
    assert np.allclose(strata.mean(0), iid.mean(0), rtol=0.04)


def test_concurrent_clients_share_one_stream(long_traffic):
    """Requests are numbered as they are sent: 8 threads drawing at once
    get the stream's first requests once each."""
    import threading

    s = loadgen_strata.Strata(9, long_traffic)
    got, lock = [], threading.Lock()

    def client():
        for _ in range(8):
            r = s.draw_request(None, long_traffic, 16384)
            with lock:
                got.append((len(r[0]), r[1]))

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    want = [(len(p), n) for p, n in _stream(long_traffic, 9)]
    assert sorted(got) == sorted(want)


def test_the_generator_runs_loadgen_s_clients(long_traffic):
    """As a process, on a spec whose window is already over: no request
    is sent, and the result has loadgen.py's keys."""
    import subprocess
    import sys
    import time

    now = time.monotonic()
    spec = {"host": "127.0.0.1", "port": 9, "path": "/generate", "seed": 3,
            "traffic": {**long_traffic, **long_traffic["rehearse"]},
            "vocab": 16384, "start_at": now - 3.0, "window_start": now - 2.0,
            "window_end": now - 1.0, "timeout_s": 1.0, "sample": 2}
    done = subprocess.run(
        [sys.executable, loadgen_strata.__file__], input=json.dumps(spec),
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout)
    assert out["attempted"] == 0 and out["hung_clients"] == []
    assert {"tokens_in_window", "ttft_ms", "itl_ms", "sample"} <= set(out)
