"""lib/glm_moe.py against counts worked by hand for GLM-4.7-Flash, the
model's tree against them at the published cut, the reducer and work
functions this configuration brings on made-up counters and traces, and
the cell's `verify` against each of its controls in rehearsal."""
import json
import os
import subprocess
import sys
import types

import pytest

from lib import glm_controls, glm_moe
from lib import trace as tr
from reducers import family_roofline, family_train_mfu

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"flops": 197e12, "hbm_bytes": 819e9}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        return json.load(f)


def test_parameter_count_of_the_share(cfg):
    s = glm_moe.sizes(cfg)
    # Wqa 2048x768, Wqb 768x(20x256), Wkva 2048x576, Wkvb 512x(20x448),
    # Wo 5120x2048
    assert glm_moe.attention_matmul_params(s) == (
        1_572_864 + 3_932_160 + 1_179_648 + 4_587_520 + 10_485_760)
    assert glm_moe.expert_params(s) == 3 * 2048 * 1536 == 9_437_184
    p = glm_moe.param_counts(cfg)
    assert p["attention"] == 21_759_232            # with its two norms
    assert p["dense_layer"] == 84_677_888
    assert p["sparse_rest"] == 31_331_648          # router, bias, shared, norms
    assert p["sparse_layer"] == 106_829_120        # 8 of the 64 held
    assert p["vocab"] == 79_298_560
    assert p["mtp"] == 115_223_872
    assert p["total"] == 706_518_848
    assert round(p["total"] * 16 / 1e9, 2) == 11.30
    assert round(p["trunk"] * 16 / 1e9, 2) == 9.46


def test_parameter_count_of_the_whole_model(cfg):
    """47 layers of 64 experts and the whole vocabulary: the published
    30B-A3B."""
    p = glm_moe.param_counts(cfg, whole=True)
    assert p["trunk"] == 29_943_393_920
    assert p["total"] == 30_587_100_096


def test_the_model_built_has_the_counted_parameters(cfg):
    """GlmMoeLM at the published cut, by shapes alone: `params` and the
    controller's biases hold what param_counts says, leaf for leaf."""
    import jax
    import jax.numpy as jnp

    model = glm_moe.build(cfg, 4096)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    held = sum(a.size for k in ("params", "controller")
               for a in jax.tree.leaves(tree[k]))
    assert held == glm_moe.param_counts(cfg)["total"] == 706_518_848
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(tree["params"]))
    biases = jax.tree.leaves(tree["controller"])
    assert len(biases) == 5 and all(b.shape == (64,) for b in biases)
    layer = tree["params"]["layer1"]["moe"]
    assert layer["w1"].shape == (8, 2048, 1536)
    assert layer["router"].shape == (2048, 64)
    assert tree["params"]["mtp"]["eh_proj"].shape == (4096, 2048)
    assert "bias" not in layer      # where no optimizer reaches it


def test_the_file_keeps_every_published_width(cfg):
    """The catalog row's config, key for key, but for `reduced`."""
    row = {"attention_bias": False, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 10240,
           "max_position_embeddings": 202752, "model_type": "glm4_moe_lite",
           "moe_intermediate_size": 1536, "topk_method": "noaux_tc",
           "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
           "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
           "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
           "first_k_dense_replace": 1, "num_hidden_layers": 47,
           "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
           "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
           "rope_scaling": None, "rope_theta": 1000000,
           "tie_word_embeddings": False, "q_lora_rank": 768,
           "kv_lora_rank": 512, "qk_nope_head_dim": 192,
           "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    for key, value in row.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 19360)
    assert cfg["vocab_size"] * 8 == 154880
    assert "8 chips share each layer" in cfg["deployment"]
    assert cfg["family"] == "glm_moe" and "rehearse" in cfg and cfg["assumed"]


def test_work_functions_at_hand_computed_sizes(cfg):
    # one step of the cell: 16,384 tokens, an eighth of 4 x 16,384 x 5
    # assignments held, 5 x 8 experts touched
    w = glm_moe.moe_train_work(cfg, assignments=40960.0, touched=40.0)
    assert w["flops"] == 18 * 2048 * 1536 * 40960
    assert w["bytes"] == 40 * 3 * 9_437_184 * 2 + 40960 * 4 * 2048 * 2
    pairs = 4 * (5 * 4096 * 4097 // 2 + 4095 * 4096 // 2)
    f = glm_moe.flash_train_work(cfg, pairs=float(pairs), tokens=16384.0)
    assert f["flops"] == 7 * 2 * 256 * 20 * pairs
    assert f["bytes"] == 12 * 6 * 16384 * 20 * 256 * 2
    n = {"tokens": 16384.0, "assignments": 40960.0, "pairs": float(pairs),
         "mtp_tokens": 4.0 * 4094}
    parts = glm_moe.train_flops(cfg, n)
    per_token = (6 * 21_757_952 + 62_914_560 + 5 * (9_437_184 + 131_072)
                 + 8_388_608)
    assert parts["tokens"] == 6.0 * per_token * 16384
    assert parts["heads"] == 6.0 * 2048 * 19360 * (16384 + 4 * 4094)
    assert parts["experts"] == 6.0 * 9_437_184 * 40960
    assert parts["attention"] == 6.0 * 2 * 256 * 20 * pairs
    # the issue's estimate: 2.87 GFLOP a token, attention and its
    # projections over half of it, the routed experts a twentieth
    total = sum(parts.values()) / 16384
    assert 2.8e9 < total < 3.0e9
    assert 0.03 < parts["experts"] / sum(parts.values()) < 0.07


def _ctx(cfg, counters=None, slice_=None, trace=None):
    return types.SimpleNamespace(config=cfg, params={}, traffic={}, chips=1,
                                 peaks=PEAKS, counters=counters or {},
                                 slice=slice_ or {}, trace=trace)


def test_train_mfu_reads_the_programs_counters(cfg):
    pairs = 4.0 * (5 * 4096 * 4097 // 2 + 4095 * 4096 // 2)
    c = {"tokens": 16384.0, "window_s": 0.8,
         "training.moe.assignments": 40960.0, "training.attn.pairs": pairs,
         "training.mtp.tokens": 4.0 * 4094}
    got = family_train_mfu.reduce(_ctx(cfg, c))
    want = 100.0 * sum(glm_moe.train_flops(cfg, {
        "tokens": 16384.0, "assignments": 40960.0, "pairs": pairs,
        "mtp_tokens": 4.0 * 4094}).values()) / (197e12 * 0.8)
    assert got == pytest.approx(want) and 20 < got < 40
    # a program without the counters, as the parent's: nothing to read
    assert family_train_mfu.reduce(_ctx(cfg, {"tokens": 1.0,
                                              "window_s": 1.0})) is None
    assert family_train_mfu.reduce(_ctx(cfg, {})) is None


def test_train_rooflines_read_the_slice_and_cannot_pass_100(cfg):
    """The least time for the counted work over the kernels' device time:
    a trace whose kernels ran exactly at the roofline reads 100, a slice
    without the counter or a trace without the kernel reads nothing."""
    work = glm_moe.moe_train_work(cfg, 40960.0, 40.0)
    least = max(work["flops"] / PEAKS["flops"],
                work["bytes"] / PEAKS["hbm_bytes"])
    events = [("_moe_gmm_train_fwd.1", 0.0, least / 2, ""),
              ("_moe_gmm_bwd_dw.3", 1.0, least / 2, ""),
              ("fusion.9", 2.0, 1.0, "")]
    args = dict(pattern="^_moe_gmm", work="moe_train_work",
                counts={"assignments": "training.moe.assignments",
                        "touched": "training.moe.experts_touched"},
                require="assignments")
    slice_ = {"training.moe.assignments": 40960.0,
              "training.moe.experts_touched": 40.0}
    trace = tr.Trace({0: events}, [])
    assert family_roofline.reduce(_ctx(cfg, slice_=slice_, trace=trace),
                                  **args) == pytest.approx(100.0)
    assert family_roofline.reduce(_ctx(cfg, slice_={}, trace=trace),
                                  **args) is None
    assert family_roofline.reduce(
        _ctx(cfg, slice_=slice_, trace=tr.Trace({0: events[2:]}, [])),
        **args) is None


# ---- the controls: `verify` has to refuse a faulty program -----------------
# (control, readings it must put over their limits).  The rehearsal computes
# in float32, where a sound run reads rounding noise everywhere.
CONTROLS = [
    ("mtp_off", ["grad.eh_proj"]),
    ("no_renorm", ["ce_main_abs", "grad.experts"]),
    ("bias_weighs", ["grad.router", "grad.experts"]),
    ("scores_softmax", ["ce_main_abs", "grad.router"]),
    ("experts_bf16", ["kernel.drows"]),
    ("dw_bf16_accum", ["kernel.dw"]),
    ("state_frozen", ["update.worst_leaf"] + [
        f"update.{g}" for g in ("attention", "dense", "router", "shared",
                                "experts", "eh_proj", "embedding", "head",
                                "norms")]),
]


def test_every_control_is_tried():
    assert sorted(c for c, _ in CONTROLS) == sorted(glm_controls.CONTROLS)


@pytest.mark.parametrize("control,over", CONTROLS,
                         ids=[c for c, _ in CONTROLS])
def test_verify_refuses_each_control(control, over):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "GLM_CONTROL": control}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "glm-train-moe", "--rehearse", "--trace", "0", "--seed", "77"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(BENCH))
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.strip()]
    verdict = next(x for x in lines if x.get("line") == "verify")
    assert verdict["control"] == control
    assert set(over) <= set(verdict["over_limit"]), verdict["over_limit"]
    assert verdict["correct"] is False and lines[-1]["correct"] is False
    assert verdict["finite"] and not verdict["epoch_compiled_anew"]
    if control == "state_frozen":
        # a state left as it was reads 1, and no bias moved
        assert all(abs(verdict["readings"][k] - 1.0) < 1e-3 for k in over)
        assert not verdict["bias_ok"] and not verdict["bias_after_ok"]
    else:
        assert verdict["bias_ok"] and verdict["bias_after_ok"]
    if control in ("experts_bf16", "dw_bf16_accum", "mtp_off"):
        # a fault of precision, or of the loss's weights, moves no choice
        assert verdict["route_misses"] == 0
        assert not {"ce_main_abs", "step1.ce_main_abs"} & set(
            verdict["over_limit"])
    if control == "dw_bf16_accum":      # ... and nothing but the dW
        assert verdict["over_limit"] == ["kernel.dw"]
