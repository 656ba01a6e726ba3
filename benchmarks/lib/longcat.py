"""The LongCat-Flash configuration as the benchmark runs it: the model of
a configuration file at its share (`build`), its weights made on the
device leaf by leaf (`init_on_device`), and the work functions the
per-layer metrics divide by: parameters, FLOPs and bytes of the share, all
from the file's published widths.

The reducers that read a configuration's own arithmetic
(`reducers/family_roofline.py`, `family_serve_mfu.py`) import
`lib.<family>` by the file's `family` and call the functions below by the
name a metric file gives: `moe_work`, `latent_decode_work`,
`prefill_attention_work`; `useful_flops`.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def sizes(cfg: dict) -> dict:
    """The widths and counts the arithmetic below needs."""
    return {
        "e": cfg["hidden_size"], "blocks": cfg["num_layers"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"],
        "dense_width": cfg["ffn_hidden_size"],
        "expert_width": cfg["expert_ffn_hidden_size"],
        "experts": cfg["published"]["n_routed_experts"],
        "zero": cfg["zero_expert_num"], "held": cfg["n_routed_experts"],
        "top_k": cfg["moe_topk"], "vocab": cfg["vocab_size"],
    }


def build(cfg: dict, max_len: int):
    """LongCatLM at the file's share and in the file's `dtype`, `max_len`
    positions."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.longcat_lm import LongCatLM

    return LongCatLM.from_config(cfg, max_len, jnp.dtype(cfg["dtype"]))


def init_on_device(model, seed: int):
    """{"params": tree} drawn on the device from the seed, ONE LEAF A
    PROGRAM, in the model's dtype (bf16 as served): the tree never exists
    in float32.  Projections are N(0, 1/fan_in) of a unit-variance input
    (so Wqb and Wkvb, whose inputs `mla_scale_q_lora` and
    `mla_scale_kv_lora` have multiplied by sqrt(E / rank), are drawn that
    factor narrower: q, k and v of unit variance, attention scores of
    unit variance and not of 30), the embedding N(0, 1), norm scales 1,
    and the routed layers' selection bias (float32, as the model keeps
    it) uniform in +-1/q_lora_rank (the file's `assumed`)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    root = jax.random.PRNGKey(seed)
    dt = model.dtype
    span = 1.0 / model.q_lora_rank
    # the gain of each scaled projection's input
    gains = {"wqb": (model.embed_dim / model.q_lora_rank) ** 0.5,
             "wkvb": (model.embed_dim / model.kv_lora_rank) ** 0.5}
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        name = path[-1].key
        if name == "scale":
            fn = lambda key, shape=leaf.shape: jnp.ones(shape, dt)
        elif name == "bias":
            fn = lambda key, shape=leaf.shape: jax.random.uniform(
                key, shape, jnp.float32, -span, span)
        else:
            std = 1.0 if name == "embed" else float(leaf.shape[-2]) ** -0.5
            std /= gains.get(name, 1.0)
            fn = lambda key, shape=leaf.shape, std=std: (
                jax.random.normal(key, shape, dt) * jnp.asarray(std, dt))
        leaves.append(jax.jit(fn)(jax.random.fold_in(root, i)))
    return {"params": jax.tree_util.tree_unflatten(tree, leaves)}


# ---- parameters -------------------------------------------------------------
def attention_params(s: dict) -> int:
    """One latent attention: Wqa, Wqb, Wkva, Wkvb, Wo."""
    e, h = s["e"], s["heads"]
    return (e * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
            + e * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * e)


def expert_params(s: dict, width=None) -> int:
    return 3 * s["e"] * (width or s["expert_width"])


def param_counts(cfg: dict, whole: bool = False) -> dict:
    """Matmul parameters of the share the file describes, or (`whole`) of
    the published model: all blocks, all experts, the whole vocabulary."""
    if whole:
        cfg = dict(cfg, **cfg["published"])
    s = sizes(cfg)
    n = s["blocks"]
    attn = 2 * n * attention_params(s)
    dense = 2 * n * expert_params(s, s["dense_width"])
    router = n * s["e"] * (s["experts"] + s["zero"])
    routed = n * s["held"] * expert_params(s)
    vocab = 2 * s["vocab"] * s["e"]
    return {"attention": attn, "dense": dense, "router": router,
            "routed": routed, "vocab": vocab,
            "total": attn + dense + router + routed + vocab}


# ---- work ---------------------------------------------------------------------
def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices in bf16."""
    return expert_params(sizes(cfg)) * BF16


def expert_flops_per_assignment(cfg: dict) -> int:
    """One token through one routed FFN expert: three matmuls."""
    return 2 * expert_params(sizes(cfg))


def moe_work(cfg: dict, assignments: float, touched: float) -> dict:
    """What the grouped matmul over the experts held NEEDS for
    `assignments` (token, FFN expert) pairs on `touched` (block, expert)
    pairs: each touched expert's weights read once, each assignment's row
    read and written once, three matmuls an assignment.  Assignments on
    identity experts are not among them: they cost no matmul."""
    s = sizes(cfg)
    return {"flops": expert_flops_per_assignment(cfg) * assignments,
            "bytes": (touched * expert_bytes(cfg)
                      + assignments * 2 * s["e"] * BF16)}


def latent_row_bytes(cfg: dict) -> int:
    """The cached values of one position of one sublayer: the latent and
    the rope key, bf16 (the 576 that are needed; the pool pads its rows
    to 640)."""
    s = sizes(cfg)
    return (s["kv_rank"] + s["rope"]) * BF16


def latent_decode_work(cfg: dict, attended: float, pages: float,
                       page: int, prefill_attended: float = 0.0) -> dict:
    """What the absorbed decode attention of all cached sublayers NEEDS
    when the decode steps' queries attend `attended` -
    `prefill_attended` cached positions (the batcher counts the
    admissions' pairs into `attended` too) and `pages` pages are in use,
    all for ONE sublayer: every head's scores against a row's kv_rank +
    rope values and its values against the row's kv_rank, two FLOPs a
    product; every live page's rows read once.  The same work whatever
    implements it."""
    s = sizes(cfg)
    sublayers = 2 * s["blocks"]
    per_pos = s["heads"] * 2 * (s["kv_rank"] + s["rope"] + s["kv_rank"])
    attended = max(attended - prefill_attended, 0.0)
    return {"flops": sublayers * per_pos * attended,
            "bytes": sublayers * pages * page * latent_row_bytes(cfg)}


def prefill_attention_work(cfg: dict, tokens: float, attended: float) -> dict:
    """What the admission's expanded attention of `tokens` prompt tokens
    NEEDS in all sublayers: QK^T at nope + rope and PV at v over the
    `attended` (query, key) pairs of ONE sublayer, every head, unpadded;
    and q, k, v (bf16) read and the output (f32) written once a token."""
    s = sizes(cfg)
    sublayers, h = 2 * s["blocks"], s["heads"]
    qk, v = s["nope"] + s["rope"], s["v"]
    return {"flops": sublayers * h * 2.0 * (qk + v) * attended,
            "bytes": sublayers * tokens * h * (2 * qk * BF16 + v * BF16
                                               + v * F32)}


def token_flops(cfg: dict) -> float:
    """Matmul FLOPs every computed token needs outside the routed experts
    and the head: the latent attentions' projections, the dense MLPs, the
    router."""
    p = param_counts(cfg)
    return 2.0 * (p["attention"] + p["dense"] + p["router"])


def head_flops(cfg: dict) -> float:
    s = sizes(cfg)
    return 2.0 * s["e"] * s["vocab"]


def useful_flops(cfg: dict, n: dict) -> dict:
    """FLOPs the useful tokens of a window NEED, by part.  `n`: `useful`
    tokens (prompt tokens prefilled + tokens decoded), `head_rows` (one a
    decoded token and one an admitted prompt), `live_assignments` (the
    useful tokens' assignments on FFN experts held; identity experts,
    padding and idle slots count nothing), `attended` {kind: positions
    attended in one sublayer of the kind}, of which `prefill_attended`
    {kind: ...} by the admissions' expanded path (the rest by the decode
    steps' absorbed path, which costs more a pair)."""
    s = sizes(cfg)
    sublayers, h = 2 * s["blocks"], s["heads"]
    pre = n["prefill_attended"].get("latent", 0.0)
    dec = n["attended"].get("latent", 0.0) - pre
    expanded = 2.0 * (s["nope"] + s["rope"] + s["v"])
    absorbed = 2.0 * (2 * s["kv_rank"] + s["rope"])
    return {"tokens": token_flops(cfg) * n["useful"],
            "head": head_flops(cfg) * n["head_rows"],
            "experts": (expert_flops_per_assignment(cfg)
                        * n["live_assignments"]),
            "attention": sublayers * h * (expanded * pre + absorbed * dec)}
