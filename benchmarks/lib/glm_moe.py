"""The GLM-4.7-Flash configuration as the benchmark trains it: the model of
a configuration file at its share (`build`), its weights made on the
device leaf by leaf (`init_on_device`), and the arithmetic the per-layer
metrics divide by: parameters, and the FLOPs and bytes a trained step
NEEDS, all from the file's published widths.

The reducers that read a configuration's own arithmetic
(`reducers/family_roofline.py`, `family_train_mfu.py`) import
`lib.<family>` by the file's `family` and call the functions below by the
name a metric file gives: `moe_train_work`, `flash_train_work`;
`train_flops`.  A multiply-add is 2 FLOPs; causal attention is counted
once; nothing made twice (a checkpointed block's second forward, a
backward kernel's second score pass) is counted.
"""
from __future__ import annotations

import functools

BF16 = 2


def sizes(cfg: dict) -> dict:
    """The widths and counts the arithmetic below needs."""
    return {
        "e": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"],
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "experts": cfg["published"]["n_routed_experts"],
        "held": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
        "mtp": cfg["num_nextn_predict_layers"],
    }


def build(cfg: dict, max_len: int, **kw):
    """GlmMoeLM at the file's share: float32 weights read in the file's
    `dtype`, `max_len` positions."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.glm_moe_lm import GlmMoeLM

    return GlmMoeLM.from_config(cfg, max_len, jnp.dtype(cfg["dtype"]), **kw)


@functools.lru_cache(maxsize=None)
def _leaf_program(kind: str, shape: tuple, dtype, std: float):
    """The jitted program that draws one leaf from a key, made once a
    (kind, shape): a tree is drawn several times a run."""
    import jax
    import jax.numpy as jnp

    if kind == "scale":
        return jax.jit(lambda key: jnp.ones(shape, dtype))
    if kind == "bias":
        return jax.jit(lambda key: jnp.zeros(shape, dtype))
    return jax.jit(lambda key: jax.random.normal(key, shape, dtype)
                   * jnp.asarray(std, dtype))


@functools.lru_cache(maxsize=None)
def _variable_shapes(model):
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))
    return jax.tree_util.tree_flatten_with_path(
        {k: shapes[k] for k in ("params", "controller")})


def init_on_device(model, seed: int) -> dict:
    """{"params", "controller"} drawn on the device from the seed, ONE
    LEAF A PROGRAM, as the model keeps them (float32): projections
    N(0, 1/fan_in), the embedding N(0, 1), norm scales 1, the selection
    biases 0 (the file's `assumed`).  The same seed gives the same
    tree, which is how `verify` gets the initial weights again."""
    import jax

    flat, tree = _variable_shapes(model)
    root = jax.random.PRNGKey(seed)
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        name = path[-1].key
        kind = name if name in ("scale", "bias") else "normal"
        std = 1.0 if name == "embed" else float(leaf.shape[-2:][0]) ** -0.5
        leaves.append(_leaf_program(kind, leaf.shape, leaf.dtype, std)(
            jax.random.fold_in(root, i)))
    return jax.tree_util.tree_unflatten(tree, leaves)


# ---- parameters -------------------------------------------------------------
def attention_matmul_params(s: dict) -> int:
    """One latent attention's five projections."""
    e, h = s["e"], s["heads"]
    return (e * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
            + e * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * e)


def expert_params(s: dict, width=None) -> int:
    return 3 * s["e"] * (width or s["expert_width"])


def param_counts(cfg: dict, whole: bool = False) -> dict:
    """Every number the share's tree holds (`params` and the controller's
    biases), by group, or (`whole`) the published model's: all layers,
    all experts, the whole vocabulary.  `total` counts the MTP module,
    `trunk` does not."""
    if whole:
        cfg = dict(cfg, **cfg["published"])
    s = sizes(cfg)
    e = s["e"]
    sparse_layers = s["layers"] - s["dense_layers"]
    attn_one = attention_matmul_params(s) + s["q_rank"] + s["kv_rank"]
    block_norms = 2 * e
    # outside its routed experts: attention, shared expert, router, bias
    sparse_rest = (attn_one + block_norms
                   + expert_params(s, s["shared"] * s["expert_width"])
                   + e * s["experts"] + s["experts"])
    sparse_one = sparse_rest + s["held"] * expert_params(s)
    dense_one = attn_one + block_norms + expert_params(s, s["dense_width"])
    vocab = 2 * s["vocab"] * e
    trunk = (s["dense_layers"] * dense_one + sparse_layers * sparse_one
             + vocab + e)
    mtp = s["mtp"] * (sparse_one + 2 * e * e + 3 * e)
    return {"attention": attn_one, "dense_layer": dense_one,
            "sparse_layer": sparse_one, "sparse_rest": sparse_rest,
            "expert": expert_params(s), "vocab": vocab, "mtp": mtp,
            "trunk": trunk, "total": trunk + mtp}


# ---- work ---------------------------------------------------------------------
def moe_train_work(cfg: dict, assignments: float, touched: float) -> dict:
    """What the grouped matmul NEEDS, forward and backward, for
    `assignments` (token, expert held) pairs on `touched` (layer, expert)
    pairs: 18 K N FLOPs an assignment (three matmuls forward, their
    products against the transposed weights, and the three dW); each
    touched expert's weights (bf16) read forward and backward and its
    gradient written once; an assignment's row read and its output
    written forward, and their gradients backward."""
    s = sizes(cfg)
    k, n = s["e"], s["expert_width"]
    return {"flops": 18.0 * k * n * assignments,
            "bytes": (touched * 3 * expert_params(s) * BF16
                      + assignments * 4 * k * BF16)}


def flash_train_work(cfg: dict, pairs: float, tokens: float) -> dict:
    """What the flash kernels NEED, forward and backward, for `pairs`
    causal (query, key) pairs a head summed over the attention sublayers
    of `tokens` trained tokens: 7 matmuls of 2 D FLOPs a pair a head
    (QK^T and PV forward; the score again, dP, dV, dK, dQ backward, as
    FlashAttention counts them; a split backward's SECOND score pass is
    not counted); q, k, v, o read or written forward and q, k, v, o, do,
    dq, dk, dv backward, D wide in bf16, a token a head a sublayer."""
    s = sizes(cfg)
    d = s["nope"] + s["rope"]
    sublayers = s["layers"] + s["mtp"]
    return {"flops": 7.0 * 2 * d * s["heads"] * pairs,
            "bytes": 12.0 * sublayers * tokens * s["heads"] * d * BF16}


def train_flops(cfg: dict, n: dict) -> dict:
    """FLOPs the trained tokens of a window NEED, forward and backward,
    by part.  `n`: `tokens` trained, `assignments` they sent to experts
    held (the program's counter), `pairs` causal pairs a head attended
    over all attention sublayers (its counter), `mtp_tokens` targets of
    the MTP head.  6 FLOPs a matmul parameter a token; 6 matmuls of 2 D
    a pair a head (two forward, four backward)."""
    s = sizes(cfg)
    e = s["e"]
    sparse_layers = s["layers"] - s["dense_layers"] + s["mtp"]
    shared = expert_params(s, s["shared"] * s["expert_width"])
    per_token = ((s["layers"] + s["mtp"]) * attention_matmul_params(s)
                 + s["dense_layers"] * expert_params(s, s["dense_width"])
                 + sparse_layers * (shared + e * s["experts"])
                 + s["mtp"] * 2 * e * e)
    d = s["nope"] + s["rope"]
    return {"tokens": 6.0 * per_token * n["tokens"],
            "heads": 6.0 * e * s["vocab"] * (n["tokens"] + n["mtp_tokens"]),
            "experts": 6.0 * expert_params(s) * n["assignments"],
            "attention": 6.0 * 2 * d * s["heads"] * n["pairs"]}
