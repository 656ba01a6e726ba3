"""The Laguna configuration as the benchmark runs it: the model of a
configuration file at its share (`build`), its weights made on the device
leaf by leaf (`init_on_device`), and the work functions the per-layer
metrics divide by: parameters, FLOPs and bytes of the share, all from the
file's published widths.
"""
from __future__ import annotations

BF16 = 2


def sizes(cfg: dict) -> dict:
    """The widths and counts the arithmetic below needs."""
    n = cfg["num_hidden_layers"]
    return {
        "e": cfg["hidden_size"], "d": cfg["head_dim"],
        "hkv": cfg["num_key_value_heads"],
        "heads": list(cfg["num_attention_heads_per_layer"][:n]),
        "windowed": [t == "sliding_attention"
                     for t in cfg["layer_types"][:n]],
        "sparse": [t == "sparse" for t in cfg["mlp_layer_types"][:n]],
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["shared_expert_intermediate_size"],
        "experts": cfg["published"]["num_experts"],
        "held": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
        "vocab": cfg["vocab_size"], "window": cfg["sliding_window"],
    }


def build(cfg: dict, max_len: int):
    """MoELM at the file's share and in the file's `dtype`, `max_len`
    positions."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.moe_lm import MoELM

    return MoELM.from_config(cfg, max_len, jnp.dtype(cfg["dtype"]))


def init_on_device(model, seed: int):
    """{"params": tree} of leaves in the model's dtype (bf16 as served)
    drawn on the device from the seed, ONE LEAF A PROGRAM: the tree never
    exists in float32 (22 GB at the benchmark's share), and no program
    holds more than one expert stack.  Projections are N(0, 1/fan_in),
    the embedding N(0, 1), norm scales 1."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    root = jax.random.PRNGKey(seed)
    dt = model.dtype
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        name = path[-1].key
        if name == "scale":
            fn = lambda key, shape=leaf.shape: jnp.ones(shape, dt)
        else:
            std = 1.0 if name == "embed" else float(leaf.shape[-2]) ** -0.5
            fn = lambda key, shape=leaf.shape, std=std: (
                jax.random.normal(key, shape, dt) * jnp.asarray(std, dt))
        leaves.append(jax.jit(fn)(jax.random.fold_in(root, i)))
    return {"params": jax.tree_util.tree_unflatten(tree, leaves)}


# ---- parameters -------------------------------------------------------------
def attention_params(s: dict, heads: int) -> int:
    e, d, hkv = s["e"], s["d"], s["hkv"]
    return 2 * e * heads * d + 2 * e * hkv * d + e * heads


def expert_params(s: dict, width=None) -> int:
    return 3 * s["e"] * (width or s["expert_width"])


def param_counts(cfg: dict, whole: bool = False) -> dict:
    """Matmul parameters of the share the file describes, or (`whole`) of
    the published model: all layers, all experts, the whole vocabulary."""
    s = sizes(cfg)
    if whole:
        pub = cfg["published"]
        full_cfg = dict(cfg, num_hidden_layers=pub["num_hidden_layers"],
                        num_experts=pub["num_experts"],
                        vocab_size=pub["vocab_size"])
        s = sizes(full_cfg)
    attn = sum(attention_params(s, h) for h in s["heads"])
    n_sparse = sum(s["sparse"])
    dense = (len(s["heads"]) - n_sparse) * expert_params(s, s["dense_width"])
    shared = n_sparse * expert_params(s, s["shared_width"])
    router = n_sparse * s["e"] * s["experts"]
    routed = n_sparse * s["held"] * expert_params(s)
    vocab = 2 * s["vocab"] * s["e"]
    return {"attention": attn, "dense": dense, "shared": shared,
            "router": router, "routed": routed, "vocab": vocab,
            "total": attn + dense + shared + router + routed + vocab}


# ---- work ---------------------------------------------------------------------
def expert_bytes(cfg: dict) -> int:
    """Bytes of one routed expert's three matrices in bf16."""
    return expert_params(sizes(cfg)) * BF16


def expert_flops_per_assignment(cfg: dict) -> int:
    """One token through one routed expert: three matmuls."""
    return 2 * expert_params(sizes(cfg))


def moe_work(cfg: dict, assignments: float, touched: float) -> dict:
    """What the grouped matmul over the experts held NEEDS for
    `assignments` (token, expert) pairs on `touched` (layer, expert)
    pairs: each touched expert's weights read once, each assignment's row
    read and written once, three matmuls an assignment.  A lower bound
    whatever implements it."""
    s = sizes(cfg)
    return {"flops": expert_flops_per_assignment(cfg) * assignments,
            "bytes": (touched * expert_bytes(cfg)
                      + assignments * 2 * s["e"] * BF16)}


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one position of one layer."""
    s = sizes(cfg)
    return 2 * s["hkv"] * s["d"] * BF16


def paged_attention_bytes(cfg: dict, pages_full: float, pages_window: float,
                          page: int) -> float:
    """K/V bytes the decode attention of all layers has to read when
    `pages_full` / `pages_window` pages are in use a layer of each kind."""
    s = sizes(cfg)
    n_win = sum(s["windowed"])
    n_full = len(s["windowed"]) - n_win
    return (pages_full * n_full + pages_window * n_win) * page * kv_row_bytes(cfg)


def token_flops(cfg: dict) -> float:
    """Matmul FLOPs every computed token needs outside the routed experts
    and the head: attention projections and gate, router, shared expert,
    layer 0's dense MLP."""
    p = param_counts(cfg)
    return 2.0 * (p["attention"] + p["dense"] + p["shared"] + p["router"])


def head_flops(cfg: dict) -> float:
    s = sizes(cfg)
    return 2.0 * s["e"] * s["vocab"]


def attention_flops(cfg: dict, attended_full: float,
                    attended_window: float) -> float:
    """QK^T and PV over the K/V rows attended: `attended_*` is the rows a
    query attends summed over queries, for ONE layer of the kind."""
    s = sizes(cfg)
    total = 0.0
    for h, w in zip(s["heads"], s["windowed"]):
        total += 4.0 * h * s["d"] * (attended_window if w else attended_full)
    return total


def prefill_attention_work(cfg: dict, tokens: float, attended_full: float,
                           attended_window: float) -> dict:
    """What the admission attention of `tokens` prompt tokens NEEDS in
    all layers: the FLOPs of the pairs attended, and q, k, v (bf16) read
    and the output (f32) written once a token a layer."""
    s = sizes(cfg)
    per_token = sum(h * s["d"] * (BF16 + 4) + 2 * s["hkv"] * s["d"] * BF16
                    for h in s["heads"])
    return {"flops": attention_flops(cfg, attended_full, attended_window),
            "bytes": tokens * per_token}
