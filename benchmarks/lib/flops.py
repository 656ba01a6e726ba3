"""Parameters, operations and bytes computed from a configuration file's
shapes: what the ALGORITHM needs, not what a compiler emitted.

A multiply-add is 2 FLOPs.  Causal attention is counted once (the masked
half is not work), recomputation (remat, a flash backward's second score
pass) is not counted, embedding gathers are not matmuls.  The arithmetic
of tools/roofline.py, as functions of the configuration.
"""
from __future__ import annotations


def lm_sizes(cfg: dict) -> dict:
    """GPT-2 style keys (`n_embd`, `n_layer`, `n_head`, `n_positions`,
    `vocab_size`); the vocabulary served is the padded one where the
    configuration assumes a padding."""
    vocab = cfg.get("assumed", {}).get("padded_vocab_size", cfg["vocab_size"])
    return {"e": cfg["n_embd"], "layers": cfg["n_layer"],
            "heads": cfg["n_head"], "head_dim": cfg["n_embd"] // cfg["n_head"],
            "positions": cfg["n_positions"], "vocab": vocab,
            "mlp": cfg.get("n_inner") or 4 * cfg["n_embd"]}


def lm_param_counts(cfg: dict) -> dict:
    """TransformerLM's tree: per block two LayerNorms (2e each), qkv
    (3e*e) and proj (e*e) without bias, mlp_in (e*m + m) and mlp_out
    (m*e + e) with bias; learned positions; an untied head without bias."""
    s = lm_sizes(cfg)
    e, m = s["e"], s["mlp"]
    block_matmul = 4 * e * e + 2 * e * m
    block = block_matmul + 4 * e + m + e
    head = e * s["vocab"]
    total = (s["layers"] * block + s["vocab"] * e + s["positions"] * e
             + 2 * e + head)
    return {"block": block, "tok_embed": s["vocab"] * e,
            "pos_embed": s["positions"] * e, "ln_f": 2 * e, "head": head,
            "matmul": s["layers"] * block_matmul + head, "total": total}


def lm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one token in a sequence of `seq`: 6 FLOPs a
    matmul parameter, plus attention's two score-sized matmuls forward and
    four backward at half the square (causal): 6 * seq * e per layer."""
    s = lm_sizes(cfg)
    return (6.0 * lm_param_counts(cfg)["matmul"]
            + 6.0 * seq * s["e"] * s["layers"])


def flash_attention_train(cfg: dict, batch: int, seq: int) -> dict:
    """One optimizer step's attention kernels over all layers (forward,
    dK/dV, dQ): FLOPs as FlashAttention counts them (2 matmuls forward, 5
    backward: the score recompute is the algorithm's, a SECOND recompute
    in a split backward is not), causal; bytes at the compute dtype: q, k,
    v, o forward, and q, k, v, o, do in, dq, dk, dv out backward."""
    s = lm_sizes(cfg)
    one = 2.0 * batch * s["heads"] * seq * seq * s["head_dim"] / 2.0
    tensor = 2.0 * batch * seq * s["e"]          # bf16 bytes of [b, s, h, d]
    return {"flops": s["layers"] * 7 * one,
            "bytes": s["layers"] * (4 + 8) * tensor}


def decode_tick_bytes(cfg: dict, live_tokens: int, kv_bytes: int = 2,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode tick has to read: every matmul weight once and the
    live K and V rows of every layer."""
    s = lm_sizes(cfg)
    return (weight_bytes * lm_param_counts(cfg)["matmul"]
            + 2.0 * kv_bytes * s["layers"] * live_tokens * s["e"])


def _resnet_convs(cfg: dict):
    """(k, c_in, c_out, out_side) of every convolution, torchvision
    ResNet v1.5 bottlenecks (the stride sits on the 3x3)."""
    side = cfg["image_size"] // 2
    convs = [(7, 3, cfg["stem_channels"], side)]
    side //= 2                                   # 3x3 max-pool, stride 2
    c_in = cfg["stem_channels"]
    for i, (blocks, w) in enumerate(zip(cfg["stage_blocks"],
                                        cfg["bottleneck_widths"])):
        out = w * cfg["expansion"]
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            convs.append((1, c_in, w, side))
            side //= stride
            convs.append((3, w, w, side))
            convs.append((1, w, out, side))
            if j == 0:
                convs.append((1, c_in, out, side))   # projection shortcut
            c_in = out
    return convs, c_in


def resnet_param_count(cfg: dict) -> int:
    """Convolution kernels, a BatchNorm scale and bias per convolution,
    and the classifier (running statistics are state, not parameters)."""
    convs, c_out = _resnet_convs(cfg)
    conv = sum(k * k * ci * co + 2 * co for k, ci, co, _ in convs)
    return conv + c_out * cfg["num_classes"] + cfg["num_classes"]


def resnet_forward_flops(cfg: dict, with_classifier: bool = False) -> float:
    """One image's forward convolutions (pooled features need no
    classifier)."""
    convs, c_out = _resnet_convs(cfg)
    flops = sum(2.0 * k * k * ci * co * side * side
                for k, ci, co, side in convs)
    if with_classifier:
        flops += 2.0 * c_out * cfg["num_classes"]
    return flops
