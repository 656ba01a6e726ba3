"""The plain reference of the Laguna family's forward pass
(poolside/Laguna-S-2.1, `config.json`): straightforward `jax.numpy` in
float32 under `highest` matmul precision — no kernels, no cache, no
batching, no sorting — written from the equations, reading the program's
parameter tree only for its numbers.

    RMS(x) = x / sqrt(mean(x^2) + eps) * w             (pre-norm blocks)
    attention of layer l, H_l query heads over Hkv KV heads of size D:
        q, k = rope_l(y Wq, y Wk);  g(h) = h // (H_l / Hkv)
        a_h = softmax(q_h k_g(h)^T / sqrt(D) + mask_l) v_g(h)
        mask_l causal, on window layers also key > query - window
        a_h <- sigmoid(y Wg)_h * a_h;   x <- x + concat(a_h) Wo
    rope_l: window layers rotate all of D at theta_w; full layers rotate
        the first D * partial_rotary_factor with YaRN's blended inverse
        frequencies, cos and sin times attention_factor
    layer 0: x <- x + (silu(y W1) * (y W3)) W2
    sparse layers: p = softmax(y Wr) in float32 over ALL experts, T the
        top_k, w_e = scaling * p_e / sum_T p;
        x <- x + E_shared(y) + sum_{e in T, lo <= e < hi} w_e E_e(y)
    final RMS, untied head over the vocabulary rows held.

`experts_held = (lo, hi)`: the experts whose weights the tree holds (its
expert stacks have hi - lo entries); what the others would add is left
out, as on the chip that holds this share.  Departures from the published
config that the configuration file lists under `assumed` are reproduced
here: a softmax router without bias or soft-capping, the head-wise
sigmoid gate read from the normed input, no q/k normalisation, SiLU.

Computed layer by layer, KV head by KV head and expert by expert, each
bf16 block widened to float32 only while it is used, so that the 5.57B
parameters of the benchmark's share never exist in float32 at once.

The reference routes by ITS OWN router.  A program that computes in
bfloat16 picks another tenth expert where the tenth and the eleventh
router logits lie nearer than its rounding, and then computes a different
(equally valid) network from there on; `hidden(..., chosen=, band=)` takes
the program's set for a token ONLY where every expert of it lies within
`band` router-logit units of the reference's own k-th, and keeps its own
everywhere else.  `layer_check` judges one routed layer by itself, on the
program's own input to it: router, top-k and experts, with no upstream
rounding in the way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def arch_of(cfg: dict) -> dict:
    """What the equations need of a configuration file (published keys;
    `num_hidden_layers`, `num_experts`, `vocab_size` as cut there)."""
    n = cfg["num_hidden_layers"]
    return {
        "layers": n,
        "heads": list(cfg["num_attention_heads_per_layer"][:n]),
        "windowed": [t == "sliding_attention"
                     for t in cfg["layer_types"][:n]],
        "sparse": [t == "sparse" for t in cfg["mlp_layer_types"][:n]],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"], "eps": cfg["rms_norm_eps"],
        "top_k": cfg["num_experts_per_tok"],
        "scaling": cfg["moe_routed_scaling_factor"],
        "rope_full": cfg["rope_parameters"]["full_attention"],
        "rope_window": cfg["rope_parameters"]["sliding_attention"],
    }


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def _yarn_inv_freq(rp: dict, head_dim: int):
    dim = int(head_dim * rp["partial_rotary_factor"])
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    idx = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extrapolation = 1.0 / theta ** (idx / dim)
    interpolation = extrapolation / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return interpolation * ramp + extrapolation * (1.0 - ramp)


def rope(x, windowed: bool, arch: dict):
    """x [S, H, D] at positions 0..S-1."""
    d = x.shape[-1]
    if windowed:
        rp = arch["rope_window"]
        dim = int(d * rp["partial_rotary_factor"])
        inv = 1.0 / float(rp["rope_theta"]) ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        factor = 1.0
    else:
        rp = arch["rope_full"]
        dim = int(d * rp["partial_rotary_factor"])
        inv = _yarn_inv_freq(rp, d)
        factor = rp["attention_factor"]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]        # [S, 1, dim]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    rot, rest = x[..., :dim], x[..., dim:]
    half = jnp.concatenate([-rot[..., dim // 2:], rot[..., :dim // 2]], -1)
    return jnp.concatenate([rot * cos + half * sin, rest], -1)


def mask(s: int, window):
    """[S, S] bool: query row sees key column."""
    q, k = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = k <= q
    if window is not None:
        seen = seen & (k > q - window)
    return seen


def _attention(p, y, h, windowed, arch):
    s = y.shape[0]
    hkv, d = arch["kv_heads"], arch["head_dim"]
    q = rope((y @ _f32(p["wq"])).reshape(s, h, d), windowed, arch)
    k = rope((y @ _f32(p["wk"])).reshape(s, hkv, d), windowed, arch)
    v = (y @ _f32(p["wv"])).reshape(s, hkv, d)
    gate = jax.nn.sigmoid(y @ _f32(p["wg"]))                  # [S, H]
    seen = mask(s, arch["window"] if windowed else None)
    group = h // hkv
    heads = []
    for kv in range(hkv):                 # a KV head and its query group
        qg = q[:, kv * group:(kv + 1) * group]                # [S, G, D]
        sc = jnp.einsum("qgd,kd->gqk", qg, k[:, kv]) / math.sqrt(d)
        sc = jnp.where(seen[None], sc, -jnp.inf)
        heads.append(jnp.einsum("gqk,kd->qgd", jax.nn.softmax(sc, -1),
                                v[:, kv]))
    a = jnp.concatenate(heads, 1) * gate[..., None]           # [S, H, D]
    return a.reshape(s, h * d) @ _f32(p["wo"])


def _gated(y, w1, w3, w2):
    return (jax.nn.silu(y @ _f32(w1)) * (y @ _f32(w3))) @ _f32(w2)


def _route(p, y, arch, chosen=None):
    """-> (router logits and probabilities [S, X], the reference's own
    top_k [S, k], and how far below its k-th router logit the worst of
    `chosen` [S, k] lies [S]: 0 where the sets agree, or without
    `chosen`)."""
    r = y @ _f32(p["router"])
    probs = jax.nn.softmax(r, -1)
    _own_p, own_e = jax.lax.top_k(probs, arch["top_k"])
    if chosen is None:
        return r, probs, own_e, jnp.zeros(y.shape[0], jnp.float32)
    deficit = (jnp.min(jnp.take_along_axis(r, own_e, -1), -1)
               - jnp.min(jnp.take_along_axis(r, chosen, -1), -1))
    return r, probs, own_e, deficit


def _routed(p, y, probs, top_e, arch, lo: int):
    """sum over the experts e of `top_e` held here of w_e E_e(y), with
    w = scaling * p / sum of p over `top_e`: expert by expert."""
    top_p = jnp.take_along_axis(probs, top_e, -1)
    w = arch["scaling"] * top_p / jnp.sum(top_p, -1, keepdims=True)

    def one(e, acc):
        # the weight token t gives expert lo + e (0 where not chosen)
        w_e = jnp.sum(jnp.where(top_e == lo + e, w, 0.0), -1)
        out = _gated(y, jax.lax.dynamic_index_in_dim(p["w1"], e, 0, False),
                     jax.lax.dynamic_index_in_dim(p["w3"], e, 0, False),
                     jax.lax.dynamic_index_in_dim(p["w2"], e, 0, False))
        return acc + w_e[:, None] * out

    return jax.lax.fori_loop(0, p["w1"].shape[0], one, jnp.zeros_like(y))


def _sparse(p, y, arch, lo: int, chosen=None, band: float = 0.0):
    """-> (the layer's output, the reference's own top_k [S, k], the
    deficit [S] of `chosen`, see `_route`).  A token is computed with
    `chosen`'s set only where its deficit is within `band` (a near tie),
    weighted by the reference's own probabilities; with the reference's
    own set everywhere else."""
    _r, probs, own_e, deficit = _route(p, y, arch, chosen)
    top_e = own_e if chosen is None else jnp.where(
        (deficit <= band)[:, None], chosen, own_e)
    sh = p["shared"]
    return (_routed(p, y, probs, top_e, arch, lo)
            + _gated(y, sh["w1"], sh["w3"], sh["w2"]), own_e, deficit)


def layer_check(p, taps: dict, arch: dict, lo: int = 0) -> dict:
    """One routed layer judged by itself.  `taps`: what the program's
    layer read and wrote, `input` [S, E] (its normed input as the experts
    read it) and `router_input` (as the router read it: the same values,
    or the norm's float32 before its rounding to the model's dtype where
    the compiler kept that), `logits` [S, X] (its router logits),
    `experts` [S, k], `routed` [S, E] (the routed experts' weighted sum,
    without the shared expert).  From the program's own input the
    equations give router logits, a top-k and a routed sum; per row:
      router_err  largest |program's router logit - the equations'|
      route_miss  how far under the equations' k-th logit the program's
                  worst chosen expert lies (0: the same set, or a tie)
      differs     the program's set is not the equations'
      routed_sq, routed_ref_sq   squared error and squared norm of the
                  routed sum, computed with the PROGRAM's set."""
    with jax.default_matmul_precision("highest"):
        chosen = taps["experts"].astype(jnp.int32)
        r, probs, own_e, miss = _route(p, _f32(taps["router_input"]), arch,
                                       chosen)
        want = _routed(p, _f32(taps["input"]), probs, chosen, arch, lo)
        return {
            "router_err": jnp.max(jnp.abs(_f32(taps["logits"]) - r), -1),
            "route_miss": miss,
            "differs": jnp.any(jnp.sort(chosen, -1) != jnp.sort(own_e, -1),
                               -1),
            "routed_sq": jnp.sum(jnp.square(_f32(taps["routed"]) - want), -1),
            "routed_ref_sq": jnp.sum(jnp.square(want), -1)}


def hidden(params, tokens, arch: dict, lo: int = 0, chosen=None,
           band: float = 0.0):
    """tokens [S] int32 -> (final-normed hidden [S, E] float32; per
    sparse layer the reference's own top_k [S, k]; per sparse layer the
    router-logit deficit [S] of `chosen`, see `_route`).  `chosen`
    [sparse layers, S, k]: a program's sets, taken for a token where
    within `band` of the reference's own (`_sparse`)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        routing, deficits = [], []
        for i in range(arch["layers"]):
            p = params[f"layer{i}"]
            y = _rms(x, p["attn_norm"]["scale"], arch["eps"])
            x = x + _attention(p, y, arch["heads"][i], arch["windowed"][i],
                               arch)
            y = _rms(x, p["mlp_norm"]["scale"], arch["eps"])
            if arch["sparse"][i]:
                m, top_e, deficit = _sparse(
                    p["moe"], y, arch, lo,
                    None if chosen is None else chosen[len(routing)], band)
                routing.append(top_e)
                deficits.append(deficit)
            else:
                m = _gated(y, p["mlp"]["w1"], p["mlp"]["w3"], p["mlp"]["w2"])
            x = x + m
        return (_rms(x, params["final_norm"]["scale"], arch["eps"]),
                routing, deficits)


def logits_at(params, h, rows):
    """Logits [len(rows), V] float32 of the hidden rows `rows`."""
    with jax.default_matmul_precision("highest"):
        return h[rows] @ _f32(params["head"])


def logits(params, tokens, arch: dict, lo: int = 0):
    """tokens [S] -> [S, V] float32 (small sizes: the tests)."""
    h, _routing, _deficits = hidden(params, tokens, arch, lo)
    return logits_at(params, h, jnp.arange(tokens.shape[0]))
