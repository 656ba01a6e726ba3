"""The plain reference of the LongCat-Flash family's forward pass
(meituan-longcat/LongCat-Flash-Chat, `config.json`): straightforward
`jax.numpy` in float32 under `highest` matmul precision — no kernels, no
cache, no batching, no sorting, attention EXPANDED (K and V of every head
made from the latent, never absorbed) — written from the equations,
reading the program's parameter tree only for its numbers.

    RMS(x) = x / sqrt(mean(x^2) + eps) * w             (pre-norm throughout)
    MLA_j(y), H heads, q/k heads of nope + rope, v heads of v:
        q       = Wqb RMS(Wqa y)              -> [H, nope + rope]
        (c, kr) = Wkva y;   c = RMS(c)        -> latent [kv_rank], [rope]
        q *= sqrt(E / q_rank);  c *= sqrt(E / kv_rank)
        q_rope, kr = rope(q_rope), rope(kr)   (kr: one key for all heads)
        (k_nope, v) = Wkvb c                  -> [H, nope], [H, v]
        a_h = softmax((q_nope.k_nope + q_rope.kr) / sqrt(nope + rope)
                      + causal mask) v_h;     out = Wo concat(a_h)
    block: x += MLA_0(RMS x);  y0 = RMS x;  s = MoE(y0);  x += MLP_0(y0)
           x += MLA_1(RMS x);  x += MLP_1(RMS x);  x += s
    MLP(y) = (silu(y W1) * (y W3)) W2
    MoE(y): p = softmax(y Wr) in float32 over the X FFN experts and then
        the Z identity experts; T = top_k of p + b (b weighs nothing);
        w_e = scaling * p_e, NOT renormalised;
        sum_{e in T, lo <= e < hi} w_e E_e(y)  +  sum_{e in T, e >= X} w_e y
    final RMS, untied head over the vocabulary rows held.

`experts_held = (lo, hi)`: the FFN experts whose weights the tree holds;
what the others would add is left out, as on the chip that holds this
share.  The identity experts hold no weights and are computed here for
every token, as on every chip.

Departures from the published code, all listed in the configuration file
under `assumed` and followed by the program alike: rope rotates the pairs
(i, i + rope/2) where the published code interleaves (2i, 2i + 1) (a
fixed permutation of the rope columns of Wqb and Wkva); SiLU; no bias
term on the router's logits; no renormalisation of the top-k; the
selection bias b, a controller's state in the published model, is a
parameter drawn from the seed; untied embedding and head; a final RMS.

Computed block by block, head by head and expert by expert, each bf16
block widened to float32 only while it is used, so that the 5.17B
parameters of the benchmark's share never exist in float32 at once.

The reference routes by ITS OWN router.  A program that computes in
bfloat16 picks another twelfth expert where the twelfth and thirteenth
biased scores lie nearer than its rounding; `hidden(..., chosen=, band=)`
takes the program's set for a token ONLY where every expert of it lies
within `band` of the reference's own k-th (in units of that k-th's
probability: a relative distance, which is what a distance of router
logits is), and keeps its own everywhere else.  `layer_check` judges one
routed layer by itself on the program's own input, `absorb_check` the
absorbed query of one latent attention on the program's own q_nope.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def arch_of(cfg: dict) -> dict:
    """What the equations need of a configuration file (published keys;
    `num_layers`, `n_routed_experts`, `vocab_size` as cut there)."""
    return {
        "layers": cfg["num_layers"], "hidden": cfg["hidden_size"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"],
        "scale_q": bool(cfg["mla_scale_q_lora"]),
        "scale_kv": bool(cfg["mla_scale_kv_lora"]),
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
        "experts": cfg["published"]["n_routed_experts"],
        "zero": cfg["zero_expert_num"], "top_k": cfg["moe_topk"],
        "scaling": float(cfg["routed_scaling_factor"]),
    }


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def rope(x, theta: float):
    """x [S, ..., D] at positions 0..S-1: the pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _mla(p, y, arch):
    """Latent attention, expanded: one head at a time."""
    s = y.shape[0]
    h, dn, dr, dv = arch["heads"], arch["nope"], arch["rope"], arch["v"]
    e, rq, rk = arch["hidden"], arch["q_rank"], arch["kv_rank"]
    q = (_rms(y @ _f32(p["wqa"]), p["q_norm"]["scale"], arch["eps"])
         @ _f32(p["wqb"])).reshape(s, h, dn + dr)
    ckr = y @ _f32(p["wkva"])
    c = _rms(ckr[:, :rk], p["kv_norm"]["scale"], arch["eps"])
    if arch["scale_q"]:
        q = q * math.sqrt(e / rq)
    if arch["scale_kv"]:
        c = c * math.sqrt(e / rk)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], arch["theta"])
    kr = rope(ckr[:, rk:], arch["theta"])                     # [S, rope]
    wkvb = _f32(p["wkvb"]).reshape(rk, h, dn + dv)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def head(args):
        qn, qr, w = args                     # [S, nope], [S, rope], [rk, .]
        kv = c @ w                                            # [S, nope + v]
        sc = (qn @ kv[:, :dn].T + qr @ kr.T) / math.sqrt(dn + dr)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jax.nn.softmax(sc, -1) @ kv[:, dn:]            # [S, v]

    a = jax.lax.map(head, (q_nope.transpose(1, 0, 2),
                           q_rope.transpose(1, 0, 2),
                           wkvb.transpose(1, 0, 2)))          # [H, S, v]
    return a.transpose(1, 0, 2).reshape(s, h * dv) @ _f32(p["wo"])


def _gated(y, w1, w3, w2):
    return (jax.nn.silu(y @ _f32(w1)) * (y @ _f32(w3))) @ _f32(w2)


def _route(p, y, arch, chosen=None):
    """-> (router logits and probabilities [S, X + Z], the reference's
    own top_k [S, k] by p + b, and how far below its k-th biased score
    the worst of `chosen` [S, k] lies, in units of that k-th's
    probability [S]: 0 where the sets agree, or without `chosen`)."""
    r = y @ _f32(p["router"])
    probs = jax.nn.softmax(r, -1)
    biased = probs + _f32(p["bias"])
    own_s, own_e = jax.lax.top_k(biased, arch["top_k"])
    if chosen is None:
        return r, probs, own_e, jnp.zeros(y.shape[0], jnp.float32)
    kth = jnp.take_along_axis(probs, own_e[:, -1:], -1)[:, 0]
    deficit = (own_s[:, -1]
               - jnp.min(jnp.take_along_axis(biased, chosen, -1), -1)) / kth
    return r, probs, own_e, deficit


def _routed(p, y, probs, top_e, arch, lo: int, parts: bool = False):
    """sum over the experts e of `top_e` of w_e * (E_e(y) where e is an
    FFN expert held here, y where e is an identity expert), with
    w = scaling * p, not renormalised: expert by expert.  `parts`: the
    FFN experts' sum and the identity experts' apart."""
    w = arch["scaling"] * jnp.take_along_axis(probs, top_e, -1)

    def one(e, acc):
        # the weight token t gives FFN expert lo + e (0 where not chosen)
        w_e = jnp.sum(jnp.where(top_e == lo + e, w, 0.0), -1)
        out = _gated(y, jax.lax.dynamic_index_in_dim(p["w1"], e, 0, False),
                     jax.lax.dynamic_index_in_dim(p["w3"], e, 0, False),
                     jax.lax.dynamic_index_in_dim(p["w2"], e, 0, False))
        return acc + w_e[:, None] * out

    ffn = jax.lax.fori_loop(0, p["w1"].shape[0], one, jnp.zeros_like(y))
    w_zero = jnp.sum(jnp.where(top_e >= arch["experts"], w, 0.0), -1)
    zero = w_zero[:, None] * y
    return (ffn, zero) if parts else ffn + zero


def _sparse(p, y, arch, lo: int, chosen=None, band: float = 0.0):
    """-> (the layer's output, the reference's own top_k [S, k], the
    deficit [S] of `chosen`, see `_route`).  A token is computed with
    `chosen`'s set only where its deficit is within `band` (a near tie),
    weighted by the reference's own probabilities."""
    _r, probs, own_e, deficit = _route(p, y, arch, chosen)
    top_e = own_e if chosen is None else jnp.where(
        (deficit <= band)[:, None], chosen, own_e)
    return _routed(p, y, probs, top_e, arch, lo), own_e, deficit


def layer_check(p, taps: dict, arch: dict, lo: int = 0) -> dict:
    """One routed layer judged by itself.  `taps`: what the program's
    layer read and wrote, `input` [S, E] (its normed input as the experts
    read it), `router_input` (as the router read it), `logits` [S, X + Z]
    (its router logits), `experts` [S, k], `routed` [S, E] (the weighted
    sum of the chosen experts, FFN and identity).  From the program's own
    input the equations give router logits, a top-k and a routed sum; per
    row:
      router_err  largest |program's router logit - the equations'|
      route_miss  how far under the equations' k-th biased score the
                  program's worst chosen expert lies, in units of that
                  k-th's probability (0: the same set, or a tie)
      differs     the program's set is not the equations'
      routed_sq, routed_ref_sq   squared error and squared norm of the
                  routed sum, computed with the PROGRAM's set
      ffn_sq, ffn_ref_sq   the same of the FFN experts' part alone (the
                  program's sum less the equations' identity part): the
                  identity experts' part is most of the sum and exact,
                  and would hide the grouped matmul's rounding
      zero, zero_ref   assignments on identity experts in the program's
                  set and in the equations' own."""
    with jax.default_matmul_precision("highest"):
        chosen = taps["experts"].astype(jnp.int32)
        r, probs, own_e, miss = _route(p, _f32(taps["router_input"]), arch,
                                       chosen)
        ffn, zero = _routed(p, _f32(taps["input"]), probs, chosen, arch, lo,
                            parts=True)
        want = ffn + zero
        return {
            "router_err": jnp.max(jnp.abs(_f32(taps["logits"]) - r), -1),
            "route_miss": miss,
            "differs": jnp.any(jnp.sort(chosen, -1) != jnp.sort(own_e, -1),
                               -1),
            "routed_sq": jnp.sum(jnp.square(_f32(taps["routed"]) - want), -1),
            "routed_ref_sq": jnp.sum(jnp.square(want), -1),
            "ffn_sq": jnp.sum(jnp.square(_f32(taps["routed"]) - zero - ffn),
                              -1),
            "ffn_ref_sq": jnp.sum(jnp.square(ffn), -1),
            "zero": jnp.sum(chosen >= arch["experts"], -1),
            "zero_ref": jnp.sum(own_e >= arch["experts"], -1)}


def absorb_check(p, taps: dict, arch: dict):
    """One latent attention's absorbed query judged by itself, on decode
    rows.  `taps`: `mla_q_nope` [S, n * nope], the q_nope of the first n
    heads as the program computed them, and `mla_query` [S, n * W], the
    query the program's page walk multiplied the cached rows by (W the
    row's width, its first kv_rank the absorbed part).  The equations
    give q' = q_nope . Wkvb_K / sqrt(nope + rope) in float32 -> per row
    the largest |program's - the equations'| over the RMS of the
    equations' own."""
    with jax.default_matmul_precision("highest"):
        dn, rk = arch["nope"], arch["kv_rank"]
        s = taps["mla_q_nope"].shape[0]
        q_nope = _f32(taps["mla_q_nope"]).reshape(s, -1, dn)
        n = q_nope.shape[1]
        got = _f32(taps["mla_query"]).reshape(s, n, -1)[..., :rk]
        w_k = _f32(p["wkvb"]).reshape(rk, arch["heads"], -1)[:, :n, :dn]
        want = jnp.einsum("shd,rhd->shr", q_nope, w_k) / math.sqrt(
            dn + arch["rope"])
        rms = jnp.sqrt(jnp.mean(jnp.square(want)) + 1e-30)
        return jnp.max(jnp.abs(got - want), (-2, -1)) / rms


def hidden(params, tokens, arch: dict, lo: int = 0, chosen=None,
           band: float = 0.0):
    """tokens [S] int32 -> (final-normed hidden [S, E] float32; per block
    the reference's own top_k [S, k]; per block the deficit [S] of
    `chosen`, see `_route`).  `chosen` [blocks, S, k]: a program's sets,
    taken for a token where within `band` of the reference's own."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        eps = arch["eps"]
        routing, deficits = [], []
        for i in range(arch["layers"]):
            p = params[f"layer{i}"]
            x = x + _mla(p["attn0"], _rms(x, p["attn_norm0"]["scale"], eps),
                         arch)
            y0 = _rms(x, p["mlp_norm0"]["scale"], eps)
            shortcut, top_e, deficit = _sparse(
                p["moe"], y0, arch, lo,
                None if chosen is None else chosen[i], band)
            routing.append(top_e)
            deficits.append(deficit)
            m = p["mlp0"]
            x = x + _gated(y0, m["w1"], m["w3"], m["w2"])
            x = x + _mla(p["attn1"], _rms(x, p["attn_norm1"]["scale"], eps),
                         arch)
            m = p["mlp1"]
            x = x + _gated(_rms(x, p["mlp_norm1"]["scale"], eps),
                           m["w1"], m["w3"], m["w2"])
            x = x + shortcut
        return (_rms(x, params["final_norm"]["scale"], eps), routing,
                deficits)


def logits_at(params, h, rows):
    """Logits [len(rows), V] float32 of the hidden rows `rows`."""
    with jax.default_matmul_precision("highest"):
        return h[rows] @ _f32(params["head"])


def logits(params, tokens, arch: dict, lo: int = 0):
    """tokens [S] -> [S, V] float32 (small sizes: the tests)."""
    h, _routing, _deficits = hidden(params, tokens, arch, lo)
    return logits_at(params, h, jnp.arange(tokens.shape[0]))
