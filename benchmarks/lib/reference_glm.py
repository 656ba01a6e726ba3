"""The plain reference of the GLM-4.7-Flash family's TRAINING objective
(zai-org/GLM-4.7-Flash, `glm4_moe_lite`): straightforward `jax.numpy` in
float32 under `highest` matmul precision — no kernels, no dispatch, no
sorting, attention plain and EXPANDED a head at a time, experts one by
one, a sequence at a time — written from the equations, reading the
program's variables only for their numbers.  `jax.grad` of `loss` is the
reference gradient.

    RMS(x) = x / sqrt(mean(x^2) + eps) * w             (pre-norm throughout)
    block: x += MLA(RMS x);  x += F(RMS x)
    MLA(y), H heads of nope + rope (q, k) and v:
        q       = Wqb RMS(Wqa y)              -> [H, nope + rope]
        (c, kr) = Wkva y;   c = RMS(c)        -> latent [kv_rank], [rope]
        q_rope, kr = rope(q_rope), rope(kr)   (kr: one key for all heads)
        (k_nope, v) = Wkvb c                  -> [H, nope], [H, v]
        a_h = softmax((q_nope.k_nope + q_rope.kr) / sqrt(nope + rope)
                      + causal mask) v_h;     out = Wo concat(a_h)
    F: layer 0 (the first `dense`)  MLP(y) = (silu(y W1) * (y W3)) W2
       after them  s = sigmoid(y Wr) over all X experts;  T = top_k of
        s + b (b weighs nothing and takes no gradient);
        w_e = scaling * s_e / (sum_{T} s + 1e-20);
        sum_{e in T, lo <= e < hi} w_e E_e(y) + Shared(y)
    MTP (depth 1, DeepSeek-V3 2.2), h the last layer's output BEFORE the
        final RMS, positions i = 0 .. S-2:
        h'_i = [RMS_e(Emb(t_{i+1})) ; RMS_h(h_i)] M;  one sparse block of
        its own over h' (positions 0 .. S-2);  logits (RMS .) Head
    loss = CE_main + lambda CE_mtp:  CE_main the mean over i < S-1 of
        -log softmax(RMS(h_i) Head)[t_{i+1}], CE_mtp the mean over
        i < S-2 of -log softmax(mtp_i)[t_{i+2}]
    controller: b_e += gamma sign(mean(load) - load_e), load the counts
        of a step's choices over ALL X experts, a routed layer at a time

`experts_held = (lo, hi)`: the experts whose weights the tree holds; what
the others would add is left out, as on the chip that holds this share.

Departures from the published code are the configuration file's
`assumed`, followed by the program alike.  Every block is a
`jax.checkpoint`, so that the gradient of a 4,096-token sequence keeps one
block's scores at a time; that changes no number.

The reference routes by ITS OWN router; `chosen=` gives it a program's
sets, taken for a token only where every expert of the set lies within
`band` of the reference's own k-th biased score (a near tie: the
program's bf16 input moved the order).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BIAS = "controller"


def arch_of(cfg: dict) -> dict:
    """What the equations need of a configuration file."""
    return {
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "theta": float(cfg["rope_theta"]), "eps": cfg["rms_norm_eps"],
        "experts": cfg["published"]["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "scaling": float(cfg["routed_scaling_factor"]),
        "mtp": cfg["num_nextn_predict_layers"],
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
    rk = arch["kv_rank"]
    q = (_rms(y @ _f32(p["wqa"]), p["q_norm"]["scale"], arch["eps"])
         @ _f32(p["wqb"])).reshape(s, h, dn + dr)
    ckr = y @ _f32(p["wkva"])
    c = _rms(ckr[:, :rk], p["kv_norm"]["scale"], arch["eps"])
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


def _gated(y, p):
    return (jax.nn.silu(y @ _f32(p["w1"])) * (y @ _f32(p["w3"]))) @ _f32(
        p["w2"])


def route(p, bias, y, arch, chosen=None, band: float = 0.0):
    """-> (scores [S, X], the experts the layer is computed with [S, k],
    the reference's own top_k [S, k], and how far below its k-th biased
    score the worst of `chosen` lies [S]: 0 where the sets agree, or
    without `chosen`)."""
    s = jax.nn.sigmoid(y @ _f32(p["router"]))
    biased = s + jax.lax.stop_gradient(_f32(bias))
    own_s, own_e = jax.lax.top_k(biased, arch["top_k"])
    if chosen is None:
        return s, own_e, own_e, jnp.zeros(y.shape[0], jnp.float32)
    deficit = own_s[:, -1] - jnp.min(
        jnp.take_along_axis(biased, chosen, -1), -1)
    top_e = jnp.where((deficit <= band)[:, None], chosen, own_e)
    return s, top_e, own_e, deficit


def _sparse(p, bias, y, arch, lo: int, chosen=None, band: float = 0.0):
    """-> (the layer's output, (the experts it was computed with, the
    reference's own, the deficit of `chosen`))."""
    s, top_e, own_e, deficit = route(p, bias, y, arch, chosen, band)
    top_s = jnp.take_along_axis(s, top_e, -1)
    w = arch["scaling"] * top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    out = _gated(y, p["shared"])
    for e in range(p["w1"].shape[0]):        # expert by expert, all rows
        w_e = jnp.sum(jnp.where(top_e == lo + e, w, 0.0), -1)
        out = out + w_e[:, None] * _gated(
            y, {k: p[k][e] for k in ("w1", "w3", "w2")})
    return out, (top_e, own_e, deficit)


def _block(p, bias, x, arch, lo, sparse: bool, chosen, band):
    eps = arch["eps"]
    x = x + _mla(p["attn"], _rms(x, p["attn_norm"]["scale"], eps), arch)
    y = _rms(x, p["mlp_norm"]["scale"], eps)
    if not sparse:
        return x + _gated(y, p["mlp"]), None
    m, routing = _sparse(p["moe"], bias["moe"]["bias"], y, arch, lo, chosen,
                         band)
    return x + m, routing


def _ce(x, head, targets):
    """Mean over the rows of -log softmax(x head)[target]."""
    logp = jax.nn.log_softmax(x @ _f32(head), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


def sequence(params, biases, tokens, arch: dict, lo: int = 0, chosen=None,
             band: float = 0.0, mtp_logits: bool = False):
    """One sequence, tokens [S] int32 -> {"ce_main", "ce_mtp" (0 without
    the module), "logits" [S, V] of the main head, "routing": per routed
    layer in the program's order (the layers, then the MTP block) (the
    experts computed with, the reference's own, the deficit)}.  `chosen`:
    per routed layer a program's sets [S or S - 1, k]."""
    with jax.default_matmul_precision("highest"):
        eps = arch["eps"]

        def block(p, bias, x, sparse, chosen):
            return jax.checkpoint(
                lambda p, bias, x, chosen: _block(p, bias, x, arch, lo,
                                                  sparse, chosen, band))(
                p, bias, x, chosen)

        picks = iter(chosen) if chosen is not None else None
        table = _f32(params["embed"])
        x = table[tokens]
        routing = []
        for i in range(arch["layers"]):
            name = f"layer{i}"
            sparse = i >= arch["dense"]
            x, r = block(params[name], biases.get(name), x, sparse,
                         next(picks) if picks and sparse else None)
            if sparse:
                routing.append(r)
        logits = _rms(x, params["final_norm"]["scale"], eps) @ _f32(
            params["head"])
        logp = jax.nn.log_softmax(logits[:-1], -1)
        out = {"ce_main": -jnp.mean(jnp.take_along_axis(
                   logp, tokens[1:, None], -1)),
               "ce_mtp": jnp.zeros((), jnp.float32), "logits": logits}
        if arch["mtp"]:
            p = params["mtp"]
            merged = jnp.concatenate(
                [_rms(table[tokens[1:]], p["enorm"]["scale"], eps),
                 _rms(x[:-1], p["hnorm"]["scale"], eps)], -1) @ _f32(
                p["eh_proj"])
            m, r = block(p["block"], biases["mtp"]["block"], merged, True,
                         next(picks) if picks else None)
            routing.append(r)
            m = _rms(m, p["final_norm"]["scale"], eps)
            out["ce_mtp"] = _ce(m[:-1], params["head"], tokens[2:])
            if mtp_logits:
                out["mtp_logits"] = m @ _f32(params["head"])
        out["routing"] = routing
        return out


def loss(params, biases, tokens, arch: dict, mtp_weight: float, lo: int = 0,
         chosen=None, band: float = 0.0):
    """tokens [B, S] -> (CE_main + mtp_weight * CE_mtp, each the mean of
    the sequences' means; {"ce_main", "ce_mtp", "routing": per sequence
    the lists of `sequence`}), a sequence at a time."""
    mains, mtps, routing = [], [], []
    for b in range(tokens.shape[0]):
        out = sequence(params, biases, tokens[b], arch, lo,
                       None if chosen is None else chosen[b], band)
        mains.append(out["ce_main"])
        mtps.append(out["ce_mtp"])
        routing.append(out["routing"])
    ce_main, ce_mtp = jnp.mean(jnp.stack(mains)), jnp.mean(jnp.stack(mtps))
    return ce_main + mtp_weight * ce_mtp, {
        "ce_main": ce_main, "ce_mtp": ce_mtp, "routing": routing}


def loads(routing, experts: int):
    """Per routed layer the counts [X] of the choices of all sequences:
    `routing` as `loss` hands it back (the experts computed with)."""
    layers = len(routing[0])
    return [sum(jnp.sum(seq[i][0][..., None] == jnp.arange(experts), (0, 1))
                for seq in routing) for i in range(layers)]


def bias_after(bias, load, gamma: float):
    """The controller's rule on one layer's counts."""
    load = _f32(load)
    return _f32(bias) + gamma * jnp.sign(jnp.mean(load) - load)
