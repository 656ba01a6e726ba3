"""Autoregressive generation for TransformerLM: KV-cached decode loop.

Beyond-reference capability (the reference serves fixed-function models;
it has no autoregressive decode): greedy / temperature sampling with a
per-layer KV cache, TPU-shaped —

  - prefill is ONE full forward over the prompt (the per-layer K/V ride
    out through flax's `sow` into the 'kvcache' collection, then pad
    into static [B, max_len, H, D] cache arrays);
  - the decode loop is ONE `lax.scan` dispatch over the new tokens
    (static shapes, cache updated in place via dynamic_update_slice) —
    no per-token host round trips.

`generate` is a pure function of (variables, prompt, rng) and jits as a
whole; serving can wrap it in a LambdaTransformer.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .transformer import TransformerLM

__all__ = ["generate", "beam_search", "speculative_generate"]


def _filter_logits(lg: jnp.ndarray, top_k: Optional[int],
                   top_p: Optional[float]) -> jnp.ndarray:
    """Mask logits outside the top-k set and/or the top-p nucleus to -inf.
    Static shapes throughout (sort + threshold, no gather-by-count)."""
    if top_k is not None and top_k < lg.shape[-1]:
        kth = jnp.sort(lg, axis=-1)[..., -top_k][..., None]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p is not None and top_p < 1.0:
        srt = jnp.sort(lg, axis=-1)[..., ::-1]                # descending
        cum = jnp.cumsum(jax.nn.softmax(srt, axis=-1), axis=-1)
        # smallest set with cumulative prob >= top_p: a token stays if the
        # mass BEFORE it (exclusive) is still < top_p
        keep = (cum - jax.nn.softmax(srt, axis=-1)) < top_p
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)[..., None]
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    return lg


def _prefill_cache(model: TransformerLM, variables, prompt: jnp.ndarray,
                   kv_cache_dtype: Optional[str] = None):
    """One prefill forward; returns (logits, per-layer KV cache padded to
    [B, max_len, ...]).  The cache is the 2-tuple (k, v) form, or the
    4-tuple int8 form (kq, ks, vq, vs) when kv_cache_dtype="int8"
    (ops/quant.quantize_kv_row; unwritten positions stay (0 * 0-scale)=0
    and are masked out of the softmax by the <= pos validity check)."""
    b, s_p = prompt.shape
    h = model.kv_heads          # the cache stores the SHARED (GQA) heads
    d = model.embed_dim // model.num_heads
    # drop any stale 'kvcache' collection captured at init time — sow
    # would try to append to it at the init shapes otherwise
    variables = {c: v for c, v in variables.items() if c != "kvcache"}
    (logits, _taps), kv = model.apply(variables, prompt, train=False,
                                      mutable=["kvcache"])
    cache = []
    for i in range(model.num_layers):
        layer = kv["kvcache"][f"block{i}"]
        k, v = layer["k"][0], layer["v"][0]          # [B, S_p, H, D]
        if kv_cache_dtype == "int8":
            from ..ops.quant import quantize_kv_row

            kq, ks = quantize_kv_row(k)
            vq, vs = quantize_kv_row(v)
            cache.append((
                jnp.zeros((b, model.max_len, h, d), jnp.int8)
                .at[:, :s_p].set(kq),
                jnp.zeros((b, model.max_len, h), jnp.float32)
                .at[:, :s_p].set(ks),
                jnp.zeros((b, model.max_len, h, d), jnp.int8)
                .at[:, :s_p].set(vq),
                jnp.zeros((b, model.max_len, h), jnp.float32)
                .at[:, :s_p].set(vs),
            ))
        else:
            kc = jnp.zeros((b, model.max_len, h, d), k.dtype).at[:, :s_p].set(k)
            vc = jnp.zeros((b, model.max_len, h, d), v.dtype).at[:, :s_p].set(v)
            cache.append((kc, vc))
    return logits, tuple(cache)


def generate(model: TransformerLM, variables, prompt: jnp.ndarray,
             max_new_tokens: int, temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             kv_cache_dtype: Optional[str] = None) -> jnp.ndarray:
    """prompt [B, S_p] int32 -> [B, S_p + max_new_tokens] int32.

    temperature == 0 is greedy argmax; > 0 samples categorically with
    `rng` (required then), optionally restricted to the `top_k` highest
    logits and/or the `top_p` nucleus.  With `eos_id`, rows that emit it
    keep emitting it and their logits stop mattering (static shapes: the
    scan always runs max_new_tokens steps).

    kv_cache_dtype="int8" stores the KV cache as int8 with per-row
    scales (ops/quant.quantize_kv_row): 4x less cache HBM than f32 — the
    long-context decode bottleneck — at ~1/255 rounding noise per row.
    """
    if kv_cache_dtype not in (None, "int8"):
        raise ValueError(f"kv_cache_dtype must be None or 'int8', "
                         f"got {kv_cache_dtype!r}")
    b, s_p = prompt.shape
    total = s_p + max_new_tokens
    if total > model.max_len:
        raise ValueError(
            f"prompt {s_p} + {max_new_tokens} new tokens exceeds "
            f"max_len {model.max_len}")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng")
    if max_new_tokens < 1:
        return prompt
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    logits, cache = _prefill_cache(model, variables, prompt, kv_cache_dtype)
    variables = {c: v for c, v in variables.items() if c != "kvcache"}

    def sample(lg, key):
        if temperature == 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        # temperature FIRST, then top-k/top-p on the tempered distribution
        # (the conventional order: nucleus membership reflects the actual
        # sampling distribution, not the T=1 one)
        lg = _filter_logits(lg / temperature, top_k, top_p)
        return jax.random.categorical(key, lg).astype(jnp.int32)

    # ---- decode: one scan over the new tokens ---------------------------
    def body(carry, _):
        cache, cur_logits, pos, key, done = carry
        key, sub = jax.random.split(key)
        tok = sample(cur_logits, sub)                          # [B]
        if eos_id is not None:
            tok = jnp.where(done, eos_id, tok)
            done = done | (tok == eos_id)
        lg, cache = model.apply(variables, tok[:, None], cache, pos,
                                method=model.decode_step)
        return (cache, lg[:, 0], pos + 1, key, done), tok

    done0 = jnp.zeros((b,), bool)
    # scan max_new_tokens - 1 steps; the LAST token samples from the
    # final step's logits outside the loop (a decode_step whose logits
    # nobody reads would be a wasted transformer forward)
    (_, last_lg, _, key, done), toks = jax.lax.scan(
        body, (cache, logits[:, -1], jnp.int32(s_p), rng, done0),
        None, length=max_new_tokens - 1)
    last = sample(last_lg, jax.random.split(key)[1])
    if eos_id is not None:
        last = jnp.where(done, eos_id, last)
    toks = jnp.concatenate([toks, last[None]], axis=0)
    return jnp.concatenate([prompt, toks.T], axis=1)


def beam_search(model: TransformerLM, variables, prompt: jnp.ndarray,
                max_new_tokens: int, num_beams: int = 4,
                length_penalty: float = 1.0,
                eos_id: Optional[int] = None,
                kv_cache_dtype: Optional[str] = None) -> jnp.ndarray:
    """Beam-search decode: prompt [B, S_p] -> [B, S_p + max_new_tokens].

    TPU-shaped like `generate`: ONE prefill forward (on B rows, cache then
    tiled to B*K) and ONE `lax.scan` over the new tokens.  Every step is
    static-shape: score accumulation is a [B, K*V] top-k, beam reordering
    is a batched gather of the KV cache, and finished beams (`eos_id`)
    are frozen by restricting their continuations to eos at zero cost.

    Hypotheses are ranked by score / len**length_penalty (GNMT
    normalization; 0.0 = raw sum of logprobs).  Because mid-search
    pruning is by RAW score, a finished hypothesis can be displaced from
    the live beam by longer continuations — every beam that finishes is
    therefore also recorded in a per-row best-finished buffer, and the
    final answer is the better of (best live, best finished).
    """
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    b, s_p = prompt.shape
    k_beams = int(num_beams)
    n = int(max_new_tokens)
    if s_p + n > model.max_len:
        raise ValueError(
            f"prompt {s_p} + {n} new tokens exceeds max_len {model.max_len}")
    if n < 1:
        return prompt
    v_size = model.vocab_size
    pen = jnp.float32(length_penalty)

    logits, cache = _prefill_cache(model, variables, prompt, kv_cache_dtype)
    variables = {c: v for c, v in variables.items() if c != "kvcache"}
    # tile each row's cache across its K beams: rows order [b0 b0 ... b1 ...]
    cache = jax.tree.map(lambda c: jnp.repeat(c, k_beams, axis=0), cache)

    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]
    cur_logp = jnp.repeat(logp0[:, None], k_beams, axis=1)         # [B, K, V]
    # only beam 0 is live initially, so the first top-k picks K DISTINCT
    # first tokens instead of K copies of the argmax
    scores = jnp.full((b, k_beams), -jnp.inf).at[:, 0].set(0.0)
    seqs = jnp.zeros((b, k_beams, n), jnp.int32)
    done = jnp.zeros((b, k_beams), bool)
    gen_len = jnp.zeros((b, k_beams), jnp.int32)
    best_norm = jnp.full((b,), -jnp.inf)       # finished-hypotheses buffer
    best_seq = jnp.zeros((b, n), jnp.int32)
    rows = jnp.arange(b)[:, None]                                  # [B, 1]

    def select(scores, seqs, done, gen_len, cur_logp, t):
        """One beam expansion: [B, K*V] top-k + state reorder at step t."""
        logp = cur_logp
        if eos_id is not None:
            # finished beams may only continue with eos, at zero cost
            frozen = jnp.full((v_size,), -jnp.inf).at[eos_id].set(0.0)
            logp = jnp.where(done[..., None], frozen[None, None], logp)
        cand = scores[..., None] + logp                    # [B, K, V]
        vals, idx = jax.lax.top_k(cand.reshape(b, -1), k_beams)
        beam = idx // v_size                               # [B, K]
        tok = (idx % v_size).astype(jnp.int32)
        seqs = seqs[rows, beam].at[:, :, t].set(tok)
        prev_done = done[rows, beam]
        gen_len = gen_len[rows, beam]
        if eos_id is not None:
            gen_len = jnp.where(prev_done, gen_len, t + 1)
            newly = ~prev_done & (tok == eos_id)
            done = prev_done | newly
        else:
            gen_len = jnp.full_like(gen_len, t + 1)
            newly = jnp.zeros_like(prev_done)
            done = prev_done
        return vals, seqs, done, gen_len, beam, newly

    def update_finished(best_norm, best_seq, scores, seqs, gen_len, newly):
        norm = scores / jnp.maximum(gen_len, 1).astype(jnp.float32) ** pen
        cand = jnp.where(newly, norm, -jnp.inf)            # [B, K]
        arg = jnp.argmax(cand, axis=1)
        cand_best = jnp.take_along_axis(cand, arg[:, None], axis=1)[:, 0]
        better = cand_best > best_norm
        best_norm = jnp.where(better, cand_best, best_norm)
        best_seq = jnp.where(better[:, None],
                             seqs[jnp.arange(b), arg], best_seq)
        return best_norm, best_seq

    def body(carry, t):
        (cache, scores, seqs, done, gen_len, cur_logp,
         best_norm, best_seq) = carry
        scores, seqs, done, gen_len, beam, newly = select(
            scores, seqs, done, gen_len, cur_logp, t)
        best_norm, best_seq = update_finished(
            best_norm, best_seq, scores, seqs, gen_len, newly)
        flat_sel = (rows * k_beams + beam).reshape(-1)     # [B*K]
        cache = jax.tree.map(lambda c: jnp.take(c, flat_sel, axis=0), cache)
        tok = seqs[:, :, t]
        lg, cache = model.apply(variables, tok.reshape(-1, 1), cache,
                                s_p + t, method=model.decode_step)
        cur_logp = jax.nn.log_softmax(
            lg[:, 0].astype(jnp.float32)).reshape(b, k_beams, v_size)
        return (cache, scores, seqs, done, gen_len, cur_logp,
                best_norm, best_seq), None

    # scan n-1 steps; the FINAL expansion needs no decode_step after it
    # (a forward whose logits nobody reads — same shape as `generate`)
    (cache, scores, seqs, done, gen_len, cur_logp,
     best_norm, best_seq), _ = jax.lax.scan(
        body, (cache, scores, seqs, done, gen_len, cur_logp,
               best_norm, best_seq), jnp.arange(n - 1))
    scores, seqs, done, gen_len, _beam, newly = select(
        scores, seqs, done, gen_len, cur_logp, n - 1)
    best_norm, best_seq = update_finished(
        best_norm, best_seq, scores, seqs, gen_len, newly)

    live_norm = scores / jnp.maximum(gen_len, 1).astype(jnp.float32) ** pen
    live_arg = jnp.argmax(live_norm, axis=1)
    live_best = jnp.take_along_axis(live_norm, live_arg[:, None],
                                    axis=1)[:, 0]
    live_seq = seqs[jnp.arange(b), live_arg]
    out = jnp.where((best_norm > live_best)[:, None], best_seq, live_seq)
    if eos_id is not None:
        # buffered hypotheses snapshot the seq at finish time, leaving
        # unwritten zeros past the eos — pad the dead tail with eos so
        # every returned row reads "...tokens, eos, eos, ..."
        seen = jnp.cumsum(out == eos_id, axis=1) > 0
        out = jnp.where(seen, eos_id, out)
    return jnp.concatenate([prompt, out], axis=1)


def speculative_generate(model: TransformerLM, variables,
                         draft_model: TransformerLM, draft_variables,
                         prompt: jnp.ndarray, max_new_tokens: int,
                         gamma: int = 4,
                         eos_id: Optional[int] = None,
                         return_stats: bool = False):
    """Greedy speculative decoding: a cheap draft proposes `gamma` tokens
    per round, the target verifies them all in ONE block `decode_step`
    (K/V written speculatively; rejected positions stay masked garbage
    the next round overwrites).  Output is EXACTLY the target's greedy
    decode — the draft only changes how many target forwards it takes,
    per round: 1 target block forward for up to gamma+1 emitted tokens.

    B must be 1 (per-row acceptance counts diverge cache positions;
    serving decodes one stream per call anyway).  The models must share
    a vocabulary; the draft is typically a smaller/int8 variant.
    """
    if prompt.shape[0] != 1:
        raise ValueError("speculative_generate supports batch size 1 "
                         f"(got {prompt.shape[0]}); decode streams "
                         "independently in serving")
    if draft_model.vocab_size != model.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    n = int(max_new_tokens)
    s_p = prompt.shape[1]
    g = int(gamma)
    if s_p + n > model.max_len:
        raise ValueError(
            f"prompt {s_p} + {n} new tokens exceeds max_len {model.max_len}")
    if n < 1:
        return prompt
    # the verify block may run up to g ahead of the emitted length
    if s_p + n + g > model.max_len or s_p + n + g > draft_model.max_len:
        raise ValueError(
            f"speculative decode needs max_len >= prompt + new + gamma "
            f"({s_p}+{n}+{g}) on both models")

    t_logits, t_cache = _prefill_cache(model, variables, prompt)
    d_logits, d_cache = _prefill_cache(draft_model, draft_variables, prompt)
    variables = {c: v for c, v in variables.items() if c != "kvcache"}
    draft_variables = {c: v for c, v in draft_variables.items()
                       if c != "kvcache"}

    # the first token comes straight from the target's prefill logits:
    # y is always "decided but not yet ingested", sitting at position p
    y0 = jnp.argmax(t_logits[:, -1], axis=-1).astype(jnp.int32)   # [1]
    out0 = jnp.zeros((n + g + 1,), jnp.int32).at[0].set(y0[0])

    def draft_round(d_cache, y, p):
        """gamma draft steps from pending token y at position p — plus one
        EXTRA step that only exists to write d_g's K/V at p+g: on a fully
        accepted round the next pending position is p+g+1, and without
        this write the draft cache would keep prefill zeros at p+g
        forever (an unmasked hole every later draft query attends over,
        silently degrading acceptance).  Its proposed token is discarded;
        partially-rejected garbage is overwritten just-in-time by the
        next round's feeds before their queries run."""
        def step(carry, i):
            d_cache, tok = carry
            lg, d_cache = draft_model.apply(
                draft_variables, tok[:, None], d_cache, p + i,
                method=draft_model.decode_step)
            nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            return (d_cache, nxt), nxt[0]
        (d_cache, _), d_toks = jax.lax.scan(
            step, (d_cache, y), jnp.arange(g + 1))
        return d_cache, d_toks[:g]                                # [g]

    def body(carry):
        t_cache, d_cache, y, p, out, emitted, rounds = carry
        d_cache, d_toks = draft_round(d_cache, y, p)
        # ONE target forward verifies y + all g draft tokens: logits[j]
        # predicts position p+j+1
        block = jnp.concatenate([y, d_toks])[None]                # [1, g+1]
        lg, t_cache = model.apply(variables, block, t_cache, p,
                                  method=model.decode_step)
        t_pred = jnp.argmax(lg[0], axis=-1).astype(jnp.int32)     # [g+1]
        match = t_pred[:g] == d_toks
        m = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((1,), bool)]))                      # 0..g
        # emitted this round: d_1..d_m then the target's own next token
        emit = jnp.where(jnp.arange(g + 1) < m,
                         jnp.concatenate([d_toks, jnp.zeros((1,), jnp.int32)]),
                         t_pred[jnp.minimum(m, g)])
        out = jax.lax.dynamic_update_slice(out, emit, (emitted,))
        y_next = t_pred[jnp.minimum(m, g)][None]
        return (t_cache, d_cache, y_next, p + m + 1, out,
                emitted + m + 1, rounds + 1)

    def cond(carry):
        emitted = carry[-2]
        return emitted < n

    (_, _, _, _, out, _, rounds) = jax.lax.while_loop(
        cond, body, (t_cache, d_cache, y0, jnp.int32(s_p), out0,
                     jnp.int32(1), jnp.int32(0)))
    toks = out[:n][None]                                          # [1, n]
    if eos_id is not None:
        # match generate's eos freeze: everything after the first eos is eos
        seen = jnp.cumsum(toks == eos_id, axis=1) > 0
        toks = jnp.where(seen, eos_id, toks)
    result = jnp.concatenate([prompt, toks], axis=1)
    if return_stats:
        # rounds = target forwards taken; (n-1)/rounds ~ tokens accepted
        # per verify — THE speculative health metric (perfect draft:
        # ceil((n-1)/(gamma+1)) rounds)
        return result, rounds
    return result
