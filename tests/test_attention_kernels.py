"""Fused-attention Pallas kernels: interpret-mode parity vs the XLA
composition (forward, and the flash backward fused and split), the causal
tile schedule as a pure function, and fallback routing."""
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops import attention_kernels as ak
from mmlspark_tpu.ops.attention_kernels import (
    attention_fits_vmem,
    fused_attention,
)
from mmlspark_tpu.parallel.ring_attention import full_attention

# interpret mode reproduces the XLA composition at true f32, so the tight
# tolerance is the real test; the run on the chip (MXU rounding, ~1e-2)
# is chip_smoke.py's, with its own printed tolerances
F32_TOL = dict(atol=2e-5, rtol=2e-5)
GRAD_TOL = dict(atol=1e-4, rtol=1e-4)


def _normal(seed, shape):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for _ in range(3))


def _assert_fwd_and_grads_match(q, k, v, causal):
    """fused_attention against the XLA composition: output and all three
    gradients, at the file's tolerances."""
    got = fused_attention(q, k, v, causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **F32_TOL)
    g1 = jax.grad(lambda *a: jnp.sum(fused_attention(*a, causal) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(full_attention(*a, causal=causal) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


@pytest.fixture
def split_backward(monkeypatch):
    """Past `_fused_bwd_fits` the backward is the dK/dV + dQ pair: force
    it at a test's size."""
    monkeypatch.setattr(ak, "_fused_bwd_fits", lambda *a: False)


@pytest.fixture
def blocks(monkeypatch):
    """Set the tile rule for one test.  The kernels' jits key on shapes,
    not on the rule, so the caches go before and after."""
    def use(block_q, block_k):
        jax.clear_caches()
        monkeypatch.setattr(ak, "_pick_blocks",
                            lambda s, d, causal: (block_q, block_k))
    yield use
    jax.clear_caches()


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 256, 4, 64
    mk = lambda: jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_kernel_matches_xla(qkv, causal):
    q, k, v = qkv
    got = fused_attention(q, k, v, causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **F32_TOL)


def test_kernel_bf16_matches_xla_bf16(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    got = fused_attention(q, k, v, True)
    ref = full_attention(q, k, v, causal=True)
    assert got.dtype == q.dtype    # the kernels write what the model keeps
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=0.02, rtol=0.02)


def test_grad_bf16_matches_xla_bf16(qkv):
    """The model's own dtype: `out` is saved as a bf16 residual and
    dq/dk/dv leave the kernel in bf16 (delta and every accumulator f32).
    chip_smoke.py's tolerance for the same comparison on the chip."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)

    def grads(attn):
        return jax.grad(lambda *a: jnp.sum(attn(*a).astype(jnp.float32)
                                           ** 2), argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda *a: fused_attention(*a, True))
    ref = grads(lambda *a: full_attention(*a, causal=True))
    for a, b in zip(got, ref):
        assert a.dtype == jnp.bfloat16
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 5e-2 * np.abs(b).max()


def test_head_dim_padding_exact():
    """D=64 pads to the 128 lane inside the kernel; the pad must not leak
    into scores (scale) or output columns."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
               for _ in range(3))
    got = fused_attention(q, k, v, True)
    ref = full_attention(q, k, v, causal=True)
    assert got.shape == (1, 128, 2, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **F32_TOL)


def test_grad_matches_xla(qkv):
    q, k, v = (x[:1, :64] for x in qkv)

    def loss_fused(q, k, v):
        return jnp.sum(fused_attention(q, k, v, True) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


@pytest.mark.parametrize("causal", [False, True])
def test_grad_multiblock_matches_xla(causal):
    """Flash-backward parity across MULTIPLE q/k blocks (seq 640 forces
    the adaptive block_k path and > 1 block on both grids) — the
    dK/dV-accumulation and dQ-accumulation kernels must agree with the
    dense-XLA gradients, causal and not."""
    _assert_fwd_and_grads_match(*_normal(7, (2, 640, 2, 64)), causal)


@pytest.mark.parametrize("seq,causal", [(196, False), (200, True),
                                        (392, False), (1000, True)])
def test_padded_seq_parity(seq, causal):
    """Non-block-multiple S pads up to the 128 grid with kv_valid
    masking (ViT's S=196 is the flagship case): forward AND gradients
    must match dense exactly — zero-padded K rows must not steal
    softmax mass, and padded Q rows must stay inert in the backward."""
    q, k, v = _normal(11, (2, seq, 2, 64))
    assert ak.kernel_ok(q), "padded path must take the kernel"
    _assert_fwd_and_grads_match(q, k, v, causal)


def test_unkernelable_shapes_fall_back_to_xla():
    """Shapes the kernel can't take must route to the XLA branch — and
    that branch must actually RUN (not just the predicate)."""
    rng = np.random.default_rng(2)
    for shape in [(1, 136, 2, 64),   # S=136: not a 128-block multiple
                  (1, 128, 2, 32)]:  # d=32: lane padding too wasteful
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                   for _ in range(3))
        assert not ak.kernel_ok(q), shape
        got = fused_attention(q, k, v, True)
        ref = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_vmem_estimate_independent_of_seq_len():
    """The blockwise kernel streams K/V: VMEM use is O(block_q*block_k),
    so even very long contexts stay kernelable."""
    assert attention_fits_vmem(1024, 128)
    assert attention_fits_vmem(2048, 64)
    assert attention_fits_vmem(131072, 128)  # 128k context


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [640, 2048])  # 640 exercises adaptive block_k
def test_long_context_multiblock_parity(seq, causal):
    """S spanning multiple K blocks (the online-softmax recurrence across
    grid steps) must stay exact vs dense — causal AND non-causal (causal
    masking must not be what hides a cross-block accumulation bug)."""
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, seq, 1, 64)), jnp.float32)
               for _ in range(3))
    assert ak.kernel_ok(q)
    got = fused_attention(q, k, v, causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **F32_TOL)


def test_transformer_default_dispatch_uses_kernel(monkeypatch):
    """The single-TPU default-attention branch in TransformerLM, forced on
    the CPU backend (interpret mode) via the dispatch predicate: logits
    must match the XLA-attention model bit-for-tolerance."""
    from mmlspark_tpu.models import transformer as T

    dense = T.transformer_lm(vocab_size=64, embed_dim=128, num_layers=1,
                             num_heads=2, max_len=128, dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    toks = jax.random.randint(rng, (2, 128), 0, 64, jnp.int32)
    variables = dense.init({"params": rng}, toks, train=False)
    ref, _ = dense.apply(variables, toks, train=False)
    monkeypatch.setattr(T, "_single_tpu", lambda: True)
    got, _ = dense.apply(variables, toks, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


# ---- the causal schedule ----------------------------------------------------

@pytest.mark.parametrize("s,block_q,block_k,share", [
    (1024, 128, 512, 0.75),      # before PR 30: 12 of 16 tiles, 8 masked
    (1024, 512, 512, 0.75),      # `_pick_blocks` at the LM cell: 3, 2 masked
    (1024, 256, 256, 0.625),
    (1024, 128, 128, 0.5625),
    (1024, 128, 256, 0.625),
    (1024, 256, 128, 0.625),
    (640, 128, 128, 0.6),
    (256, 256, 256, 1.0),
    (64, 64, 64, 1.0),
])
def test_causal_tiles_are_exactly_the_tiles_with_something_to_show(
        s, block_q, block_k, share):
    """The mechanism's engagement count, and it is static: the kernels
    visit every tile with at least one unmasked element and no other,
    mask exactly those that also hold a masked one, and the key side's
    ranges (the fused backward's loops) and the tile-by-tile predicate
    (the split pair's grids) say the same."""
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = rows >= cols
    want = []
    for qi in range(s // block_q):
        for ki in range(s // block_k):
            t = seen[qi * block_q:(qi + 1) * block_q,
                     ki * block_k:(ki + 1) * block_k]
            if t.any():
                want.append((qi, ki, not t.all()))
    got = ak.causal_tiles(s, block_q, block_k)
    assert got == want
    by_key = []
    for ki in range(s // block_k):
        first, full = ak._q_range(ki * block_k, block_q, block_k)
        by_key += [(qi, ki, qi < full) for qi in range(first, s // block_q)]
    assert sorted(by_key) == sorted(want)
    for qi in range(s // block_q):
        for ki in range(s // block_k):
            visible, masked = ak._tile_kind(qi * block_q, ki * block_k,
                                            block_q, block_k, True, None)
            assert visible == ((qi, ki, True) in want
                               or (qi, ki, False) in want)
            if visible:
                assert (qi, ki, bool(masked)) in want
    visited = sum(block_q * block_k for _ in got) / (s * s)
    assert visited == pytest.approx(share)


def test_pick_blocks_follows_the_shape():
    """The tile rule is a function of the shape: the LM cell's 1,024 and
    the serving buckets' 512 take one 512 tile a side, ViT's padded 256
    one tile, 640 the 128 that tiles it, a short bucket itself; heads
    wider than the lane stay at 256."""
    assert ak._pick_blocks(1024, 64, True) == (512, 512)
    assert ak._pick_blocks(512, 64, True) == (512, 512)
    assert ak._pick_blocks(256, 64, False) == (256, 256)
    assert ak._pick_blocks(640, 64, True) == (128, 128)
    assert ak._pick_blocks(32, 64, True) == (32, 32)
    assert ak._pick_blocks(1024, 192, True) == (256, 256)
    masked = [t for t in ak.causal_tiles(1024, 512, 512) if t[2]]
    assert len(ak.causal_tiles(1024, 512, 512)) == 3 and len(masked) == 2


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [128, 256, 640, 1024])
def test_causal_schedule_parity(s, d):
    """Forward and gradients against the XLA composition at the lengths
    the schedule distinguishes: one tile (128, 256), the 128 tile that
    640 forces, and 1,024 (the LM cell: tiles under the diagonal beside
    tiles it crosses, the fused backward's two loops both non-empty)."""
    assert ak._fused_bwd_fits(s, d, 4)
    _assert_fwd_and_grads_match(*_normal(s + d, (1, s, 1, d)), True)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
def test_unequal_blocks_parity(blocks, monkeypatch, block_q, block_k,
                               fused):
    """block_q != block_k: a key tile crossed by the diagonal for two
    query blocks, or a query block crossing two key tiles."""
    blocks(block_q, block_k)
    monkeypatch.setattr(ak, "_fused_bwd_fits", lambda *a: fused)
    _assert_fwd_and_grads_match(*_normal(3, (1, 512, 2, 64)), True)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [200, 512, 640])
def test_split_backward_parity(split_backward, s, causal):
    """The dK/dV + dQ pair that sequences past the fused kernel's VMEM
    take: the same tiles (parked index maps, the mask on the diagonal's
    only), padded and not."""
    _assert_fwd_and_grads_match(*_normal(s, (1, s, 2, 64)), causal)


def test_streamed_major_blocks_parity(monkeypatch):
    """A sequence whose K/V do not fit one forward grid step streams them
    in major blocks: the online softmax carries m, l and o across grid
    steps, and a causal major block above the diagonal is skipped."""
    monkeypatch.setattr(ak, "_kv_major", lambda s, d, itemsize, bk: 2 * bk)
    for causal in (True, False):
        q, k, v = _normal(9, (1, 2048, 1, 64))
        got = fused_attention(q, k, v, causal)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **F32_TOL)


def test_fused_backward_limit():
    """The one-kernel backward is a function of (s, d, itemsize): the LM
    cell's shape, ViT's and GLM's take it, 16k keys and a 128k context
    cannot."""
    assert ak._fused_bwd_fits(1024, 64, 2)
    assert ak._fused_bwd_fits(256, 64, 2)
    assert ak._fused_bwd_fits(2048, 64, 2)
    assert ak._fused_bwd_fits(8192, 64, 2)
    assert ak._fused_bwd_fits(4096, 256, 2)
    assert not ak._fused_bwd_fits(16384, 64, 2)
    assert not ak._fused_bwd_fits(8192, 256, 2)
    assert not ak._fused_bwd_fits(131072, 128, 2)
    assert ak._kv_major(1024, 64, 2, 256) == 1024
    assert ak._kv_major(131072, 128, 2, 256) == 4096


@pytest.mark.parametrize("d,itemsize,last", [
    (64, 2, 9856), (128, 2, 9856), (256, 2, 4992),
    (64, 4, 5504), (256, 4, 2688)])
def test_fused_backward_boundary(d, itemsize, last):
    """The largest length on the 128 grid that fuses under
    `_FUSED_BWD_VMEM_BUDGET`, and the next multiple of its tile, which
    does not; at every length past the default's room the estimate is
    one the raised limit holds."""
    block = ak._pick_blocks(last, d, True)[0]
    assert ak._fused_bwd_fits(last, d, itemsize)
    assert not ak._fused_bwd_fits(last + block, d, itemsize)
    assert ak._fused_bwd_vmem(last, d, itemsize) > \
        ak._FUSED_BWD_VMEM_DEFAULT
    assert ak._FUSED_BWD_VMEM_BUDGET < ak._FUSED_BWD_VMEM_LIMIT


@pytest.mark.parametrize("bh,s,d,limit", [
    (96, 1024, 64, None), (80, 4096, 256, ak._FUSED_BWD_VMEM_LIMIT)],
    ids=["lm_train", "glm"])
def test_fused_backward_asks_for_vmem_only_past_the_default(bh, s, d,
                                                            limit):
    """lm-train's shape passes no compiler parameters, so its kernel is
    the one the default limit compiles; GLM's asks for
    `_FUSED_BWD_VMEM_LIMIT`."""
    q = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16)
    stat = jax.ShapeDtypeStruct((bh, s), jnp.float32)
    traced = jax.make_jaxpr(partial(ak._attention_bwd_dkdv_dq.__wrapped__,
                                    causal=True, scale=d ** -0.5))(
        q, q, q, q, stat, stat)
    params, = [e.params["compiler_params"] for e in traced.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    if limit is None:
        assert not params
    else:
        assert params["mosaic_tpu"].vmem_limit_bytes == limit


# ---- no hidden fallback: a refused kernel raises ---------------------------

def _boom(*a, **kw):
    raise RuntimeError("Mosaic rejected this shape")


def test_forward_kernel_failure_raises(monkeypatch):
    """A shape `kernel_ok` admits whose pallas_call raises must surface
    the error — never quietly run the XLA composition instead (a run
    that "passes" that way says nothing about the kernel)."""
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
               for _ in range(3))
    assert ak.kernel_ok(q)
    monkeypatch.setattr(ak, "_attention_pallas", _boom)
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        fused_attention(q, k, v, True)
    assert ak.kernel_ok(q)  # the predicate is shape-only: nothing cached


@pytest.mark.parametrize("kernel,fused", [
    ("_attention_bwd_dkdv_dq", True),
    ("_attention_bwd_dkdv", False),
    ("_attention_bwd_dq", False)])
def test_backward_kernel_failure_raises(monkeypatch, kernel, fused):
    """Every backward kernel there is: the fused one where a head's dQ
    fits, the split pair past it."""
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
               for _ in range(3))
    monkeypatch.setattr(ak, "_fused_bwd_fits", lambda *a: fused)
    monkeypatch.setattr(ak, kernel, _boom)
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        jax.grad(lambda q: jnp.sum(fused_attention(q, k, v, True) ** 2))(q)


@pytest.mark.parametrize("d,want", [(64, 64), (128, 128), (192, 192),
                                    (80, 128), (160, 256)])
def test_kernel_head_dim_rule(d, want):
    """64-multiples run native (what the described-v5e compiles in
    tests/test_aot_tpu_compile.py admit); the rest pad to the lane."""
    assert ak._kernel_d(d) == want


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "split"])
def test_grad_at_256_wide_heads(monkeypatch, fused):
    """The latent-attention training shape: q, k and v heads of 256 (the
    kernels run it native, 256 x 256 tiles).  At the cell's S = 4,096 the
    repo's own predicates give the kernel path, 2,048 keys a forward step
    and the ONE-KERNEL backward (at the raised VMEM limit); here the same
    kernels at a length the interpreter can afford, by the predicate, and
    the split pair forced as a sequence past the limit takes it."""
    assert ak.attention_fits_vmem(4096, 256)
    assert ak._pick_blocks(4096, 256, True) == (256, 256)
    assert ak._kv_major(4096, 256, 2, 256) == 2048
    assert ak._fused_bwd_fits(4096, 256)
    assert ak._kernel_d(256) == 256
    # lm-train's shape keeps its tiles and its fused backward
    assert ak._pick_blocks(1024, 64, True) == (512, 512)
    assert ak._fused_bwd_fits(1024, 64)
    q, k, v = _normal(13, (1, 512, 2, 256))
    assert ak.kernel_ok(q)
    if fused:
        assert ak._fused_bwd_fits(512, 256, q.dtype.itemsize)
    else:
        monkeypatch.setattr(ak, "_fused_bwd_fits", lambda *a, **kw: False)
    _assert_fwd_and_grads_match(q, k, v, True)


# ---- what the training kernels share with the admission kernel -------------
def _masked_before(sc, q0, k0, q_axis, causal, kv_valid):
    """`_masked` as the training kernels had it before the admission
    kernel shared its definition (PR 33's tree), word for word."""
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1 - q_axis)
    mask = None
    if causal:
        mask = (q0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape,
                                              q_axis)) >= kpos
    if kv_valid is not None:
        kv_mask = kpos < kv_valid
        mask = kv_mask if mask is None else (mask & kv_mask)
    return jnp.where(mask, sc, ak._NEG_INF)


@pytest.mark.parametrize("q_axis", [0, 1])
@pytest.mark.parametrize("causal,kv_valid", [(True, None), (True, 200),
                                             (False, 200)])
def test_training_mask_traces_as_before(q_axis, causal, kv_valid):
    """`_hide` took a window and thin positions for the admission
    kernel; what the training kernels trace through `_masked` is the
    same operations in the same order as before (one jaxpr, so one
    Mosaic module: `lm-train` and `glm-train-moe` run the device code
    they ran), on a forward tile (queries down) and a transposed one."""
    sc = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    at = jax.ShapeDtypeStruct((), jnp.int32)
    now, before = (str(jax.make_jaxpr(
        lambda s, q0, k0: fn(s, q0, k0, q_axis, causal, kv_valid))(
            sc, at, at)) for fn in (ak._masked, _masked_before))
    assert now == before


@pytest.mark.parametrize("s,d,itemsize,block_k", [
    (1024, 64, 2, 512), (4096, 256, 2, 256), (4096, 128, 2, 512),
    (8192, 64, 2, 512), (8192, 128, 4, 512), (2048, 256, 4, 256),
    (640, 64, 2, 128)])
def test_training_major_block_is_as_before(s, d, itemsize, block_k):
    """`_kv_major` took a v width and a budget for the admission
    kernel; with neither given it is the training forward's rule: K and
    V of one width, double-buffered, in half the image budget."""
    d_l = ak._pad_up(d, 128)
    n = s // block_k
    before = next((m * block_k for m in range(n, 0, -1) if n % m == 0
                   and 4 * m * block_k * d_l * itemsize
                   <= ak.PALLAS_IMAGE_VMEM_BUDGET // 2), block_k)
    assert ak._kv_major(s, d, itemsize, block_k) == before


# ---- the admission flash forward (serving's prefill) -----------------------
def _prefill_qkv(seed, b, s, hkv, group, d, dv, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype) for shape in
                 ((b, s, hkv * group, d), (b, s, hkv, d), (b, s, hkv, dv)))


def _prefill_lengths(kind, s, group):
    """The lengths of a batch by name; block edges are the picked
    tile's."""
    edge = ak._pick_prefill_blocks(s, group)[0]
    return {"none": None, "full": [s, s], "ragged": [s, s // 2 + 3, 1, 0],
            "one": [1, 1], "edge": [min(edge, s), min(2 * edge, s)],
            "edge+1": [min(edge + 1, s), min(2 * edge + 1, s)]}[kind]


def _assert_prefill_matches(q, k, v, window, lengths, tol=F32_TOL):
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    assert ak.prefill_attention_ok(q, v)
    got = np.asarray(ak.prefill_attention(q, k, v, window, lens),
                     np.float32)
    want = np.asarray(ak._xla_prefill_attention(q, k, v, window, lens))
    np.testing.assert_allclose(got, want, **tol)
    for row, n in enumerate(lengths or []):
        assert not got[row, n:].any()          # the padding: exactly zero


_PREFILL_S = [128, 256, 1024, 2048]
_PREFILL_LENGTHS = ["none", "full", "ragged", "one", "edge", "edge+1"]
# every s with every kind of lengths; head widths, group and window taken
# in turn so that each pair of them meets
_PREFILL_CASES = [
    (s, [(128, 128), (192, 128)][(i + j) % 2], [1, 6, 9][(i + j // 2) % 3],
     [None, 512, 4096][(2 * i + j) % 3], kind)
    for i, s in enumerate(_PREFILL_S)
    for j, kind in enumerate(_PREFILL_LENGTHS)]


@pytest.mark.parametrize(
    "s,dd,group,window,kind", _PREFILL_CASES,
    ids=[f"s{s}-d{dd[0]}-g{g}-w{w}-{kind}"
         for s, dd, g, w, kind in _PREFILL_CASES])
def test_prefill_kernel_matches_its_composition(s, dd, group, window, kind):
    """The admission kernel against `_xla_prefill_attention` over the
    buckets' lengths, both head shapes, grouped heads packed into a
    step, no window, a window inside the bucket and one past it, and
    the prompt's own length: whole, ragged in one batch (a row of 0
    among them), 1, on a block's edge and one past it."""
    lengths = _prefill_lengths(kind, s, group)
    b = 2 if lengths is None else len(lengths)
    q, k, v = _prefill_qkv(s + group, b, s, 1, group, *dd)
    _assert_prefill_matches(q, k, v, window, lengths)


@pytest.mark.parametrize("window", [None, 300])
@pytest.mark.parametrize("blocks", [(128, 128, 3), (256, 128, 1),
                                    (128, 256, 3)])
def test_prefill_kernel_streams_major_blocks(monkeypatch, blocks, window):
    """Past what stays resident the keys stream in major blocks, the
    statistics carried in scratch: 256 keys a step here, unequal tiles,
    and the index maps park where a step shows nothing."""
    monkeypatch.setattr(ak, "_pick_prefill_blocks", lambda *a: blocks)
    monkeypatch.setattr(ak, "_kv_major", lambda *a, **kw: 256)
    ak._prefill_attention_pallas.clear_cache()
    try:
        q, k, v = _prefill_qkv(5, 3, 1024, 2, 3, 128, 128)
        _assert_prefill_matches(q, k, v, window, [1000, 257, 1])
    finally:
        ak._prefill_attention_pallas.clear_cache()


def test_prefill_kernel_at_bf16():
    """bf16 in, bf16 out, on both arms."""
    q, k, v = _prefill_qkv(3, 2, 512, 1, 2, 192, 128, jnp.bfloat16)
    lens = jnp.asarray([512, 200], jnp.int32)
    got = ak.prefill_attention(q, k, v, None, lens)
    assert got.dtype == jnp.bfloat16
    assert ak.prefill_attention(q, k, v, None, lens,
                                kernel=False).dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ak._xla_prefill_attention(q, k, v, None, lens)),
        atol=3e-2)


def _prefill_tiles(s, length, window, block_q, block_k):
    """[(query block, key block, masked)] of every tile the admission
    kernel computes for a row of `length` own positions in a bucket of
    s, by the arithmetic the kernel's loops run on (`_prefill_k_range`
    a query block that holds an own row): the schedule as a list, for
    the brute-force picture to be held against."""
    tiles = []
    for qi in range(min(s, length + block_q - 1) // block_q):
        r0 = qi * block_q
        a, b, c, n = ak._prefill_k_range(r0, min(r0 + block_q, length),
                                         block_k, window)
        tiles += [(qi, ki, not b <= ki < c) for ki in range(a, n)]
    return tiles


@pytest.mark.parametrize("s,block_q,block_k,window", [
    (1024, 512, 512, None), (1024, 256, 256, None), (1024, 256, 256, 512),
    (1024, 128, 128, 512), (1024, 128, 256, 300), (1024, 256, 128, 512),
    (1024, 512, 512, 512), (2048, 512, 512, 4096), (256, 256, 256, 512),
    (64, 64, 64, None), (1024, 128, 512, 1), (1024, 512, 128, 129),
])
def test_prefill_tiles_are_exactly_the_tiles_with_something_to_show(
        s, block_q, block_k, window):
    """The admission schedule is a pure function of the bucket, the
    prompt's length, the window and the tile: every tile in which an own
    row (one under the length) sees a key and no other, masked exactly
    where such a row also has a key hidden, at every length that matters
    (none, one, around every block's edge, the bucket's)."""
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = rows >= cols
    if window is not None:
        seen &= cols > rows - window
    lengths = {0, 1, s // 2 + 7, s - 1, s}
    for edge in range(block_q, s, block_q):
        lengths |= {edge - 1, edge, edge + 1}
    for length in sorted(lengths):
        want = []
        for qi in range(s // block_q):
            own = slice(qi * block_q, min((qi + 1) * block_q, length))
            for ki in range(s // block_k):
                t = seen[own, ki * block_k:(ki + 1) * block_k]
                if t.any():
                    want.append((qi, ki, not t.all()))
        assert _prefill_tiles(s, length, window, block_q,
                              block_k) == want, length
    # with everything shown it is the training schedule
    if window is None:
        assert _prefill_tiles(s, s, None, block_q, block_k) == \
            ak.causal_tiles(s, block_q, block_k)


# (query heads a KV head, window, q/k and v head widths) of the two
# serving cells' admission layers and the buckets each admits
# (tests/test_aot_tpu_compile.py holds the head shapes to the cells'
# configurations)
_CELL_KINDS = {"laguna_full": (6, None, (128, 128), 128, 4096),
               "laguna_window": (9, 512, (128, 128), 128, 4096),
               "longcat": (1, None, (192, 128), 256, 8192)}
_CELL_BUCKETS = [(kind, bucket) for kind, (_g, _w, _dd, lo, hi)
                 in _CELL_KINDS.items()
                 for bucket in (128, 256, 512, 1024, 2048, 4096, 8192)
                 if lo <= bucket <= hi]
_CELL_IDS = [f"{kind}-{bucket}" for kind, bucket in _CELL_BUCKETS]


def _brute_force_tiles(s, length, window, block_q, block_k):
    """[(query block, key block, masked)]: every tile in which a row
    under `length` sees a key, masked where such a row also has one
    hidden, from the s x s picture of who sees whom."""
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = rows >= cols
    if window is not None:
        seen &= cols > rows - window
    own = rows < length
    shape = (s // block_q, block_q, s // block_k, block_k)
    shown = (seen & own).reshape(shape).any(axis=(1, 3))
    hidden = (~seen & own).reshape(shape).any(axis=(1, 3))
    return [(int(qi), int(ki), bool(hidden[qi, ki]))
            for qi, ki in zip(*np.nonzero(shown))]


def _lengths_that_matter(s, block_q):
    """1, around the first and a middle tile's edge, the bucket."""
    mid = (s // block_q // 2) * block_q
    return sorted({1, s} | {n for edge in (block_q, mid)
                            for n in (edge - 1, edge, edge + 1)
                            if 1 <= n <= s})


@pytest.mark.parametrize("window", [None, 512])
@pytest.mark.parametrize("s", [128, 256, 512, 1024, 2048, 4096, 8192])
def test_prefill_schedule_at_the_picked_tiles(s, window):
    """The schedule at the tiles the rule picks (grouped heads packed,
    and heads of their own) over every bucket a cell admits, with and
    without Laguna's window, against the brute-force picture."""
    for group in (1, 6):
        block_q, block_k, _pack = ak._pick_prefill_blocks(s, group)
        for length in _lengths_that_matter(s, block_q):
            assert _prefill_tiles(s, length, window, block_q, block_k) \
                == _brute_force_tiles(s, length, window, block_q,
                                      block_k), (group, length)


@pytest.mark.parametrize("kind,bucket", _CELL_BUCKETS, ids=_CELL_IDS)
def test_prefill_tile_counts_are_the_schedules_lengths(kind, bucket):
    """What the batcher counts a request (`prefill_tile_counts`: the
    prompt's own tiles and the bucket's) is the length of the schedule
    the kernel runs (`_prefill_tiles`, which the tests above hold to
    the picture of who sees whom), at every length that matters and a
    few dozen drawn ones."""
    group, window, dd, _lo, _hi = _CELL_KINDS[kind]
    block_q, block_k, _pack = ak._pick_prefill_blocks(bucket, group)
    whole = len(_prefill_tiles(bucket, bucket, window, block_q, block_k))
    drawn = np.random.default_rng(bucket + group).integers(1, bucket + 1, 40)
    for length in [0, *_lengths_that_matter(bucket, block_q), *drawn]:
        length = int(length)
        assert ak.prefill_tile_counts(bucket, length, window, group, *dd) \
            == (len(_prefill_tiles(bucket, length, window, block_q,
                                   block_k)), whole), length


@pytest.mark.parametrize("s,group,dd,itemsize", [
    (1000, 6, (128, 128), 2),      # a bucket cut at a max_len of no tiles
    (200, 1, (192, 128), 2), (4100, 9, (128, 128), 2),
    (1024, 6, (64, 64), 2),        # a head the contraction does not take
    (1024, 1, (192, 64), 2),
    (8192, 1, (512, 512), 4),      # a grid step past the VMEM asked
])
def test_prefill_tile_counts_where_the_kernel_declines(s, group, dd,
                                                        itemsize):
    """No tile is counted at a shape the kernel does not take: the
    count and the dispatch ask one test (`_prefill_shape_ok`), and a
    bucket equal to a `max_len` that no block tiles is such a shape,
    not an error."""
    q = jax.ShapeDtypeStruct((1, s, group, dd[0]),
                             jnp.bfloat16 if itemsize == 2 else jnp.float32)
    v = jax.ShapeDtypeStruct((1, s, 1, dd[1]), q.dtype)
    assert not ak.prefill_attention_ok(q, v)
    for window in (None, 512):
        assert ak.prefill_tile_counts(s, s // 2, window, group, *dd,
                                      itemsize=itemsize) == (0, 0)


# what a score tile may hold by cell: an instance of the kernel compiles
# for a described v5e in at most 0.2 s at Laguna's shapes and 0.5 s at
# LongCat's up to these (PERF.md section 6, PR 35: 0.15 s at 128 x 256
# keys x 3 heads and half as much again at 512 keys, 0.31-0.43 at
# 512 x 512 with one head; the parent's 0.06-0.16)
_CELL_TILE = {"laguna_full": (384, 96 * 1024),
              "laguna_window": (384, 96 * 1024),
              "longcat": (512, 256 * 1024)}


@pytest.mark.parametrize("kind,bucket", _CELL_BUCKETS, ids=_CELL_IDS)
def test_prefill_tiles_stay_inside_the_setup_budget(kind, bucket):
    """The compile budget, held by what a CPU can hold it by: compile
    seconds follow a score tile's lanes and area, so at every bucket of
    both cells' admissions the picked tile stays within the rule's
    lanes, its key rows within 256 or a query block's, and both within
    the widest tile whose compile was measured inside the cell's
    budget."""
    group, _window, _dd, _lo, _hi = _CELL_KINDS[kind]
    block_q, block_k, pack = ak._pick_prefill_blocks(bucket, group)
    lanes, area = pack * block_q, block_k * pack * block_q
    assert group % pack == 0 and bucket % block_q == bucket % block_k == 0
    assert lanes <= ak._PREFILL_LANES
    assert block_k <= max(block_q, ak._PREFILL_KEYS)
    cell_lanes, cell_area = _CELL_TILE[kind]
    assert lanes <= cell_lanes and area <= cell_area
    # the rule widens with the bucket and never narrows
    if bucket > 128:
        bq, bk, _p = ak._pick_prefill_blocks(bucket // 2, group)
        assert bq * bk <= block_q * block_k


def test_pick_prefill_blocks_follows_the_shape():
    """The tile and the packing are functions of the bucket and the
    group: within 512 lanes, key tiles of 256 rows or a query block's,
    LongCat's 64 heads of their own at 512 x 512, Laguna's six (full)
    and nine (window) query heads a KV head three to a step at 128 x
    256, short buckets one tile, a large group its largest divisor that
    fits; both cells' longest buckets keep K and V resident under the
    VMEM the call asks for."""
    assert ak._pick_prefill_blocks(8192, 1) == (512, 512, 1)
    assert ak._pick_prefill_blocks(4096, 1) == (512, 512, 1)
    assert ak._pick_prefill_blocks(512, 1) == (512, 512, 1)
    assert ak._pick_prefill_blocks(384, 1) == (128, 128, 1)
    assert ak._pick_prefill_blocks(256, 1) == (256, 256, 1)
    assert ak._pick_prefill_blocks(4096, 2) == (256, 256, 2)
    assert ak._pick_prefill_blocks(4096, 6) == (128, 256, 3)
    assert ak._pick_prefill_blocks(4096, 9) == (128, 256, 3)
    assert ak._pick_prefill_blocks(1024, 9) == (128, 256, 3)
    assert ak._pick_prefill_blocks(128, 9) == (128, 128, 3)
    assert ak._pick_prefill_blocks(64, 6) == (64, 64, 1)
    assert ak._pick_prefill_blocks(1024, 64) == (128, 256, 4)
    assert ak._kv_major(8192, 192, 2, 512, 128,
                        ak._PREFILL_KV_BUDGET) == 8192
    assert ak.prefill_attention_vmem(8192, 192, 128, 1) \
        <= ak._PREFILL_VMEM_BUDGET
    assert ak.prefill_attention_vmem(4096, 128, 128, 9) \
        <= ak._PREFILL_VMEM_BUDGET
