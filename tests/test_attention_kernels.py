"""Fused-attention Pallas kernels: interpret-mode parity vs the XLA
composition (forward, and the flash backward fused and split), the causal
tile schedule as a pure function, and fallback routing."""
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
    cell's shape and ViT's take it, a 128k context cannot."""
    assert ak._fused_bwd_fits(1024, 64, 2)
    assert ak._fused_bwd_fits(256, 64, 2)
    assert ak._fused_bwd_fits(2048, 64, 2)
    assert not ak._fused_bwd_fits(8192, 64, 2)
    assert not ak._fused_bwd_fits(131072, 128, 2)
    assert ak._kv_major(1024, 64, 2, 256) == 1024
    assert ak._kv_major(131072, 128, 2, 256) == 4096


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


def test_grad_at_256_wide_heads_takes_the_split_backward(monkeypatch):
    """The latent-attention training shape: q, k and v heads of 256 (the
    kernels run it native, 256 x 256 tiles).  At the cell's S = 4,096 the
    repo's own predicates give the kernel path, 2,048 keys a forward step
    and the SPLIT backward; here the same kernels at a length the
    interpreter can afford, the split pair forced as the long sequence
    takes it."""
    assert ak.attention_fits_vmem(4096, 256)
    assert ak._pick_blocks(4096, 256, True) == (256, 256)
    assert ak._kv_major(4096, 256, 2, 256) == 2048
    assert not ak._fused_bwd_fits(4096, 256)
    assert ak._kernel_d(256) == 256
    # lm-train's shape keeps its tiles and its fused backward
    assert ak._pick_blocks(1024, 64, True) == (512, 512)
    assert ak._fused_bwd_fits(1024, 64)
    q, k, v = _normal(13, (1, 512, 2, 256))
    assert ak.kernel_ok(q)
    monkeypatch.setattr(ak, "_fused_bwd_fits", lambda *a, **kw: False)
    _assert_fwd_and_grads_match(q, k, v, True)
