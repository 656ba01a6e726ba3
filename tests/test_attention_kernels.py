"""Fused-attention Pallas kernel: interpret-mode parity vs the XLA
composition, fallback routing, and gradient correctness (the backward is
the exact XLA recompute via custom_vjp)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

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
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=0.02, rtol=0.02)


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
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 640, 2, 64)), jnp.float32)
               for _ in range(3))

    def loss_fused(q, k, v):
        return jnp.sum(fused_attention(q, k, v, causal) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


@pytest.mark.parametrize("seq,causal", [(196, False), (200, True)])
def test_padded_seq_parity(seq, causal):
    """Non-block-multiple S pads up to the 128 grid with kv_valid
    masking (ViT's S=196 is the flagship case): forward AND gradients
    must match dense exactly — zero-padded K rows must not steal
    softmax mass, and padded Q rows must stay inert in the backward."""
    from mmlspark_tpu.ops import attention_kernels as ak

    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(2, seq, 2, 64)), jnp.float32)
               for _ in range(3))
    assert ak.kernel_ok(q), "padded path must take the kernel"
    got = fused_attention(q, k, v, causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **F32_TOL)

    def loss_fused(q, k, v):
        return jnp.sum(fused_attention(q, k, v, causal) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **GRAD_TOL)


def test_unkernelable_shapes_fall_back_to_xla():
    """Shapes the kernel can't take must route to the XLA branch — and
    that branch must actually RUN (not just the predicate)."""
    from mmlspark_tpu.ops import attention_kernels as ak

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
    from mmlspark_tpu.ops import attention_kernels as ak

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


# ---- no hidden fallback: a refused kernel raises ---------------------------

def _boom(*a, **kw):
    raise RuntimeError("Mosaic rejected this shape")


def test_forward_kernel_failure_raises(monkeypatch):
    """A shape `kernel_ok` admits whose pallas_call raises must surface
    the error — never quietly run the XLA composition instead (a run
    that "passes" that way says nothing about the kernel)."""
    from mmlspark_tpu.ops import attention_kernels as ak

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
               for _ in range(3))
    assert ak.kernel_ok(q)
    monkeypatch.setattr(ak, "_attention_pallas", _boom)
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        fused_attention(q, k, v, True)
    assert ak.kernel_ok(q)  # the predicate is shape-only: nothing cached


@pytest.mark.parametrize("kernel", ["_attention_bwd_dkdv",
                                    "_attention_bwd_dq"])
def test_backward_kernel_failure_raises(monkeypatch, kernel):
    from mmlspark_tpu.ops import attention_kernels as ak

    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.float32)
               for _ in range(3))
    monkeypatch.setattr(ak, kernel, _boom)
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        jax.grad(lambda q: jnp.sum(fused_attention(q, k, v, True) ** 2))(q)


@pytest.mark.parametrize("d,want", [(64, 64), (128, 128), (192, 192),
                                    (80, 128), (160, 256)])
def test_kernel_head_dim_rule(d, want):
    """64-multiples run native (what the described-v5e compiles in
    tests/test_aot_tpu_compile.py admit); the rest pad to the lane."""
    from mmlspark_tpu.ops import attention_kernels as ak

    assert ak._kernel_d(d) == want
