"""Paged-attention kernel parity: the Pallas page-walk over FLAT pools
[NP, page, H*D] (interpret mode on CPU) must match the XLA gather
composition exactly — including trash-page garbage, recycled pages, and
per-slot positions mid-page."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.paged_attention import (
    _paged_pallas,
    _xla_paged,
    paged_decode_attention,
    paged_kernel_ok,
)


# (h, d): the shapes the kernel always had, and the serving cell's
# sixteen heads of 64 (H*D = 1024)
SHAPES = [(4, 128), (16, 64)]
SHAPE_IDS = ["h4_d128", "h16_d64"]


def _setup(b=3, h=4, d=128, np_=9, page=8, mp=4, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    # pools carry garbage EVERYWHERE (trash page 0 included) — masking,
    # not zero-init, must be what keeps dead positions invisible
    k_pool = jnp.asarray(rng.normal(size=(np_, page, h * d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(np_, page, h * d)), jnp.float32)
    # slot 0: 2 live pages, mid-page pos; slot 1: 1 page; slot 2: all MP
    table = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0], [4, 5, 6, 7]],
                        jnp.int32)
    pos = jnp.asarray([page + 3, 3, page * mp - 1], jnp.int32)
    return q, k_pool, v_pool, table, pos


@pytest.mark.parametrize("h,d", SHAPES, ids=SHAPE_IDS)
def test_kernel_matches_xla_gather(h, d):
    q, k_pool, v_pool, table, pos = _setup(h=h, d=d)
    got = np.asarray(_paged_pallas(q, k_pool, v_pool, table, pos))
    ref = np.asarray(_xla_paged(q, k_pool, v_pool, table, pos))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h,d", SHAPES, ids=SHAPE_IDS)
def test_kernel_matches_bf16_pools(h, d):
    q, k_pool, v_pool, table, pos = _setup(h=h, d=d, seed=1)
    q16 = q.astype(jnp.bfloat16)
    kp, vp = k_pool.astype(jnp.bfloat16), v_pool.astype(jnp.bfloat16)
    got = np.asarray(_paged_pallas(q16, kp, vp, table, pos))
    ref = np.asarray(_xla_paged(q16, kp, vp, table, pos))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)


def test_kernel_pos_zero_single_row():
    # a freshly admitted slot at pos 0: exactly one visible position
    q, k_pool, v_pool, table, _ = _setup(seed=2)
    pos = jnp.asarray([0, 0, 0], jnp.int32)
    got = np.asarray(_paged_pallas(q, k_pool, v_pool, table, pos))
    ref = np.asarray(_xla_paged(q, k_pool, v_pool, table, pos))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # with one visible position softmax is 1.0 on it: out == that v row
    for b in range(3):
        np.testing.assert_allclose(
            got[b].reshape(-1), np.asarray(v_pool)[int(table[b, 0]), 0],
            rtol=1e-5)


def test_dispatch_predicate():
    q, k_pool, *_ = _setup()
    assert paged_kernel_ok(q, k_pool)
    # a grouped-query pool of lane-wide heads goes to _page_walk_gqa ...
    assert paged_kernel_ok(q, k_pool[:, :, :2 * 128])
    # ... one of narrower heads to the gather
    assert not paged_kernel_ok(jnp.zeros((2, 4, 64), jnp.float32),
                               jnp.zeros((4, 8, 2 * 64), jnp.float32))
    q65 = jnp.zeros((2, 4, 65), jnp.float32)
    assert not paged_kernel_ok(q65, jnp.zeros((4, 8, 4 * 65), jnp.float32))
    # the flat width is what has to fill the lanes, not the head's own
    assert paged_kernel_ok(jnp.zeros((2, 16, 64), jnp.float32),
                           jnp.zeros((4, 8, 1024), jnp.float32))
    assert not paged_kernel_ok(jnp.zeros((2, 3, 64), jnp.float32),
                               jnp.zeros((4, 8, 192), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
def test_dispatch_predicate_wants_whole_sublane_tiles(dtype):
    # a page block is (page, H*D): pages of whole 8-row tiles, whatever
    # the pool's dtype (tests/test_aot_tpu_compile.py compiles them)
    q = jnp.zeros((2, 4, 128), jnp.float32)
    assert paged_kernel_ok(q, jnp.zeros((4, 8, 512), dtype))
    assert not paged_kernel_ok(q, jnp.zeros((4, 12, 512), dtype))


def test_public_entry_falls_back_and_matches():
    # a GQA pool (hkv=2 < h=4) fails paged_kernel_ok, so the public
    # entry must route to the XLA gather — and the gather must expand
    # the shared heads to match _gqa_expand's repeat semantics
    from mmlspark_tpu.models.transformer import (_cache_attention,
                                                 _gqa_expand)

    rng = np.random.default_rng(3)
    b, h, hkv, d, np_, page, mp = 2, 4, 2, 64, 5, 8, 2
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(np_, page, hkv * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(np_, page, hkv * d)), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([9, 14], jnp.int32)
    assert not paged_kernel_ok(q, kp)
    out = np.asarray(paged_decode_attention(q, kp, vp, table, pos))
    # reference: the model's own GQA gather branch (_cache_attention)
    ref = np.asarray(_cache_attention(
        q[:, None], _gqa_expand(kp[table].reshape(b, mp * page, hkv, d), h),
        _gqa_expand(vp[table].reshape(b, mp * page, hkv, d), h),
        pos[:, None], d))[:, 0]
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_vmem_gate_rejects_oversized_pages():
    # a page config whose working set exceeds the VMEM budget must route
    # to the gather (Mosaic would reject it), even though the dims align
    q = jnp.zeros((1, 32, 128), jnp.float32)
    huge = jnp.zeros((2, 2048, 32 * 128), jnp.float32)
    assert not paged_kernel_ok(q, huge)


def _int8_setup(b=2, h=4, d=64, np_=7, page=8, mp=3, seed=4):
    from mmlspark_tpu.ops.quant import quantize_kv_row

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    raw_k = jnp.asarray(rng.normal(size=(np_, page, h, d)), jnp.float32)
    raw_v = jnp.asarray(rng.normal(size=(np_, page, h, d)), jnp.float32)
    kq, ks = quantize_kv_row(raw_k)
    vq, vs = quantize_kv_row(raw_v)
    kq, vq = kq.reshape(np_, page, h * d), vq.reshape(np_, page, h * d)
    table = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    pos = jnp.asarray([page + 5, 2 * page + 4], jnp.int32)
    return q, kq, ks, vq, vs, table, pos


@pytest.mark.parametrize("h,d", [(4, 64), (16, 64)],
                         ids=["h4_d64", "h16_d64"])
def test_int8_kernel_matches_xla_gather(h, d):
    from mmlspark_tpu.ops.paged_attention import (_paged_pallas_int8,
                                                  _xla_paged_int8)

    q, kq, ks, vq, vs, table, pos = _int8_setup(h=h, d=d)
    got = np.asarray(_paged_pallas_int8(q, kq, ks, vq, vs, table, pos))
    ref = np.asarray(_xla_paged_int8(q, kq, ks, vq, vs, table, pos))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_int8_xla_gather_matches_cache_attention():
    """The int8 fallback must reproduce the model's _cache_attention
    quant factoring bit for bit on the gathered logical view."""
    from mmlspark_tpu.models.transformer import _cache_attention
    from mmlspark_tpu.ops.paged_attention import _xla_paged_int8

    q, kq, ks, vq, vs, table, pos = _int8_setup(seed=5)
    b, h, d = q.shape
    np_, page, _ = kq.shape
    mp = table.shape[1]
    got = np.asarray(_xla_paged_int8(q, kq, ks, vq, vs, table, pos))
    ref = np.asarray(_cache_attention(
        q[:, None],
        kq[table].reshape(b, mp * page, h, d),
        vq[table].reshape(b, mp * page, h, d),
        pos[:, None], d,
        k_scale=ks[table].reshape(b, mp * page, h),
        v_scale=vs[table].reshape(b, mp * page, h)))[:, 0]
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
