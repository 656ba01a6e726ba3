"""Image ops + image stage tests (OpenCV-parity semantics)."""
import numpy as np
import pytest

from mmlspark_tpu import Table
from mmlspark_tpu.io.image import (
    array_to_image_row,
    decode_image,
    encode_image_row,
    image_row_to_array,
    safe_read,
)
from mmlspark_tpu.ops import image as I
from mmlspark_tpu.ops.image_stages import (
    ImageSetAugmenter,
    ImageTransformer,
    ResizeImageTransformer,
    UnrollBinaryImage,
    UnrollImage,
)

from fuzzing import fuzz


def _rand_img(rng, h=16, w=12, c=3):
    return rng.integers(0, 255, size=(h, w, c)).astype(np.uint8)


@pytest.fixture
def img_table(rng):
    rows = [array_to_image_row(_rand_img(rng), origin=f"img{i}") for i in range(6)]
    return Table({"image": rows, "id": np.arange(6)})


class TestImageIO:
    def test_encode_decode_roundtrip(self, rng):
        row = array_to_image_row(_rand_img(rng))
        data = encode_image_row(row, "PNG")
        back = decode_image(data)
        np.testing.assert_array_equal(image_row_to_array(back), image_row_to_array(row))

    def test_safe_read_garbage(self):
        assert safe_read(b"not an image") is None
        assert safe_read(None) is None


class TestOps:
    def test_resize_shapes(self):
        b = np.zeros((2, 8, 8, 3), np.float32)
        out = I.resize(b, 4, 6)
        assert out.shape == (2, 4, 6, 3)

    def test_flip(self):
        b = np.arange(8, dtype=np.float32).reshape(1, 2, 4, 1)
        lr = np.asarray(I.flip(b, True, False))
        np.testing.assert_array_equal(lr[0, 0, :, 0], [3, 2, 1, 0])
        ud = np.asarray(I.flip(b, False, True))
        np.testing.assert_array_equal(ud[0, :, 0, 0], [4, 0])

    def test_color_convert_gray_matches_opencv_weights(self):
        bgr = np.array([[[[100.0, 50.0, 200.0]]]], np.float32)
        gray = float(np.asarray(I.color_convert(bgr, "bgr2gray"))[0, 0, 0, 0])
        assert gray == pytest.approx(0.114 * 100 + 0.587 * 50 + 0.299 * 200, rel=1e-5)

    def test_threshold_kinds(self):
        b = np.array([[[[10.0], [200.0]]]], np.float32)
        assert np.asarray(I.threshold(b, 100, 255, "binary")).ravel().tolist() == [0, 255]
        assert np.asarray(I.threshold(b, 100, 255, "trunc")).ravel().tolist() == [10, 100]

    def test_gaussian_kernel_normalized(self):
        k = I.gaussian_kernel(5, 1.2)
        assert k.shape == (5, 5)
        assert k.sum() == pytest.approx(1.0, abs=1e-6)

    def test_blur_preserves_constant(self):
        b = np.full((1, 8, 8, 3), 7.0, np.float32)
        out = np.asarray(I.gaussian_blur(b, 3, 1.0))
        np.testing.assert_allclose(out[0, 2:6, 2:6], 7.0, rtol=1e-5)

    def test_unroll_roundtrip(self):
        b = np.arange(24, dtype=np.float32).reshape(1, 2, 4, 3)
        flat = np.asarray(I.hwc_to_chw_flat(b))
        assert flat.shape == (1, 24)
        # CHW layout: first H*W entries are channel 0
        np.testing.assert_array_equal(flat[0, :8], b[0, :, :, 0].ravel())
        back = np.asarray(I.chw_flat_to_hwc(flat, 2, 4, 3))
        np.testing.assert_array_equal(back, b)


class TestImageStages:
    def test_resize_stage(self, img_table):
        out = ResizeImageTransformer(height=8, width=8).transform(img_table)
        r = out["image"][0]
        assert (r["height"], r["width"]) == (8, 8)

    def test_image_transformer_pipeline(self, img_table):
        t = ImageTransformer()
        t.resize(10, 10).center_crop(8, 8).flip()
        out = t.transform(img_table)
        r = out["image"][0]
        assert (r["height"], r["width"]) == (8, 8)

    def test_image_transformer_matches_numpy_flip(self, img_table):
        t = ImageTransformer()
        t.flip(flip_left_right=True)
        out = t.transform(img_table)
        src = image_row_to_array(img_table["image"][0])
        got = image_row_to_array(out["image"][0])
        np.testing.assert_array_equal(got, src[:, ::-1, :])

    def test_image_transformer_fuzz(self, img_table):
        t = ImageTransformer()
        t.resize(8, 8)
        fuzz(t, img_table)

    def test_mixed_shapes_grouped(self, rng):
        rows = [array_to_image_row(_rand_img(rng, 16, 16)),
                array_to_image_row(_rand_img(rng, 8, 8))]
        t = Table({"image": rows})
        out = ResizeImageTransformer(height=4, width=4).transform(t)
        assert all(r["height"] == 4 for r in out["image"])

    def test_none_rows_passthrough(self, rng):
        rows = [array_to_image_row(_rand_img(rng)), None]
        out = ResizeImageTransformer(height=4, width=4).transform(Table({"image": rows}))
        assert out["image"][1] is None

    def test_unroll_image(self, img_table):
        out = UnrollImage().transform(img_table)
        v = out["unrolled"][0]
        assert v.shape == (16 * 12 * 3,)
        src = image_row_to_array(img_table["image"][0]).astype(np.float64)
        np.testing.assert_allclose(v[: 16 * 12], src[:, :, 0].ravel())

    def test_unroll_binary_image(self, rng):
        img = _rand_img(rng, 8, 8)
        data = encode_image_row(array_to_image_row(img), "PNG")
        t = Table({"bytes": [data]})
        out = UnrollBinaryImage(height=4, width=4).transform(t)
        assert out["unrolled"][0].shape == (4 * 4 * 3,)

    def test_augmenter_doubles_rows(self, img_table):
        out = ImageSetAugmenter().transform(img_table)
        assert out.num_rows == 12


def test_pallas_fused_normalize_unroll_matches_xla():
    import jax.numpy as jnp

    from mmlspark_tpu.ops.image import hwc_to_chw_flat, normalize
    from mmlspark_tpu.ops.pallas_kernels import fused_normalize_unroll

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.random((3, 24, 24, 3)).astype(np.float32))
    got = fused_normalize_unroll(x, (0.5, 0.4, 0.3), (0.2, 0.3, 0.4))
    ref = hwc_to_chw_flat(normalize(x, (0.5, 0.4, 0.3), (0.2, 0.3, 0.4)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_unroll_image_stage_with_normalization():
    from mmlspark_tpu.ops.image_stages import UnrollImage
    from mmlspark_tpu.io.image import array_to_image_row

    rng = np.random.default_rng(12)
    rows = np.empty(2, dtype=object)
    for i in range(2):
        rows[i] = array_to_image_row(
            (rng.random((8, 8, 3)) * 255).astype(np.uint8)
        )
    t = Table({"image": rows})
    out = UnrollImage(mean=[127.5, 127.5, 127.5], std=[255.0, 255.0, 255.0]).transform(t)
    v = out["unrolled"][0]
    assert v.shape == (8 * 8 * 3,)
    assert -0.5 <= v.min() and v.max() <= 0.5


def test_pallas_fused_resize_normalize_matches_xla():
    """Interpret-mode parity of the fused cast+resize+normalize kernel vs
    the XLA composition it replaces (resize is the exact jax.image.resize
    bilinear via identity-resized weight matrices)."""
    import jax.numpy as jnp

    from mmlspark_tpu.ops.image import normalize, resize
    from mmlspark_tpu.ops.pallas_kernels import fused_resize_normalize

    rng = np.random.default_rng(5)
    x = rng.integers(0, 256, size=(3, 20, 16, 3), dtype=np.uint8)
    mean, std = (100.0, 110.0, 120.0), (50.0, 55.0, 60.0)
    got = fused_resize_normalize(jnp.asarray(x), 12, 10, mean, std)
    ref = normalize(resize(jnp.asarray(x, jnp.float32), 12, 10), mean, std)
    assert got.shape == (3, 12, 10, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-3, rtol=1e-4)


def test_pallas_fused_resize_normalize_identity_size():
    import jax.numpy as jnp

    from mmlspark_tpu.ops.pallas_kernels import fused_resize_normalize

    rng = np.random.default_rng(6)
    x = rng.integers(0, 256, size=(2, 8, 8, 3), dtype=np.uint8)
    got = fused_resize_normalize(jnp.asarray(x), 8, 8, (0.0,), (1.0,))
    np.testing.assert_allclose(np.asarray(got), x.astype(np.float32),
                               atol=1e-4)


def test_image_preprocess_pallas_matches_xla_path():
    """ImagePreprocess with use_pallas on/off must agree — the featurizer's
    device-side feed is identical either way."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.tpu_model import ImagePreprocess

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 256, size=(2, 30, 24, 3), dtype=np.uint8))
    mean = [103.5, 116.3, 123.7]
    std = [57.4, 57.1, 58.4]
    on = ImagePreprocess(16, 12, mean=mean, std=std, use_pallas=True)(x)
    off = ImagePreprocess(16, 12, mean=mean, std=std, use_pallas=False)(x)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               atol=1e-4, rtol=1e-4)


def test_image_preprocess_pallas_sharded_matches_xla_path():
    """The shard_map-wrapped fused kernel on a dp=8 mesh (the multi-chip
    variant promised by ImagePreprocess._pallas_wanted's auto mode) must
    agree with the XLA composition — per-shard Mosaic launches on a
    batch-sharded input, interpret mode here, same code path on chips."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.tpu_model import ImagePreprocess
    from mmlspark_tpu.parallel.mesh import batch_sharding, make_mesh

    mesh = make_mesh()  # all 8 virtual devices on the data axis
    rng = np.random.default_rng(9)
    xs = rng.integers(0, 256, size=(16, 30, 24, 3), dtype=np.uint8)
    x = jax.device_put(xs, batch_sharding(mesh, xs.ndim))
    mean = [103.5, 116.3, 123.7]
    std = [57.4, 57.1, 58.4]
    pre_on = ImagePreprocess(16, 12, mean=mean, std=std, use_pallas=True)
    pre_off = ImagePreprocess(16, 12, mean=mean, std=std, use_pallas=False)
    on = jax.jit(lambda b: pre_on(b, mesh=mesh))(x)
    off = jax.jit(lambda b: pre_off(b, mesh=mesh))(x)
    assert on.shape == (16, 16, 12, 3)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               atol=1e-4, rtol=1e-4)


def test_image_preprocess_sharded_fallbacks_stay_correct():
    """Multi-device layouts the per-shard kernel can't take — a batch not
    divisible by dp, or a mesh with data=1 — must fall back to the XLA
    composition, not error or replicate an unpartitionable kernel."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.tpu_model import ImagePreprocess
    from mmlspark_tpu.parallel.mesh import make_mesh

    pre = ImagePreprocess(16, 12, mean=[100.0], std=[50.0], use_pallas=True)
    ref = ImagePreprocess(16, 12, mean=[100.0], std=[50.0], use_pallas=False)
    rng = np.random.default_rng(10)

    # batch of 12 on a dp=8 mesh: 12 % 8 != 0 -> XLA path
    mesh = make_mesh()
    x = jnp.asarray(rng.integers(0, 256, (12, 30, 24, 3), np.uint8))
    np.testing.assert_allclose(np.asarray(pre(x, mesh=mesh)),
                               np.asarray(ref(x)), atol=1e-4, rtol=1e-4)

    # model-parallel-only mesh (data=1, 8 devices): XLA path
    mp_mesh = make_mesh(data=1, model=8)
    x2 = jnp.asarray(rng.integers(0, 256, (8, 30, 24, 3), np.uint8))
    np.testing.assert_allclose(np.asarray(pre(x2, mesh=mp_mesh)),
                               np.asarray(ref(x2)), atol=1e-4, rtol=1e-4)


def test_pallas_vmem_gate_and_identity_shortcut():
    """Oversized inputs must fall back to XLA, never attempt a Mosaic
    compile that would overflow VMEM; identity-size inputs skip the
    (pointless) identity matmuls."""
    from mmlspark_tpu.ops.pallas_kernels import _fits_vmem

    # a 4000x3000 photo: ~36MB uint8 + 144MB f32 cast >> 16MB VMEM
    assert not _fits_vmem((1, 4000, 3000, 3), 224, 224, 1)
    assert _fits_vmem((8, 256, 256, 3), 224, 224, 1)


def test_image_preprocess_mean_none_std_set_matches_xla():
    """mean=None disables normalization on BOTH paths — std alone must be
    ignored identically (a saved pipeline must score the same everywhere)."""
    import jax.numpy as jnp

    from mmlspark_tpu.models.tpu_model import ImagePreprocess

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 256, size=(2, 10, 8, 3), dtype=np.uint8))
    on = ImagePreprocess(6, 6, mean=None, std=[57.0, 57.0, 57.0],
                         use_pallas=True)(x)
    off = ImagePreprocess(6, 6, mean=None, std=[57.0, 57.0, 57.0],
                          use_pallas=False)(x)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), atol=1e-4)


def test_image_preprocess_unpickles_pre_use_pallas_state():
    """Pipelines pickled before use_pallas existed must keep loading."""
    from mmlspark_tpu.models.tpu_model import ImagePreprocess

    old_state = {"height": 8, "width": 8, "mean": None, "std": None}
    pre = ImagePreprocess.__new__(ImagePreprocess)
    pre.__setstate__(old_state)
    assert pre.use_pallas is None
    assert pre.key[-1] is None
    assert isinstance(pre._pallas_wanted(), bool)
