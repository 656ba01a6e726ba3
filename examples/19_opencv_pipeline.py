"""OpenCV-style pipeline image transformations.

Reference workload: "OpenCV - Pipeline Image Transformations.ipynb" —
chain ImageTransformer ops (resize, crop, color, blur, threshold, flip,
normalize) as pipeline stages over an image column, then unroll to a
flat feature vector for downstream ML (opencv/ImageTransformer.scala).

TPU-first difference worth seeing: the reference shells into OpenCV via
JNI per image; here every op is a batched XLA computation (and the
fused resize+normalize serving path has a Pallas kernel — see
ops/pallas_kernels.py), so a directory of images is ONE device program,
not N library calls.

Run: python examples/19_opencv_pipeline.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from mmlspark_tpu import Table
from mmlspark_tpu.core.pipeline import Pipeline
from mmlspark_tpu.io.image import array_to_image_row, image_row_to_array
from mmlspark_tpu.ops.image_stages import ImageTransformer, UnrollImage

FAST = bool(os.environ.get("MMLSPARK_EXAMPLE_FAST"))


def main():
    rng = np.random.default_rng(3)
    n = 4 if FAST else 12
    rows = [array_to_image_row(
        rng.integers(0, 256, size=(40 + 4 * i, 36 + 2 * i, 3),
                     dtype=np.uint8).astype(np.uint8),
        origin=f"synth://img{i}") for i in range(n)]
    table = Table({"image": rows})
    print(f"{n} images, mixed sizes "
          f"{[ (r['height'], r['width']) for r in rows[:3] ]}...")

    # the notebook's chain: standardize size -> crop -> smooth -> flip
    # (uint8 image rows throughout), then normalize + unroll to a flat
    # CHW vector in ONE fused stage (UnrollImage carries mean/std — the
    # featurizer-feed shape, Pallas-fused on chip)
    tr = ImageTransformer()
    tr.resize(32, 32).center_crop(28, 28).blur(2.0, 2.0).flip(
        flip_left_right=True)
    unroll = UnrollImage(input_col="image", output_col="features",
                         mean=[124.0, 116.0, 104.0],
                         std=[58.4, 57.1, 57.4])
    pipe = Pipeline([tr, unroll])
    out = pipe.fit(table).transform(table)

    img0 = image_row_to_array(out["image"][0])
    f0 = np.asarray(out["features"][0])
    print(f"after pipeline: shape {img0.shape}, dtype {img0.dtype}")
    print(f"unrolled features: {f0.shape} per image, "
          f"range [{f0.min():.2f}, {f0.max():.2f}]")
    assert img0.shape == (28, 28, 3) and img0.dtype == np.uint8
    assert f0.shape == (28 * 28 * 3,)
    # normalize really standardized the channels
    assert -4.0 < f0.min() < 0.0 < f0.max() < 4.0

    # same chain, flip disabled, must differ exactly by mirror symmetry
    tr2 = ImageTransformer()
    tr2.resize(32, 32).center_crop(28, 28).blur(2.0, 2.0)
    out2 = tr2.transform(table)
    img0_noflip = image_row_to_array(out2["image"][0])
    np.testing.assert_array_equal(img0, img0_noflip[:, ::-1, :])
    print("flip stage verified: mirrored output matches the unflipped run")


if __name__ == "__main__":
    main()
