"""A seeded table of JPEG images that compress like photographs.

bench.py's generator (sizes round robin, PIL, quality 85) with other
pixels: uniform noise is the worst case for Huffman decode and three to
four times the bytes of a photograph.  Here each image is low-resolution
noise upsampled bicubically (smooth structure) plus a little per-pixel
noise (texture), which lands a quality-85 file at the 15-35% of raw size
that photographs have.  Image i depends on (seed, i) alone, so threads
can make the table in any order.
"""
from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor


def jpeg_blob(seed: int, i: int, h: int, w: int, coarse: int,
              noise: int, quality: int) -> bytes:
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng([seed, i])
    low = rng.integers(0, 256, size=(max(h // coarse, 2),
                                     max(w // coarse, 2), 3), dtype=np.uint8)
    smooth = np.asarray(Image.fromarray(low).resize((w, h), Image.BICUBIC),
                        np.int16)
    fine = rng.integers(-noise, noise + 1, size=(h, w, 3), dtype=np.int16)
    arr = np.clip(smooth + fine, 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def jpeg_blobs(n: int, sizes, seed: int, coarse: int = 8, noise: int = 6,
               quality: int = 85, threads: int = 8) -> list:
    """n JPEG byte strings, sizes (h, w) round robin."""
    def one(i):
        h, w = sizes[i % len(sizes)]
        return jpeg_blob(seed, i, h, w, coarse, noise, quality)

    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(one, range(n)))
