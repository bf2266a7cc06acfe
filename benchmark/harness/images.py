"""Seeded images with the statistics of photographs, as an ImageNet folder.

``bench.py bench_input`` fills its JPEGs with uniform noise, which compresses
and decodes unlike a photograph. These have a 1/f amplitude spectrum (smooth
fields plus texture, the spectrum of natural scenes), correlated colour
channels, and a coarse layout and base colour tied to the class, so that the
label is learnable. The fields are made once per seed (an FFT each) and every
image mixes four of them, rolled: making 1,024 images has to cost a second or
two, because a run with a seed the cache has not seen pays for it in set-up.
At 500 x 375 a quality-90 baseline JPEG weighs 90 to 120 KB, as ImageNet's
do. Layout: ``root/c0000/000000.JPEG`` with 1,000 class directories, the
layout ``data/sources.imagenet_folder`` reads.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HEIGHT, WIDTH = 375, 500
NUM_CLASSES = 1000
ALPHA = 1.1      # amplitude ~ 1/f^ALPHA
CONTRAST = 0.16
QUALITY = 90


POOL = 24        # 1/f fields made per seed; every image mixes four of them


def field_pool(seed: int) -> np.ndarray:
    """``POOL`` unit-variance fields with a 1/f amplitude spectrum, float32
    [POOL, HEIGHT, WIDTH]: white noise shaped in the frequency domain."""
    fy = np.fft.fftfreq(HEIGHT)[:, None]
    fx = np.fft.rfftfreq(WIDTH)[None, :]
    filt = (1.0 / (np.sqrt(fy ** 2 + fx ** 2) + 1.0 / WIDTH) ** ALPHA)
    white = np.random.default_rng([seed, 104729]).standard_normal(
        (POOL, HEIGHT, WIDTH)).astype(np.float32)
    fields = np.fft.irfft2(np.fft.rfft2(white) * filt.astype(np.float32),
                           s=(HEIGHT, WIDTH)).astype(np.float32)
    return fields / fields.std(axis=(1, 2), keepdims=True)


def _bilinear(out: int, src: int) -> np.ndarray:
    """float32 [out, src]: the weights that stretch ``src`` samples over
    ``out`` pixels (pixel centres, edges clamped), as PIL's BILINEAR does."""
    at = np.clip((np.arange(out) + 0.5) * src / out - 0.5, 0, src - 1)
    lo = np.minimum(at.astype(int), src - 2)
    w = np.zeros((out, src), np.float32)
    w[np.arange(out), lo] = 1.0 - (at - lo)
    w[np.arange(out), lo + 1] = at - lo
    return w


STRETCH_Y = _bilinear(HEIGHT, 3)                       # [HEIGHT, 3]
STRETCH_X = np.ascontiguousarray(_bilinear(WIDTH, 4).T)  # [4, WIDTH]


class Scratch:
    """The arrays one thread makes its images in: fresh 2-MB arrays for every
    image fault their pages in under one kernel lock, and threads then queue."""

    def __init__(self) -> None:
        self.img = np.empty((3, HEIGHT, WIDTH), np.float32)
        self.plane = np.empty((HEIGHT, WIDTH), np.float32)


def _scaled_roll(out: np.ndarray, field: np.ndarray, dy: int, dx: int,
                 scale: np.float32) -> np.ndarray:
    """``out[:] = scale * np.roll(field, (dy, dx), (0, 1))`` with no array
    made on the way: a roll moves a field and leaves its spectrum alone."""
    for dst_y, src_y in ((slice(dy, None), slice(None, HEIGHT - dy)),
                         (slice(None, dy), slice(HEIGHT - dy, None))):
        for dst_x, src_x in ((slice(dx, None), slice(None, WIDTH - dx)),
                             (slice(None, dx), slice(WIDTH - dx, None))):
            np.multiply(field[src_y, src_x], scale, out=out[dst_y, dst_x])
    return out


def make_image(seed: int, index: int, label: int, pool: np.ndarray,
               scratch: Scratch) -> np.ndarray:
    """uint8 [HEIGHT, WIDTH, 3]: detail from (seed, index) - four pool fields,
    each rolled by its own random offset - the coarse layout and base colour
    from (seed, label): ``base + 0.18 * layout + contrast * (luminance + 0.35
    * colour)``. It runs 1,024 times in the set-up of a run whose seed the
    cache has not seen, so it passes over the pixels as few times as numpy
    allows, in the calling thread's ``scratch``. ``tests/test_images.py``
    holds the plain version it has to match."""
    rng = np.random.default_rng([seed, index])
    crng = np.random.default_rng([seed, 7919, label])
    picks = rng.choice(len(pool), 4, replace=False)
    offsets = [(int(rng.integers(HEIGHT)), int(rng.integers(WIDTH)))
               for _ in picks]
    layout = crng.uniform(0, 255, (3, 4, 3)).astype(np.uint8)
    base = crng.uniform(0.3, 0.7, 3).astype(np.float32)
    contrast = np.float32(CONTRAST * rng.uniform(0.8, 1.25))
    img, plane = scratch.img, scratch.plane             # [3, H, W], [H, W]
    # the 3 x 4 layout, already scaled and offset, stretched over the image
    low = base + np.float32(0.18) * (layout / np.float32(127.5) - 1)  # [3,4,3]
    np.matmul(np.einsum("yi,ijc->cyj", STRETCH_Y, low), STRETCH_X, out=img)
    # luminance from two fields on every channel, colour from one each on
    # red and blue
    for k, (dy, dx), channels, weight in zip(
            picks, offsets, ((0, 1, 2), (0, 1, 2), (0,), (2,)),
            (0.7071, 0.7071, 0.35, 0.35)):
        _scaled_roll(plane, pool[k], dy, dx, contrast * np.float32(weight))
        for c in channels:
            img[c] += plane
    np.clip(img, 0.0, 1.0, out=img)
    img *= 255
    return img.transpose(1, 2, 0).astype(np.uint8, order="C")


def for_each_image(seed: int, num_images: int, fn) -> list:
    """``fn(i, label, image)`` for images 0 .. ``num_images`` - 1 on every
    core, the results in order. Image ``i`` has label ``i % 1000``."""
    pool = field_pool(seed)
    local = threading.local()

    def one(i: int):
        if not hasattr(local, "scratch"):
            local.scratch = Scratch()
        label = i % NUM_CLASSES
        return fn(i, label, make_image(seed, i, label, pool, local.scratch))

    with ThreadPoolExecutor(os.cpu_count() or 4) as threads:
        return list(threads.map(one, range(num_images)))


def write_folder(root: str, *, seed: int, num_images: int) -> int:
    """Write ``num_images`` JPEGs under ``root``; returns the bytes written.
    Every class directory exists."""
    from PIL import Image

    for c in range(NUM_CLASSES):
        os.makedirs(os.path.join(root, f"c{c:04d}"), exist_ok=True)

    def save(i: int, label: int, image: np.ndarray) -> int:
        path = os.path.join(root, f"c{label:04d}", f"{i:06d}.JPEG")
        Image.fromarray(image).save(path, "JPEG", quality=QUALITY)
        return os.path.getsize(path)

    return sum(for_each_image(seed, num_images, save))
