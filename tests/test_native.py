"""Native (C++) host data-plane kernels: build, parity with numpy, wiring.

The native library is the rebuild's host-side native layer (SURVEY.md §1 L2:
the reference's native layer is CUDA/NCCL; ours is XLA on-device + these
kernels on-host). Parity tests pin native == numpy so either path is safe.
"""

import numpy as np
import pytest

from distributeddeeplearningspark_tpu.data import vision
from distributeddeeplearningspark_tpu.utils import native


def test_native_builds_and_loads():
    # g++ is baked into the image; the kernels must actually build here.
    assert native.available(), "native kernels failed to build/load"


def _rand_u8(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 256, shape).astype(np.uint8)


def test_failed_build_says_so_loudly(monkeypatch, tmp_path, caplog):
    """A sealed copy whose csrc/ does not compile falls back to numpy — at
    ERROR level with the compiler's words, not in a warning nobody reads."""
    import logging
    import subprocess

    def no_compiler(cmd, **kw):
        raise subprocess.CalledProcessError(
            1, cmd, stderr=b"dls_native.cc:1: fatal error: no such header")

    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))  # an empty home cache
    monkeypatch.setattr(subprocess, "run", no_compiler)
    with caplog.at_level(logging.ERROR,
                         logger="distributeddeeplearningspark_tpu.native"):
        assert native._build(native._SRC) is None
    (rec,) = caplog.records
    assert rec.levelno == logging.ERROR
    assert "did NOT build" in rec.getMessage()
    assert "no such header" in rec.getMessage()
    assert "numpy" in rec.getMessage()
    assert list((tmp_path / "dls_tpu").glob("*.so")) == []


def test_crop_flip_normalize_parity():
    imgs = _rand_u8((4, 12, 16, 3))
    ys = np.array([0, 1, 2, 3], np.int32)
    xs = np.array([3, 2, 1, 0], np.int32)
    flips = np.array([0, 1, 0, 1], np.uint8)
    mean, std = vision.IMAGENET_MEAN, vision.IMAGENET_STD
    got = native.crop_flip_normalize_batch(imgs, ys, xs, flips, (8, 10), mean, std)
    assert got.shape == (4, 8, 10, 3) and got.dtype == np.float32
    for i in range(4):
        ref = imgs[i, ys[i]:ys[i] + 8, xs[i]:xs[i] + 10]
        if flips[i]:
            ref = ref[:, ::-1]
        ref = (ref.astype(np.float32) / 255.0 - mean) / std
        np.testing.assert_allclose(got[i], ref, atol=1e-6)


def test_normalize_u8_batch_parity():
    imgs = _rand_u8((3, 6, 7, 3), seed=1)
    got = native.normalize_u8_batch(imgs, vision.IMAGENET_MEAN, vision.IMAGENET_STD)
    ref = (imgs.astype(np.float32) / 255.0 - vision.IMAGENET_MEAN) / vision.IMAGENET_STD
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_resize_bilinear_parity():
    img = np.random.default_rng(2).normal(0, 1, (17, 23, 3)).astype(np.float32)
    got = native.resize_bilinear(img, (8, 9))
    ref = vision.resize_bilinear(img, (8, 9))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    # upsampling too
    got_up = native.resize_bilinear(img, (30, 40))
    ref_up = vision.resize_bilinear(img, (30, 40))
    np.testing.assert_allclose(got_up, ref_up, atol=1e-5, rtol=1e-5)


def test_sum_into_parity():
    a = np.random.default_rng(3).normal(0, 1, (1 << 17,)).astype(np.float32)
    b = np.random.default_rng(4).normal(0, 1, (1 << 17,)).astype(np.float32)
    want = a + b
    got = native.sum_into(a.copy(), b)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_numpy_fallback_matches_native(monkeypatch):
    imgs = _rand_u8((2, 8, 8, 3), seed=5)
    ys = xs = np.zeros(2, np.int32)
    flips = np.array([1, 0], np.uint8)
    args = (imgs, ys, xs, flips, (8, 8), vision.IMAGENET_MEAN, vision.IMAGENET_STD)
    with_native = native.crop_flip_normalize_batch(*args)
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)
    assert not native.available()
    without = native.crop_flip_normalize_batch(*args)
    np.testing.assert_allclose(with_native, without, atol=1e-6)


def test_train_transform_uint8_standardizes():
    """uint8 inputs must come out unit-scaled AND standardized — including
    through the crop path (regression: crop path skipped /255)."""
    tf = vision.train_transform(size=8, seed=0)
    same = tf({"image": _rand_u8((8, 8, 3), seed=6), "label": np.int32(0)})["image"]
    cropped = tf({"image": _rand_u8((14, 14, 3), seed=7), "label": np.int32(0)})["image"]
    for out in (same, cropped):
        assert out.shape == (8, 8, 3) and out.dtype == np.float32
        # standardized pixels live in roughly [-3, 3]; unnormalized would be ~255
        assert np.abs(out).max() < 5.0


def test_eval_transform_uint8_standardizes():
    tf = vision.eval_transform(size=8)
    out = tf({"image": _rand_u8((12, 16, 3), seed=8)})["image"]
    assert out.shape == (8, 8, 3) and np.abs(out).max() < 5.0
    out_same = tf({"image": _rand_u8((8, 8, 3), seed=9)})["image"]
    assert np.abs(out_same).max() < 5.0


def test_crop_origin_bounds_checked():
    """ADVICE r1: invalid crop origins must raise, not heap-overread in C++."""
    imgs = _rand_u8((2, 12, 16, 3))
    flips = np.zeros(2, np.uint8)
    mean, std = vision.IMAGENET_MEAN, vision.IMAGENET_STD
    # y origin too large: 5 + 8 > 12
    with pytest.raises(ValueError, match="out of bounds"):
        native.crop_flip_normalize_batch(
            imgs, np.array([0, 5], np.int32), np.zeros(2, np.int32), flips,
            (8, 10), mean, std)
    # negative x origin
    with pytest.raises(ValueError, match="out of bounds"):
        native.crop_flip_normalize_batch(
            imgs, np.zeros(2, np.int32), np.array([-1, 0], np.int32), flips,
            (8, 10), mean, std)
    # crop larger than image
    with pytest.raises(ValueError, match="exceeds"):
        native.crop_flip_normalize_batch(
            imgs, np.zeros(2, np.int32), np.zeros(2, np.int32), flips,
            (13, 10), mean, std)


def test_rrc_flip_normalize_parity():
    """Fused crop→resize→flip→normalize == the numpy chain (crop the /255
    float frame, resize_bilinear, flip, standardize) to fp tolerance —
    up- and down-scaling crops, both flip states."""
    img = _rand_u8((37, 53, 3), seed=11)
    mean, std = vision.IMAGENET_MEAN, vision.IMAGENET_STD
    for region, flip, size in [
        ((3, 5, 20, 30), False, (16, 16)),   # downscale
        ((0, 0, 9, 7), True, (24, 24)),      # upscale
        ((10, 10, 16, 16), True, (16, 16)),  # identity resize
    ]:
        got = native.rrc_flip_normalize(img, region, flip, size, mean, std)
        assert got is not None and got.dtype == np.float32
        y, x, ch, cw = region
        ref = vision.resize_bilinear(
            img[y:y + ch, x:x + cw].astype(np.float32) / 255.0, size)
        if flip:
            ref = ref[:, ::-1]
        ref = (ref - mean) / std
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_rrc_region_bounds_checked():
    img = _rand_u8((16, 16, 3), seed=12)
    mean, std = vision.IMAGENET_MEAN, vision.IMAGENET_STD
    for bad in [(-1, 0, 8, 8), (0, 0, 17, 8), (10, 10, 8, 8), (0, 0, 0, 8)]:
        with pytest.raises(ValueError, match="out of bounds"):
            native.rrc_flip_normalize(img, bad, False, (8, 8), mean, std)


def test_train_transform_native_matches_numpy(monkeypatch):
    """The fused-native and numpy train paths must pick the SAME crop (same
    rng stream) and agree to fp tolerance — scheduling/native availability
    cannot change the augmented output."""
    ex = {"image": _rand_u8((40, 48, 3), seed=13), "label": np.int32(1)}
    tf = vision.train_transform(size=16, seed=3)
    with_native = tf(dict(ex))["image"]
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)
    without = tf(dict(ex))["image"]
    np.testing.assert_allclose(with_native, without, atol=1e-4, rtol=1e-4)


def test_a_call_fans_out_by_its_work_not_by_the_hosts_cores(monkeypatch):
    """One 224 x 224 x 3 example is too little work to start a thread for:
    ``map_parallel``'s pool, as wide as the host, calls the kernel once an
    image, and a thread an image and core cost more than the pixels."""
    lib = native._load()
    monkeypatch.delenv("DLS_NATIVE_THREADS", raising=False)
    cores = lib.dls_num_threads()
    example = 224 * 224 * 3
    assert lib.dls_plan_threads(224, 224 * 3) == 1      # its rows
    assert lib.dls_plan_threads(7, 32 * 224 * 3) == 1   # its row groups
    assert lib.dls_plan_threads(1, example) == 1
    assert lib.dls_plan_threads(0, example) == 1
    # a batch of them, and one large frame, still use the host
    assert lib.dls_plan_threads(256, example) == min(cores, 147)
    assert lib.dls_plan_threads(2048, 2048 * 3) == min(cores, 48)
    monkeypatch.setenv("DLS_NATIVE_THREADS", "1")
    assert lib.dls_plan_threads(256, example) == 1


def test_a_large_call_is_the_same_on_one_thread_and_on_many(monkeypatch):
    img = _rand_u8((700, 900, 3), seed=5)
    mean, std = vision.IMAGENET_MEAN, vision.IMAGENET_STD
    monkeypatch.delenv("DLS_NATIVE_THREADS", raising=False)
    assert native._load().dls_plan_threads(1024, 1024 * 3) > 1 \
        or native._load().dls_num_threads() == 1
    many = native.rrc_flip_normalize(img, (10, 20, 600, 800), True,
                                     (1024, 1024), mean, std)
    monkeypatch.setenv("DLS_NATIVE_THREADS", "1")   # read on every call
    one = native.rrc_flip_normalize(img, (10, 20, 600, 800), True,
                                    (1024, 1024), mean, std)
    assert many.tobytes() == one.tobytes()
