"""ctypes loader for the native (C++) host data-plane kernels.

The reference's native layer is CUDA/NCCL linked through torch/Horovod; the
rebuild's device-side native layer is XLA:TPU itself (SURVEY.md §1 L2). This
module owns the *host-side* native layer: csrc/dls_native.cc, compiled to a
shared library and called through ctypes (pybind11 is not in the image; ctypes
releases the GIL around every call, so these kernels parallelize for real
under the prefetch thread).

Loading strategy: use a prebuilt ``_dls_native*.so`` next to this package if
present (none is committed; ``*.so`` is git-ignored), else build one from
``csrc/`` on first use with the system ``g++`` (cached under
``~/.cache/dls_tpu``, so a sealed copy with an empty home builds once per
machine). Every entry point has a numpy fallback with identical semantics; a
failed build or load logs an ERROR, :func:`available` says which path is
live (``chip_smoke.py`` carries it in its result line), and the test suite
pins native == numpy bit-for-bit where exactness is defined.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile

import numpy as np

logger = logging.getLogger("distributeddeeplearningspark_tpu.native")

_CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc")
_SRC = [
    os.path.join(_CSRC_DIR, "dls_native.cc"),
    os.path.join(_CSRC_DIR, "dls_jpeg.cc"),
]
_LIB: ctypes.CDLL | None = None
_TRIED = False

#: dls_jpeg.cc return codes
_JPEG_OK = 0
_JPEG_UNSUPPORTED = -2

_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")


def _build(srcs: list[str]) -> str | None:
    """Compile csrc → cached .so keyed by source hashes; None if no compiler."""
    h = hashlib.sha256()
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()[:16]
    cache_dir = os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")), "dls_tpu"
    )
    out = os.path.join(cache_dir, f"_dls_native_{digest}.so")
    if os.path.exists(out):
        return out
    os.makedirs(cache_dir, exist_ok=True)
    # unique per-builder temp name (mkstemp), atomic rename into the cache:
    # concurrent builders each link their own file and the last rename wins
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=cache_dir)
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread", "-o", tmp, *srcs]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        logger.error(
            "native host kernels did NOT build from %s (%s)%s — falling "
            "back to the numpy implementations; decode/augment will be "
            "several times slower (available() reports False)",
            _CSRC_DIR, e,
            ": " + detail.decode(errors="replace")[-400:] if detail else "")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return out


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.dls_version.restype = ctypes.c_int
    lib.dls_num_threads.restype = ctypes.c_int
    lib.dls_plan_threads.restype = ctypes.c_int
    lib.dls_plan_threads.argtypes = [ctypes.c_int64, ctypes.c_int64]
    lib.dls_crop_flip_normalize_batch.argtypes = [
        _u8p, ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        _i32p, _i32p, _u8p, ctypes.c_int, ctypes.c_int, _f32p, _f32p, _f32p,
    ]
    lib.dls_normalize_u8_batch.argtypes = [
        _u8p, ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        _f32p, _f32p, _f32p,
    ]
    lib.dls_resize_bilinear.argtypes = [
        _f32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, _f32p,
    ]
    lib.dls_rrc_flip_normalize.argtypes = [
        _u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, _f32p, _f32p, _f32p,
    ]
    lib.dls_rrc_flip_normalize_varbatch.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), _i32p, _i32p, ctypes.c_int,
        _i32p, _i32p, _i32p, _i32p, _u8p, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int, _f32p, _f32p, _f32p,
    ]
    lib.dls_sum_into_f32.argtypes = [_f32p, _f32p, ctypes.c_int64]
    lib.dls_jpeg_info.restype = ctypes.c_int
    lib.dls_jpeg_info.argtypes = [
        _u8p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.dls_jpeg_decode.restype = ctypes.c_int
    lib.dls_jpeg_decode.argtypes = [_u8p, ctypes.c_int64, _u8p, ctypes.c_int64]
    lib.dls_jpeg_decode_batch.restype = None
    lib.dls_jpeg_decode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int),
    ]
    return lib


def _prebuilt() -> str | None:
    """A _dls_native*.so shipped next to the package (no-compiler deploys)."""
    import glob

    pkg_dir = os.path.dirname(os.path.dirname(__file__))
    hits = sorted(glob.glob(os.path.join(pkg_dir, "_dls_native*.so")))
    return hits[-1] if hits else None


def _load() -> ctypes.CDLL | None:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("DLS_DISABLE_NATIVE"):
        return None
    try:
        path = _prebuilt() or _build(_SRC)
        if path is not None:
            _LIB = _bind(ctypes.CDLL(path))
            logger.info("native kernels loaded (%d threads): %s",
                        _LIB.dls_num_threads(), path)
    except Exception as e:  # any load failure → numpy fallback, said loudly
        logger.error("native host kernels unavailable (%s: %s) — falling "
                     "back to the numpy implementations (available() "
                     "reports False)", type(e).__name__, e)
        _LIB = None
    return _LIB


def available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# Kernels (native with numpy fallback, identical semantics)
# ---------------------------------------------------------------------------

def crop_flip_normalize_batch(
    images: np.ndarray,          # [N, H, W, C] uint8
    ys: np.ndarray,              # [N] int32 crop origin rows
    xs: np.ndarray,              # [N] int32 crop origin cols
    flips: np.ndarray,           # [N] bool/uint8 horizontal flip
    crop: tuple[int, int],
    mean: np.ndarray,
    std: np.ndarray,
) -> np.ndarray:
    """Fused random-crop + flip + (x/255 - mean)/std over a batch → float32."""
    n, h, w, c = images.shape
    ch, cw = crop
    images = np.ascontiguousarray(images, np.uint8)
    ys = np.ascontiguousarray(ys, np.int32)
    xs = np.ascontiguousarray(xs, np.int32)
    # Bounds-check BEFORE dispatch: the C++ kernel reads raw offsets, so an
    # invalid origin would be an out-of-bounds heap read there, while the
    # numpy path would merely produce a short slice — fail loudly on both.
    if len(ys) != n or len(xs) != n:
        raise ValueError(f"ys/xs must have length {n}: got {len(ys)}/{len(xs)}")
    if ch > h or cw > w:
        raise ValueError(f"crop {crop} exceeds image size {(h, w)}")
    bad = (ys < 0) | (ys > h - ch) | (xs < 0) | (xs > w - cw)
    if bad.any():
        i = int(np.argmax(bad))
        raise ValueError(
            f"crop origin out of bounds at index {i}: y={ys[i]} x={xs[i]} "
            f"for image {(h, w)} crop {crop}")
    flips = np.ascontiguousarray(flips, np.uint8)
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    lib = _load()
    if lib is not None:
        out = np.empty((n, ch, cw, c), np.float32)
        lib.dls_crop_flip_normalize_batch(
            images, n, h, w, c, ys, xs, flips, ch, cw, mean, std, out
        )
        return out
    out = np.empty((n, ch, cw, c), np.float32)
    for i in range(n):
        img = images[i, ys[i]:ys[i] + ch, xs[i]:xs[i] + cw]
        if flips[i]:
            img = img[:, ::-1]
        out[i] = (img.astype(np.float32) / 255.0 - mean) / std
    return out


def normalize_u8_batch(images: np.ndarray, mean: np.ndarray, std: np.ndarray) -> np.ndarray:
    """[N,H,W,C] uint8 → standardized float32 (no crop/flip)."""
    n, h, w, c = images.shape
    images = np.ascontiguousarray(images, np.uint8)
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    lib = _load()
    if lib is not None:
        out = np.empty((n, h, w, c), np.float32)
        lib.dls_normalize_u8_batch(images, n, h, w, c, mean, std, out)
        return out
    return (images.astype(np.float32) / 255.0 - mean) / std


def rrc_flip_normalize(
    image: np.ndarray,                # [H, W, C] uint8
    region: tuple[int, int, int, int],  # (y0, x0, ch, cw) crop in source px
    flip: bool,
    size: tuple[int, int],
    mean: np.ndarray,
    std: np.ndarray,
) -> np.ndarray | None:
    """Fused crop→bilinear-resize→flip→(x/255-mean)/std, uint8 in, f32 out.

    The whole per-epoch augmentation tail of the record input path in ONE
    GIL-free pass with no float intermediate image (the numpy chain converts
    the full frame to f32 before cropping — ~4× the bytes touched). Returns
    None when the native library is unavailable; callers fall back to the
    equivalent numpy chain (vision.train_transform does).
    """
    lib = _load()
    if lib is None:
        return None
    h, w, c = image.shape
    y0, x0, ch, cw = region
    if not (0 <= y0 and 0 <= x0 and ch > 0 and cw > 0
            and y0 + ch <= h and x0 + cw <= w):
        raise ValueError(f"crop region {region} out of bounds for {(h, w)}")
    image = np.ascontiguousarray(image, np.uint8)
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    oh, ow = size
    out = np.empty((oh, ow, c), np.float32)
    lib.dls_rrc_flip_normalize(image, h, w, c, y0, x0, ch, cw, int(flip),
                               oh, ow, mean, std, out)
    return out


def rrc_flip_normalize_varbatch(
    images: list[np.ndarray],          # N × [Hi, Wi, C] uint8 (varying size)
    regions: np.ndarray,               # [N, 4] int32 (y0, x0, ch, cw)
    flips: np.ndarray,                 # [N] uint8
    size: tuple[int, int],
    mean: np.ndarray,
    std: np.ndarray,
    out: np.ndarray | None = None,     # [N, OH, OW, C] f32 (written in place)
) -> np.ndarray | None:
    """Whole-batch fused augmentation over variable-size images in ONE
    native call (parallel over images × row groups) writing directly into
    the batch buffer — no per-image ctypes overhead, no np.stack pass.
    Returns None when the native library is unavailable (callers fall back
    to the per-example path)."""
    lib = _load()
    if lib is None:
        return None
    n = len(images)
    c = images[0].shape[2]
    oh, ow = size
    regions = np.ascontiguousarray(regions, np.int32)
    if regions.shape != (n, 4):
        raise ValueError(f"regions must be [{n}, 4], got {regions.shape}")
    hs = np.empty(n, np.int32)
    ws = np.empty(n, np.int32)
    ptrs = (ctypes.c_void_p * n)()
    contig = []  # keep alive for the duration of the call
    for i, img in enumerate(images):
        img = np.ascontiguousarray(img, np.uint8)
        if img.ndim != 3 or img.shape[2] != c:
            raise ValueError(f"image {i}: want [H, W, {c}] u8, got {img.shape}")
        h, w = img.shape[:2]
        y0, x0, ch, cw = regions[i]
        if not (0 <= y0 and 0 <= x0 and ch > 0 and cw > 0
                and y0 + ch <= h and x0 + cw <= w):
            raise ValueError(
                f"image {i}: crop region {tuple(regions[i])} out of bounds "
                f"for {(h, w)}")
        hs[i], ws[i] = h, w
        contig.append(img)
        ptrs[i] = img.ctypes.data_as(ctypes.c_void_p)
    # fail loudly BEFORE dispatch — the C++ kernel reads raw offsets, so a
    # short flips/mean/std array would be an out-of-bounds heap read there
    flips = np.ascontiguousarray(flips, np.uint8)
    if len(flips) != n:
        raise ValueError(f"flips must have length {n}, got {len(flips)}")
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    if len(mean) != c or len(std) != c:
        raise ValueError(
            f"mean/std must have length {c}, got {len(mean)}/{len(std)}")
    if out is None:
        out = np.empty((n, oh, ow, c), np.float32)
    elif out.shape != (n, oh, ow, c) or out.dtype != np.float32 \
            or not out.flags.c_contiguous:
        raise ValueError(f"out must be C-contiguous [{n}, {oh}, {ow}, {c}] f32")
    lib.dls_rrc_flip_normalize_varbatch(
        ptrs, hs, ws, c,
        np.ascontiguousarray(regions[:, 0]), np.ascontiguousarray(regions[:, 1]),
        np.ascontiguousarray(regions[:, 2]), np.ascontiguousarray(regions[:, 3]),
        flips, n, oh, ow, mean, std, out)
    del contig
    return out


def resize_bilinear(image: np.ndarray, size: tuple[int, int]) -> np.ndarray:
    """[H,W,C] (or [H,W]) float32 → resized, half-pixel centers (vision.py math)."""
    if image.ndim == 2:  # grayscale: process as single-channel
        return resize_bilinear(image[..., None], size)[..., 0]
    h, w, c = image.shape
    oh, ow = size
    if (h, w) == (oh, ow):
        return np.asarray(image, np.float32)
    image = np.ascontiguousarray(image, np.float32)
    lib = _load()
    if lib is not None:
        out = np.empty((oh, ow, c), np.float32)
        lib.dls_resize_bilinear(image, h, w, c, oh, ow, out)
        return out
    from distributeddeeplearningspark_tpu.data import vision

    return vision.resize_bilinear(image, size)


class JpegUnsupported(ValueError):
    """Valid JPEG but a coding mode outside baseline (progressive, 12-bit,
    arithmetic, CMYK): only PIL decodes it."""


def jpeg_decode(data: bytes) -> np.ndarray | None:
    """Baseline JPEG bytes → uint8 HWC (csrc/dls_jpeg.cc): the decoder of
    :func:`..data.vision.decode_jpeg` where PIL cannot be imported, and of
    nothing else (with PIL there, libjpeg-turbo decodes, four times faster on
    a quiet thread; on subsampled chroma, which this decoder replicates and
    libjpeg interpolates, the two differ by a level or so on average).

    Returns None when the native library is unavailable; raises
    :class:`JpegUnsupported` for non-baseline streams and ValueError for
    malformed data. The decode releases the GIL (ctypes), so prefetch
    threads decode in parallel with the main thread.
    """
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    rc = lib.dls_jpeg_info(buf, buf.size, ctypes.byref(h), ctypes.byref(w),
                           ctypes.byref(c))
    if rc == _JPEG_UNSUPPORTED:
        raise JpegUnsupported("non-baseline JPEG (progressive/12-bit/arith)")
    if rc != _JPEG_OK:
        raise ValueError(f"malformed JPEG (dls_jpeg_info rc={rc})")
    out = np.empty((h.value, w.value, c.value), np.uint8)
    rc = lib.dls_jpeg_decode(buf, buf.size, out.reshape(-1), out.size)
    if rc == _JPEG_UNSUPPORTED:
        raise JpegUnsupported("non-baseline JPEG (progressive/12-bit/arith)")
    if rc != _JPEG_OK:
        raise ValueError(f"malformed JPEG (dls_jpeg_decode rc={rc})")
    return out


def jpeg_decode_batch(datas: list[bytes]) -> list[np.ndarray] | None:
    """Decode many baseline JPEGs in parallel (one C++ thread per image).

    Returns None when the native library is unavailable. Per-image failures
    raise (JpegUnsupported if any stream is non-baseline, ValueError
    otherwise) — callers wanting soft failure decode singly.
    """
    lib = _load()
    if lib is None:
        return None
    n = len(datas)
    if n == 0:
        return []
    bufs = [np.frombuffer(d, np.uint8) for d in datas]
    outs: list[np.ndarray] = []
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    for buf in bufs:
        rc = lib.dls_jpeg_info(buf, buf.size, ctypes.byref(h), ctypes.byref(w),
                               ctypes.byref(c))
        if rc == _JPEG_UNSUPPORTED:
            raise JpegUnsupported("non-baseline JPEG in batch")
        if rc != _JPEG_OK:
            raise ValueError(f"malformed JPEG in batch (rc={rc})")
        outs.append(np.empty((h.value, w.value, c.value), np.uint8))
    data_ptrs = (ctypes.c_void_p * n)(*[b.ctypes.data for b in bufs])
    lens = (ctypes.c_int64 * n)(*[b.size for b in bufs])
    out_ptrs = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
    out_lens = (ctypes.c_int64 * n)(*[o.size for o in outs])
    rcs = (ctypes.c_int * n)()
    lib.dls_jpeg_decode_batch(data_ptrs, lens, out_ptrs, out_lens, n, rcs)
    for i in range(n):
        if rcs[i] == _JPEG_UNSUPPORTED:
            raise JpegUnsupported(f"non-baseline JPEG at batch index {i}")
        if rcs[i] != _JPEG_OK:
            raise ValueError(f"malformed JPEG at batch index {i} (rc={rcs[i]})")
    return outs


def sum_into(dst: np.ndarray, src: np.ndarray) -> np.ndarray:
    """dst += src (float32, flattened view) — host gradient aggregation."""
    if dst.dtype != np.float32 or not dst.flags.c_contiguous:
        # reshape(-1) on a non-contiguous dst would COPY, and the kernel
        # would accumulate into the discarded copy — hard error instead
        raise ValueError("sum_into needs a C-contiguous float32 dst")
    if src.size != dst.size:
        # the kernel reads dst.size floats from src — a short src would be
        # a heap over-read, not the broadcast error numpy would raise
        raise ValueError(f"sum_into size mismatch: dst {dst.size} vs src {src.size}")
    src = np.ascontiguousarray(src, np.float32)
    lib = _load()
    if lib is not None:
        lib.dls_sum_into_f32(dst.reshape(-1), src.reshape(-1), dst.size)
        return dst
    dst += src
    return dst
