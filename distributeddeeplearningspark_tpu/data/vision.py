"""Image pipeline — the rebuild of the reference's Spark RDD image plane.

The reference decodes/augments/batches ImageNet inside executor partitions
(SURVEY.md §2 'Data: image pipeline'). Here the same steps are RDD-style
``map`` transforms over a :class:`~distributeddeeplearningspark_tpu.rdd.
PartitionedDataset`, executed on the *host* by the prefetch thread (device
time is reserved for the MXU; host decode overlaps device compute via
:mod:`.prefetch`).

All transforms are numpy, per-example, composable with ``dataset.map``. JPEG
decoding is libjpeg-turbo's through PIL wherever PIL can be imported, so a
pixel is what PIL, torchvision and TensorFlow users get from the same file;
our own baseline decoder (csrc/dls_jpeg.cc) decodes only where it cannot —
see :func:`decode_jpeg`.
"""

from __future__ import annotations

import io
from typing import Callable, Iterable

import numpy as np

from distributeddeeplearningspark_tpu.rdd import PartitionedDataset
from distributeddeeplearningspark_tpu.telemetry import spans

#: ImageNet channel statistics (the universal constants every framework bakes in).
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def normalize(image: np.ndarray, mean: np.ndarray = IMAGENET_MEAN,
              std: np.ndarray = IMAGENET_STD) -> np.ndarray:
    """[0,1] float or uint8 HWC → standardized float32."""
    if image.dtype == np.uint8:
        image = image.astype(np.float32) / 255.0
    return (image.astype(np.float32) - mean) / std


def resize_bilinear(image: np.ndarray, size: tuple[int, int]) -> np.ndarray:
    """Minimal bilinear resize (numpy; avoids a PIL/TF dependency)."""
    h, w = image.shape[:2]
    out_h, out_w = size
    if (h, w) == (out_h, out_w):
        return image
    ys = (np.arange(out_h) + 0.5) * h / out_h - 0.5
    xs = (np.arange(out_w) + 0.5) * w / out_w - 0.5
    y0 = np.clip(np.floor(ys).astype(np.int64), 0, h - 1)
    x0 = np.clip(np.floor(xs).astype(np.int64), 0, w - 1)
    y1 = np.clip(y0 + 1, 0, h - 1)
    x1 = np.clip(x0 + 1, 0, w - 1)
    wy = np.clip(ys - y0, 0.0, 1.0).astype(np.float32)[:, None, None]
    wx = np.clip(xs - x0, 0.0, 1.0).astype(np.float32)[None, :, None]
    img = image.astype(np.float32)
    top = img[y0][:, x0] * (1 - wx) + img[y0][:, x1] * wx
    bot = img[y1][:, x0] * (1 - wx) + img[y1][:, x1] * wx
    return top * (1 - wy) + bot * wy


def _resize(image: np.ndarray, size: tuple[int, int]) -> np.ndarray:
    """Bilinear resize via the native (C++) kernel when built, numpy otherwise.

    Identical math either way (csrc/dls_native.cc mirrors resize_bilinear);
    the native path releases the GIL and, for a large output, parallelizes
    across rows.
    """
    from distributeddeeplearningspark_tpu.utils import native

    return native.resize_bilinear(np.asarray(image, np.float32), size)


def sample_crop_region(h: int, w: int, rng: np.random.Generator,
                       scale: tuple[float, float] = (0.08, 1.0),
                       ratio: tuple[float, float] = (3 / 4, 4 / 3),
                       ) -> tuple[int, int, int, int] | None:
    """Inception-style crop sampling: (y, x, ch, cw), or None when 10 draws
    of random area/aspect never fit (extreme aspect ratios) — callers fall
    back to a center crop. Split from :func:`random_resized_crop` so the
    fused native path consumes the SAME rng stream and picks the same crop."""
    area = h * w
    for _ in range(10):
        target = area * rng.uniform(*scale)
        aspect = np.exp(rng.uniform(np.log(ratio[0]), np.log(ratio[1])))
        cw = int(round(np.sqrt(target * aspect)))
        ch = int(round(np.sqrt(target / aspect)))
        if cw <= w and ch <= h:
            y = int(rng.integers(0, h - ch + 1))
            x = int(rng.integers(0, w - cw + 1))
            return y, x, ch, cw
    return None


def random_resized_crop(image: np.ndarray, rng: np.random.Generator, size: int = 224,
                        scale: tuple[float, float] = (0.08, 1.0),
                        ratio: tuple[float, float] = (3 / 4, 4 / 3)) -> np.ndarray:
    """Inception-style crop: random area/aspect, resized to ``size``."""
    h, w = image.shape[:2]
    region = sample_crop_region(h, w, rng, scale, ratio)
    if region is None:
        return center_crop(image, size)  # fallback
    y, x, ch, cw = region
    return _resize(image[y:y + ch, x:x + cw], (size, size))


def center_crop(image: np.ndarray, size: int = 224, resize_shorter: int = 256) -> np.ndarray:
    """Eval transform: resize shorter side then center crop."""
    h, w = image.shape[:2]
    scale = resize_shorter / min(h, w)
    image = _resize(image, (int(round(h * scale)), int(round(w * scale))))
    h, w = image.shape[:2]
    y, x = (h - size) // 2, (w - size) // 2
    return image[y:y + size, x:x + size]


def random_flip(image: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    return image[:, ::-1] if rng.random() < 0.5 else image


def _content_seed(img: np.ndarray) -> int:
    """Process-stable 32-bit content hash (built-in hash() is siphash-salted
    per process, which would break cross-host augmentation determinism)."""
    import hashlib

    return int.from_bytes(
        hashlib.blake2b(img.tobytes()[:64], digest_size=4).digest(), "little"
    )


def decode_jpeg(path_or_bytes) -> np.ndarray:
    """JPEG → uint8 HWC, at full resolution: ``[H, W, 3]``, or ``[H, W, 1]``
    for a one-channel file.

    The decoder follows what the interpreter can import, and nothing else:

    1. libjpeg-turbo through PIL, whenever PIL can be imported: every coding
       mode (baseline, progressive, CMYK → RGB), chroma interpolated as
       libjpeg does it, the GIL released while it decodes and while it
       packs the pixels (one call each: :func:`_pil_pixels`). The array
       views PIL's bytes and is read-only.
    2. the native baseline decoder (csrc/dls_jpeg.cc, also GIL-free) where
       PIL is absent. It replicates chroma where libjpeg interpolates, so a
       4:2:0 file's pixels differ by a level or so on average, and with
       them the content-seeded crop and flip (:func:`_content_seed`).

    A malformed stream raises ``ValueError`` on both routes. The time spent
    here is the span ``dls.feed/decode`` (counter ``input_decode_s`` where
    the calling thread has a feed's sink bound).
    """
    if isinstance(path_or_bytes, (bytes, bytearray)):
        data, where = bytes(path_or_bytes), ""
    else:
        with open(path_or_bytes, "rb") as f:
            data = f.read()
        where = f" in {path_or_bytes}"
    with spans.span("dls.feed/decode", spans.bound_sink()):
        try:
            from PIL import Image
        except ImportError:
            return _decode_jpeg_native(data, where)
        try:
            img = Image.open(io.BytesIO(data))
            # the whole stream in one decoder call, not one per 64 KB
            img.decodermaxblock = max(len(data), img.decodermaxblock)
            if img.mode not in ("RGB", "L"):
                img = img.convert("RGB")
            return _pil_pixels(img)
        except OSError as e:  # UnidentifiedImageError, a truncated stream
            raise ValueError(f"malformed JPEG{where}: {e}") from e


def _pil_pixels(img) -> np.ndarray:
    """``np.asarray(img)`` as ``[H, W, C]`` with one call into PIL's packer
    where ``Image.tobytes`` makes one per 64 KB. Each call gives the GIL up
    and has to win it back from a pool of threads doing the same."""
    from PIL import Image

    img.load()
    shape = (img.height, img.width, len(img.getbands()))
    packer = Image._getencoder(img.mode, "raw", img.mode)
    packer.setimage(img.im)
    parts, status = [], 0
    while not status:  # as ``tobytes`` reads it: 0 more to come, 1 the end
        _, status, part = packer.encode(shape[0] * shape[1] * shape[2])
        parts.append(part)
    if status < 0:
        raise OSError(f"encoder error {status} in tobytes")
    return np.frombuffer(b"".join(parts), np.uint8).reshape(shape)


def _decode_jpeg_native(data: bytes, where: str) -> np.ndarray:
    from distributeddeeplearningspark_tpu.utils import native

    try:
        out = native.jpeg_decode(data)
    except ValueError as e:  # malformed, or a mode only PIL decodes
        raise type(e)(f"{e}{where}") from e
    if out is None:
        raise RuntimeError(
            "no JPEG decoder available (PIL absent and the native build failed)")
    return out


def _augment_decision(img: np.ndarray, seed: int, size: int
                      ) -> tuple[tuple[int, int, int, int] | None, bool]:
    """THE content-seeded crop/flip decision → ``(region, flip)``.

    One copy of the rng-stream contract shared by :func:`train_transform`'s
    uint8 paths, :func:`_fused_example_transform` (worker pool) and
    ``imagenet_train_batched``'s fused batch — the byte-parity between the
    in-process and worker-pool feeds (and checkpoint fast-forward resume
    with it) depends on every path drawing the same stream: an already-
    ``size``-sized frame consumes NO region draw (region is the full
    frame), then ONE flip draw; otherwise the 10-draw crop sampler runs
    first. ``region`` is None when the sampler gave up — callers fall back
    to a center crop.
    """
    rng = np.random.default_rng(
        (seed * 2654435761 + _content_seed(img)) & 0xFFFFFFFF)
    h, w = img.shape[:2]
    if h == w == size:
        region: tuple[int, int, int, int] | None = (0, 0, h, w)
    else:
        region = sample_crop_region(h, w, rng)
    return region, bool(rng.random() < 0.5)


def train_transform(size: int = 224, seed: int = 0) -> Callable[[dict], dict]:
    """Per-example ImageNet train augmentation: crop + flip + normalize.

    Contract: uint8 input is raw pixels → unit-scaled then standardized with
    the ImageNet stats; float input is assumed already normalized → geometric
    ops only. Deterministic per example content hash + seed so multi-host
    pipelines don't need rng plumbing through partitions.
    """

    def apply(example: dict) -> dict:
        example = _decode_if_bytes(example)
        img = example["image"]
        if img.dtype == np.uint8:
            region, flip = _augment_decision(img, seed, size)
            if img.shape[0] == img.shape[1] == size:
                # fused flip+normalize in one native pass (numpy fallback)
                from distributeddeeplearningspark_tpu.utils import native

                img = native.crop_flip_normalize_batch(
                    img[None], np.zeros(1, np.int32), np.zeros(1, np.int32),
                    np.array([flip], np.uint8), (size, size),
                    IMAGENET_MEAN, IMAGENET_STD,
                )[0]
                return {**example, "image": img}
            # uint8 + crop (the record input path): one fused native pass —
            # crop→resize→flip→normalize with no float intermediate frame.
            # Same rng stream as the numpy chain, so native/numpy pick the
            # same crop and agree to fp tolerance.
            from distributeddeeplearningspark_tpu.utils import native

            fused = (
                native.rrc_flip_normalize(
                    img, region, flip, (size, size), IMAGENET_MEAN, IMAGENET_STD)
                if region is not None else None)
            if fused is not None:
                return {**example, "image": fused}
            if region is not None:
                y, x, ch, cw = region
                img = _resize(img[y:y + ch, x:x + cw].astype(np.float32) / 255.0,
                              (size, size))
            else:
                img = center_crop(img.astype(np.float32) / 255.0, size)
            img = normalize(img[:, ::-1] if flip else img)
        else:
            rng = np.random.default_rng(
                (seed * 2654435761 + _content_seed(img)) & 0xFFFFFFFF)
            if img.shape[0] != size or img.shape[1] != size:
                img = random_resized_crop(img, rng, size)
            img = random_flip(img, rng)
        return {**example, "image": np.ascontiguousarray(img, np.float32)}

    return apply


def _decode_if_bytes(example: dict) -> dict:
    """``{"jpeg": bytes}`` (imagenet_folder(decode=False)) → decoded
    ``{"image": ...}``. Decoding INSIDE the transform is what lets
    ``map_parallel`` spread it over cores — decode in the source iterator
    runs on the single consumer thread no matter the pool size."""
    if "jpeg" not in example:
        return example
    out = {k: v for k, v in example.items() if k != "jpeg"}
    out["image"] = decode_jpeg(example["jpeg"])
    return out


def eval_transform(size: int = 224) -> Callable[[dict], dict]:
    """uint8 → scale+standardize (see train_transform contract); float → crop only.

    The shorter-side resize scales with the crop (ratio 0.875 — the standard
    256→224 ImageNet recipe generalized): a fixed 256 would be a zoom for any
    other crop size (e.g. size=64 would evaluate on the central 24×24 of the
    original image — measured as a 1.0-train / 0.28-eval accuracy split on a
    memorized toy set before this scaled)."""
    resize_shorter = int(round(size / 0.875))

    def apply(example: dict) -> dict:
        example = _decode_if_bytes(example)
        img = example["image"]
        needs_crop = img.shape[0] != size or img.shape[1] != size
        if img.dtype == np.uint8:
            if not needs_crop:
                from distributeddeeplearningspark_tpu.utils import native

                return {**example, "image": native.normalize_u8_batch(
                    img[None], IMAGENET_MEAN, IMAGENET_STD)[0]}
            h, w = img.shape[:2]
            if min(h, w) == resize_shorter:
                # record path (already shorter-side == resize_shorter): crop
                # in uint8 and normalize in one native pass — no resize, no
                # float intermediate frame
                from distributeddeeplearningspark_tpu.utils import native

                y, x = (h - size) // 2, (w - size) // 2
                return {**example, "image": native.crop_flip_normalize_batch(
                    img[None], np.array([y], np.int32), np.array([x], np.int32),
                    np.zeros(1, np.uint8), (size, size),
                    IMAGENET_MEAN, IMAGENET_STD)[0]}
            img = normalize(center_crop(img.astype(np.float32) / 255.0, size,
                                        resize_shorter))
        elif needs_crop:
            img = center_crop(img, size, resize_shorter)
        return {**example, "image": np.ascontiguousarray(img, np.float32)}

    return apply


def imagenet_train(dataset: PartitionedDataset, *, size: int = 224, seed: int = 0,
                   num_threads: int | None = None,
                   repeat: bool = False,
                   num_workers: int | None = None) -> PartitionedDataset:
    """RDD-shaped pipeline: shuffle → (repeat) → decode+augment.

    Feed it ``imagenet_folder(root, decode=False)`` so JPEG decode happens
    INSIDE the (optionally parallel) transform — decode in the source
    iterator would stay on the single consumer thread and cap a host at one
    core's rate while a chip consumes thousands (the cell
    ``resnet50_imagenet.fit_jpeg`` of ``BENCHMARK.json`` runs this path).
    ``num_threads``: thread-pool decode/augment (the
    Spark task-slots-per-executor analog; 0/1 = serial; augmentation is
    content-seeded per example, so thread scheduling cannot change WHICH
    augmentation an example gets — but concurrent native-kernel calls have
    been observed to race at the byte level on oversubscribed shared hosts
    (tests/test_input_workers.py quarantine note), so pipelines that need
    bit-determinism should use ``num_threads=0`` or worker processes,
    which reproduce exactly at any width).
    ``repeat=True`` makes the stream infinite HERE — shuffle must precede
    repeat, and repeating before the parallel map keeps one thread pool
    alive across epochs instead of respawning per pass.

    ``num_workers`` (default ``DLS_DATA_WORKERS``, 0 = off): run the
    decode/augment map across worker *processes* instead of threads —
    :class:`~.workers.WorkerMappedDataset`, real cores with no GIL and
    shared-memory delivery. The batch stream is byte-identical for any
    worker count (content-seeded augmentation + ordered delivery), so
    checkpoint fast-forward resume is unaffected. When enabled it replaces
    the thread pool (``num_threads`` is ignored) — process×thread pools
    would oversubscribe the host.
    """
    from distributeddeeplearningspark_tpu.data import workers as workers_lib

    ds = dataset.shuffle(seed)
    if repeat:
        ds = ds.repeat()
    tf = train_transform(size, seed)
    if workers_lib.resolve_num_workers(num_workers) > 0:
        return workers_lib.WorkerMappedDataset(ds, tf, num_workers,
                                               label="imagenet_train")
    return ds.map_parallel(tf, num_threads=num_threads)


def imagenet_eval(dataset: PartitionedDataset, *, size: int = 224,
                  num_threads: int | None = None,
                  num_workers: int | None = None) -> PartitionedDataset:
    from distributeddeeplearningspark_tpu.data import workers as workers_lib

    if workers_lib.resolve_num_workers(num_workers) > 0:
        return workers_lib.WorkerMappedDataset(
            dataset, eval_transform(size), num_workers, label="imagenet_eval")
    return dataset.map_parallel(eval_transform(size), num_threads=num_threads)


def _fused_example_transform(size: int, seed: int) -> Callable[[dict], dict]:
    """Per-example twin of :func:`imagenet_train_batched`'s fused batch call.

    Exactly the varbatch kernel's per-image math (csrc/dls_native.cc shares
    the float expressions between ``dls_rrc_flip_normalize`` and its
    varbatch loop) with exactly ``_fused_batch``'s decision logic — crop
    region/flip drawn from the same content-seeded rng, same fallbacks —
    so the worker-pool path of the batched feed is byte-identical to the
    in-process path for any ``num_workers``.
    """
    tf_fallback = train_transform(size, seed)

    def one(ex: dict) -> dict:
        from distributeddeeplearningspark_tpu.utils import native

        img = ex.get("image")
        if (native.available() and isinstance(img, np.ndarray)
                and img.dtype == np.uint8 and img.ndim == 3):
            region, flip = _augment_decision(img, seed, size)
            if region is not None:
                fused = native.rrc_flip_normalize(
                    img, region, flip, (size, size),
                    IMAGENET_MEAN, IMAGENET_STD)
                if fused is not None:
                    return {**ex, "image": fused}
        return {**ex, "image": tf_fallback(dict(ex))["image"]}

    return one


def imagenet_train_batched(
    dataset: PartitionedDataset,
    batch_size: int,
    *,
    size: int = 224,
    seed: int = 0,
    drop_remainder: bool = True,
    num_workers: int | None = None,
):
    """Record-path fast feed: yield READY train batches with whole-batch
    fused native augmentation.

    The record path's host time goes to per-example augment calls, the
    np.stack batch copy and thread-pool bookkeeping. This feed removes all
    three at once: records stream serially (cheap), crop/flip decisions stay
    per-example content-seeded (identical stream to ``train_transform``),
    and ONE ``dls_rrc_flip_normalize_varbatch`` call per batch crops,
    resizes, flips and normalizes every image directly into the
    preallocated [B, size, size, 3] batch buffer — parallel over images
    in C, no GIL, no stack pass.

    Yields ``{"image": [B, size, size, 3] f32, "label": [B] i32}``; falls
    back to the per-example chain when the native library is unavailable
    or an image is pre-float. Shuffle/repeat the dataset BEFORE this feed.

    ``num_workers`` (default ``DLS_DATA_WORKERS``, 0 = off): the
    per-example fused augment runs across worker processes
    (:mod:`.workers`) — the same kernel math as the in-process varbatch
    call, so the batch stream stays byte-identical for any worker count —
    and the consumer stacks shared-memory views straight into the batch
    buffer.
    """
    from distributeddeeplearningspark_tpu.data import workers as workers_lib
    from distributeddeeplearningspark_tpu.data.feed import _round_robin
    from distributeddeeplearningspark_tpu.utils import native

    if workers_lib.resolve_num_workers(num_workers) > 0:
        mapped = workers_lib.WorkerMappedDataset(
            dataset, _fused_example_transform(size, seed), num_workers,
            label="imagenet_train_batched")

        def stack_mapped(buf: list[dict]) -> dict:
            out = np.empty((len(buf), size, size, 3), np.float32)
            for j, e in enumerate(buf):
                out[j] = e["image"]  # shm view → batch buffer, one copy
            rest = {k: np.stack([np.asarray(e[k]) for e in buf])
                    for k in buf[0] if k != "image"}
            return {"image": out, **rest}

        def pooled_batches():
            streams = [mapped.iter_partition(i)
                       for i in range(mapped.num_partitions)]
            buf: list[dict] = []
            for ex in _round_robin([iter(s) for s in streams]):
                buf.append(ex)
                if len(buf) < batch_size:
                    continue
                yield stack_mapped(buf)
                buf = []
            if buf and not drop_remainder:
                yield stack_mapped(buf)

        return pooled_batches()

    # the SAME partition interleave as host_batches — the output-parity
    # contract with the per-example path depends on sharing one dealer
    streams = [dataset.iter_partition(i) for i in range(dataset.num_partitions)]
    tf_fallback = train_transform(size, seed)

    def _fused_batch(buf: list[dict]) -> dict:
        # split: images the fused kernel can take vs the rare odd ones
        # (pre-float, or the 10-draw crop sampler gave up) — only the odd
        # ones pay the per-example chain, not the whole batch
        fused_idx, images, regions, flips = [], [], [], []
        fallback_idx: list[int] = []
        if native.available():
            for j, ex in enumerate(buf):
                img = ex["image"]
                if img.dtype != np.uint8 or img.ndim != 3:
                    fallback_idx.append(j)
                    continue
                region, flip = _augment_decision(img, seed, size)
                if region is None:  # center-crop fallback shape — rare
                    fallback_idx.append(j)
                    continue
                fused_idx.append(j)
                images.append(img)
                regions.append(region)
                flips.append(flip)
        else:
            fallback_idx = list(range(len(buf)))

        out = np.empty((len(buf), size, size, 3), np.float32)
        if fused_idx:
            fused = native.rrc_flip_normalize_varbatch(
                images, np.asarray(regions, np.int32),
                np.asarray(flips, np.uint8), (size, size),
                IMAGENET_MEAN, IMAGENET_STD)
            out[np.asarray(fused_idx)] = fused
        for j in fallback_idx:
            out[j] = tf_fallback(dict(buf[j]))["image"]
        rest = {k: np.stack([np.asarray(e[k]) for e in buf])
                for k in buf[0] if k != "image"}
        return {"image": out, **rest}

    def batches():
        buf: list[dict] = []
        for ex in _round_robin([iter(s) for s in streams]):
            buf.append(ex)
            if len(buf) < batch_size:
                continue
            yield _fused_batch(buf)
            buf = []
        if buf and not drop_remainder:
            yield _fused_batch(buf)

    return batches()
