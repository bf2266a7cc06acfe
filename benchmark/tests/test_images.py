"""The seeded images: the fast generator against the plain one it replaced,
threads against one thread, and the record shards' shapes."""

import numpy as np
from PIL import Image

from benchmark.harness import images


def plain_image(seed, index, label, pool):
    """``make_image`` as it is easiest to read: whole-array numpy, PIL's own
    bilinear stretch of the 3 x 4 layout. Same draws in the same order."""
    h, w = images.HEIGHT, images.WIDTH
    rng = np.random.default_rng([seed, index])
    crng = np.random.default_rng([seed, 7919, label])
    picks = rng.choice(len(pool), 4, replace=False)
    rolled = [np.roll(pool[k], (int(rng.integers(h)), int(rng.integers(w))),
                      axis=(0, 1)) for k in picks]
    lum = (rolled[0] + rolled[1]) * np.float32(0.7071)
    img = np.stack([lum + 0.35 * rolled[2], lum, lum + 0.35 * rolled[3]], -1)
    layout = crng.uniform(0, 255, (3, 4, 3)).astype(np.uint8)
    coarse = np.asarray(Image.fromarray(layout).resize(
        (w, h), Image.BILINEAR), np.float32) / 127.5 - 1.0
    base = crng.uniform(0.3, 0.7, 3).astype(np.float32)
    contrast = np.float32(images.CONTRAST * rng.uniform(0.8, 1.25))
    img = base + np.float32(0.18) * coarse + contrast * img
    return (np.clip(img, 0.0, 1.0) * 255).astype(np.uint8)


def test_the_fast_generator_matches_the_plain_one_to_one_level_of_255():
    # the sums run in another order and PIL rounds the stretched layout to
    # 8 bits: one level of 255, set before the comparison was run
    pool = images.field_pool(5)
    scratch = images.Scratch()
    for i in (0, 1, 999, 1000, 1023):
        fast = images.make_image(5, i, i % 1000, pool, scratch)
        plain = plain_image(5, i, i % 1000, pool)
        assert fast.shape == (375, 500, 3) and fast.dtype == np.uint8
        assert fast.flags["C_CONTIGUOUS"]
        assert np.abs(fast.astype(int) - plain.astype(int)).max() <= 1
    assert 30 < fast.std() < 90          # a picture, not a flat field


def test_threads_share_nothing(monkeypatch):
    pool = images.field_pool(2)
    scratch = images.Scratch()
    serial = [images.make_image(2, i, i % 1000, pool, scratch).copy()
              for i in range(48)]
    monkeypatch.setattr(images.os, "cpu_count", lambda: 16)  # more than cores
    got = images.for_each_image(2, 48, lambda i, label, im: (i, label, im))
    assert [(i, label) for i, label, _ in got] == [(i, i % 1000)
                                                   for i in range(48)]
    assert all(np.array_equal(a, b[2]) for a, b in zip(serial, got))


def test_jpeg_folder_and_record_shards(tmp_path):
    from benchmark.feeds import imagenet_records
    from distributeddeeplearningspark_tpu.data.records import array_records

    nbytes = images.write_folder(str(tmp_path / "jpeg"), seed=1, num_images=8)
    assert 85_000 < nbytes / 8 < 125_000          # as ImageNet's JPEGs weigh
    assert len(list((tmp_path / "jpeg").iterdir())) == 1000
    imagenet_records.write_shards(str(tmp_path / "rec"), seed=1, num_images=8,
                                  record_px=256, num_shards=2)
    rows = array_records(str(tmp_path / "rec"), num_partitions=1).take(8)
    assert len(rows) == 8
    for row in rows:                    # what write_imagenet_records leaves
        assert row["image"].shape == (256, 341, 3)
        assert row["image"].dtype == np.uint8
        assert row["label"].dtype == np.int32 and row["label"].shape == ()
    assert sorted(int(r["label"]) for r in rows) == list(range(8))
