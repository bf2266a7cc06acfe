"""The ``--records-dir`` path of ``examples/train_resnet.py``: the seeded
images as shorter-side-``record_px`` uint8 record shards, which is what
``write_imagenet_records`` leaves of a JPEG tree (the ``rdd.cache()`` analog)
-> ``array_records`` -> ``imagenet_train`` (crop, flip, normalise; no decode).
Traffic parameters: ``num_images``, ``image_size``, ``record_px``. The shards
are made once per seed, straight from ``harness/images.py``'s arrays with the
program's ``write_array_records`` (a JPEG tree written and decoded again on
the way would double what a seed the cache has not seen pays in set-up), and
cached."""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness import images, seedcache


def write_shards(out_dir: str, *, seed: int, num_images: int, record_px: int,
                 num_shards: int) -> None:
    from distributeddeeplearningspark_tpu.data.records import (
        write_array_records)
    from PIL import Image

    scale = min(1.0, record_px / min(images.HEIGHT, images.WIDTH))
    size = (round(images.WIDTH * scale), round(images.HEIGHT * scale))

    def record(i: int, label: int, image: np.ndarray) -> dict:
        small = Image.fromarray(image).resize(size, Image.BILINEAR)
        return {"image": np.asarray(small), "label": np.int32(label)}

    write_array_records(
        images.for_each_image(seed, num_images, record), out_dir,
        num_shards=num_shards)


def build(spark, traffic: dict, seed: int) -> dict:
    from distributeddeeplearningspark_tpu.data import records, vision

    parts = max(spark.default_parallelism, 1)
    shape = {"num_images": traffic["num_images"],
             "record_px": traffic["record_px"],
             "num_shards": max(parts, 8)}  # as the driver materialises them
    key = seedcache.key(seed, [images.__file__, __file__, records.__file__],
                        shape)
    rec_dir, hit = seedcache.ensure(
        key, "records", lambda d: write_shards(d, seed=seed, **shape))
    ds = records.array_records(rec_dir, num_partitions=parts)
    ds = vision.imagenet_train(ds, size=traffic["image_size"], seed=seed,
                               repeat=True)
    nbytes = sum(os.path.getsize(os.path.join(rec_dir, f))
                 for f in os.listdir(rec_dir))
    return {"dataset": ds, "sample_from": ds,
            "facts": {"seed_cache_hit": hit, "records_bytes": nbytes}}
