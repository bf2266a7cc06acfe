"""``BASELINE.json`` configuration 2 as written: a class-per-directory tree
of JPEG files -> ``imagenet_folder(decode=False)`` -> ``imagenet_train``
(decode, random-resized crop, flip, normalise on the host for every image of
every epoch), as ``examples/train_resnet.py --data-dir`` builds it. Traffic
parameters: ``num_images``, ``image_size``. The JPEGs are made once per seed
(``harness/images.py``) and cached."""

from __future__ import annotations

from benchmark.harness import images, seedcache


def build(spark, traffic: dict, seed: int) -> dict:
    from distributeddeeplearningspark_tpu.data import vision
    from distributeddeeplearningspark_tpu.data.sources import imagenet_folder

    key = seedcache.key(seed, [images.__file__],
                        {"num_images": traffic["num_images"]})
    root, hit = seedcache.ensure(
        key, "jpeg", lambda d: images.write_folder(
            d, seed=seed, num_images=traffic["num_images"]))
    ds = imagenet_folder(root, decode=False,
                         num_partitions=max(spark.default_parallelism, 1))
    ds = vision.imagenet_train(ds, size=traffic["image_size"], seed=seed,
                               repeat=True)
    return {"dataset": ds, "sample_from": ds, "facts": {"seed_cache_hit": hit}}
