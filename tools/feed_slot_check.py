"""On the chip: is a batch's host memory ever written while a transfer reads it?

``host_batches`` fills its batches into slots it keeps and writes a slot again
once nothing refers to its arrays (``data/feed._Slots``). The one referrer no
CPU test has is a ``device_put`` whose transfer to the chip is still reading
the host memory. This drives ``resnet50_imagenet.fit_jpeg``'s feed through
``prefetch_to_device`` as ``fit`` does, takes a digest of every host batch at
``put`` and compares it with the digest of the device array fetched back two
batches later (the consumer holds the last two device batches, as ``fit``
does). Prints one JSON line; exit code 1 on any difference.

    chiprun --chips 1 -- python3 tools/feed_slot_check.py [batches]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import zlib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import numpy as np

    from benchmark.harness import images
    from distributeddeeplearningspark_tpu.data import feed, prefetch, vision
    from distributeddeeplearningspark_tpu.data.sources import imagenet_folder
    from distributeddeeplearningspark_tpu.parallel.mesh import single_device_mesh

    batches = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    mesh = single_device_mesh()
    with tempfile.TemporaryDirectory() as d:
        images.write_folder(d, seed=7, num_images=1024)
        ds = vision.imagenet_train(
            imagenet_folder(d, decode=False, num_partitions=1), size=224,
            seed=7, repeat=True)

        def digest(batch) -> list[int]:
            return [zlib.crc32(np.ascontiguousarray(batch[k]))
                    for k in sorted(batch)]

        at_put: list[list[int]] = []
        refs: list[tuple[int, int]] = []

        def put(batch, mesh):
            at_put.append(digest(batch))
            before = sys.getrefcount(batch["image"])
            placed = feed.put_global(batch, mesh)
            refs.append((before, sys.getrefcount(batch["image"])))
            return placed

        probe = prefetch.StarvationProbe()
        wrong: list[int] = []

        def check(i: int, placed) -> None:
            if digest(jax.device_get(placed)) != at_put[i]:
                wrong.append(i)

        held: list = []  # the last two device batches, as ``fit`` holds them
        stream = prefetch.prefetch_to_device(
            feed.host_batches(ds, 256), mesh, put=put, probe=probe)
        for i, placed in enumerate(stream):
            if len(held) == 2:
                check(*held.pop(0))  # two batches after its put
            held.append((i, placed))
            del placed
            if i + 1 == batches:
                break
        for each in held:
            check(*each)
        snap = probe.snapshot()
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "batches": batches,
        "wrong": wrong,
        "refs_to_image_before_and_after_put": sorted(set(refs)),
        "input_slot_reused": snap["input_slot_reused"],
        "input_slot_new": snap["input_slot_new"],
        # the batches whose rows the map's pool wrote (two are being filled
        # at once then): all but the stream's first, or the check ran the
        # pull path
        "input_filled_by_map": snap["input_filled_by_map"]}))
    return 1 if wrong else 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)  # the feed's daemon thread is blocked on a full ring
