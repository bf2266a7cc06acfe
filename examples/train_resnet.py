"""Config-2 driver script: ResNet-50 / ImageNet-1k, RDD image pipeline → TPU.

The reference streams ImageNet RDD partitions into GPUs under NCCL DP
(BASELINE.json config 2). Here the same driver-script shape runs the jitted
SPMD step on the mesh::

    dlsubmit --master tpu examples/train_resnet.py -- --steps 100
    python examples/train_resnet.py --variant resnet18 --image-size 64
"""

import argparse
import logging

import numpy as np

from distributeddeeplearningspark_tpu import Session, Trainer
from distributeddeeplearningspark_tpu.data import vision
from distributeddeeplearningspark_tpu.data.sources import synthetic_images
from distributeddeeplearningspark_tpu.models import (
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from distributeddeeplearningspark_tpu.train import losses, optim

RESNETS = {
    "resnet18": ResNet18, "resnet34": ResNet34, "resnet50": ResNet50,
    "resnet101": ResNet101, "resnet152": ResNet152,
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--master", default=None)
    p.add_argument("--variant", default="resnet50",
                   choices=["resnet18", "resnet34", "resnet50", "resnet101",
                            "resnet152"])
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--data-dir", default=None,
                   help="ImageNet root (class-per-subdir of JPEGs); synthetic if unset")
    p.add_argument("--records-dir", default=None,
                   help="preprocessed array-record dir (data/records.py): "
                        "stream pre-decoded frames instead of paying JPEG "
                        "decode per epoch. "
                        "Create once with --materialize-records")
    p.add_argument("--materialize-records", default=None, metavar="OUT_DIR",
                   help="one-time: decode + shorter-side-resize --data-dir "
                        "into OUT_DIR record shards, then exit (the "
                        "rdd.cache() analog; point --records-dir here after)")
    p.add_argument("--record-px", type=int, default=0,
                   help="shorter-side size baked into materialized records "
                        "(0 = auto: max(256, image-size/0.875) so training "
                        "crops never upscale degraded frames)")
    p.add_argument("--data-workers", type=int, default=None,
                   help="decode/augment worker processes (default: "
                        "DLS_DATA_WORKERS env, else as many as the cores "
                        "allow for --data-dir and the thread pool for "
                        "records; 0 = in-process). Byte-identical batch "
                        "stream at any count — see docs/PERFORMANCE.md "
                        "'Scaling the host input pipeline'")
    p.add_argument("--eval-dir", default=None,
                   help="validation root (same layout); reports top-1/top-5 "
                        "after training via the exact tail-inclusive evaluator")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--optimizer", default="sgd", choices=["sgd", "lars"],
                   help="lars = layerwise-adaptive rate scaling "
                        "(arXiv:1708.03888), the large-batch recipe: a "
                        "v4-32 pure-DP run at b=256/chip is global batch "
                        "8192, where momentum-SGD needs it to stay stable. "
                        "Base --lr scales with batch under LARS (the paper "
                        "uses lr = 0.1 * batch/256 with warmup)")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace window into this dir")
    p.add_argument("--tensorboard-dir", default=None)
    p.add_argument("--mfu", action="store_true",
                   help="report achieved MFU (costs one extra compile)")
    p.add_argument("--weights", default=None,
                   help="pretrained backbone: a torch .pt/.pth state_dict in "
                        "the torchvision resnet naming (fine-tune mode)")
    args = p.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")

    spark = Session.builder.master(args.master or "auto").appName("resnet-imagenet").getOrCreate()
    print(spark)

    if args.materialize_records:
        if not args.data_dir:
            raise SystemExit("--materialize-records needs --data-dir")
        from distributeddeeplearningspark_tpu.data.records import (
            write_imagenet_records)

        # record resolution tracks the training crop: baking 256-side frames
        # and then training --image-size 384 would silently upscale degraded
        # pixels
        record_px = args.record_px or max(
            256, int(round(args.image_size / 0.875)))
        paths = write_imagenet_records(
            args.data_dir, args.materialize_records, size=record_px,
            num_shards=max(spark.default_parallelism, 8))
        print(f"materialized {len(paths)} record shards in "
              f"{args.materialize_records}")
        spark.stop()
        return

    if args.records_dir:
        from distributeddeeplearningspark_tpu.data.records import array_records

        if args.eval_dir and not args.data_dir:
            # record labels were baked from the TRAIN dir's class mapping;
            # letting the eval dir derive its own set would silently
            # renumber labels (the hazard the --eval-dir pin exists for)
            raise SystemExit(
                "--records-dir with --eval-dir needs --data-dir too (the "
                "original class-per-subdir root) to pin the class mapping "
                "the records were materialized with")
        ds = array_records(
            args.records_dir,
            num_partitions=max(spark.default_parallelism, 1))
    elif args.data_dir:
        from distributeddeeplearningspark_tpu.data.sources import imagenet_folder

        # decode=False: JPEG decode runs inside imagenet_train's (parallel)
        # transform, not on the single partition-iterator thread
        ds = imagenet_folder(
            args.data_dir, num_partitions=max(spark.default_parallelism, 1),
            decode=False,
        )
    else:
        ds = synthetic_images(
            args.batch_size * max(args.steps, 1),
            image_size=args.image_size,
            num_classes=args.num_classes,
            num_partitions=max(spark.default_parallelism, 1),
        )
    ds = vision.imagenet_train(ds, size=args.image_size, repeat=True,
                               num_workers=args.data_workers)

    model = RESNETS[args.variant](num_classes=args.num_classes)
    schedule = optim.warmup_cosine(args.lr, warmup_steps=min(args.steps // 10, 500),
                                   total_steps=args.steps)
    tx = (optim.lars(schedule, momentum=0.9, weight_decay=1e-4)
          if args.optimizer == "lars" else
          optim.sgd(schedule, momentum=0.9, weight_decay=1e-4))
    trainer = Trainer(spark, model, losses.softmax_xent, tx)
    if args.weights:
        import torch

        from distributeddeeplearningspark_tpu.models.resnet_io import (
            import_torchvision_resnet)

        from distributeddeeplearningspark_tpu.models.resnet import (
            BottleneckBlock)

        sd = torch.load(args.weights, map_location="cpu", weights_only=True)
        # derive the import layout from the model itself so the table can't
        # drift from models/resnet.py
        params, stats = import_torchvision_resnet(
            sd, stage_sizes=tuple(model.stage_sizes),
            bottleneck=issubclass(model.block_cls, BottleneckBlock))
        if args.num_classes != np.shape(params["head"]["bias"])[0]:
            # fine-tuning to a new label space: keep the fresh-init head
            params.pop("head")
        trainer.init(trainer._sample_batch(ds, args.batch_size))
        trainer.load_pretrained(params, batch_stats=stats,
                                allow_uncovered=("head",))

    profile = None
    if args.profile_dir:
        from distributeddeeplearningspark_tpu.utils.profiling import ProfileSpec

        profile = ProfileSpec(args.profile_dir, start_step=min(10, args.steps // 2))
    state, summary = trainer.fit(
        ds, batch_size=args.batch_size, steps=args.steps, log_every=10,
        profile=profile, measure_flops=args.mfu, tensorboard_dir=args.tensorboard_dir,
    )
    print(f"train summary: {summary}")
    if args.eval_dir:
        from distributeddeeplearningspark_tpu.data.sources import (
            folder_classes,
            imagenet_folder,
        )

        eval_ds = vision.imagenet_eval(
            imagenet_folder(
                args.eval_dir, num_partitions=max(spark.default_parallelism, 1),
                decode=False,
                # pin the TRAINING mapping: an eval dir with a different
                # class-directory set would otherwise silently renumber
                # labels and report confident garbage
                class_to_index=(folder_classes(args.data_dir)
                                if args.data_dir else None),
            ),
            size=args.image_size,
        )
        emetrics = trainer.evaluate(eval_ds, batch_size=args.batch_size)
        print(f"eval metrics: "
              f"{ {k: round(float(v), 4) for k, v in emetrics.items()} }")
    spark.stop()


if __name__ == "__main__":
    main()
