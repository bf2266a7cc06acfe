"""``resnet50_imagenet``: the program's ``models/resnet.py`` ``ResNet50`` at
the sizes in ``resnet50_imagenet.json``, trained as
``examples/train_resnet.py`` trains it: cross-entropy, SGD with momentum and
weight decay on a warm-up cosine schedule, batch-norm statistics as mutable
state."""

from __future__ import annotations

from benchmark.harness import flops


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.resnet import (
        BottleneckBlock, ResNet)
    from distributeddeeplearningspark_tpu.train import losses, optim

    model = ResNet(stage_sizes=tuple(cfg["stage_sizes"]),
                   block_cls=BottleneckBlock,
                   num_classes=cfg["num_classes"], width=cfg["width"],
                   dtype=jnp.dtype(cfg["compute_dtype"]))
    opt = cfg["optimizer"]
    schedule = optim.warmup_cosine(opt["lr"], warmup_steps=opt["warmup_steps"],
                                   total_steps=opt["total_steps"])
    tx = optim.sgd(schedule, momentum=opt["momentum"],
                   weight_decay=opt["weight_decay"])
    return {"model": model, "loss": losses.softmax_xent, "tx": tx,
            "fit_kwargs": {}}


def items_per_example(cfg: dict, traffic: dict) -> int:
    return 1


def flops_per_item(cfg: dict, traffic: dict) -> float:
    return flops.resnet_flops_per_image(
        stage_sizes=cfg["stage_sizes"], width=cfg["width"],
        num_classes=cfg["num_classes"], image_size=cfg["image_size"])


def program_loss(model, loss_fn, params, mutable, batch):
    """The program's model and loss in training mode, as the step runs it."""
    logits, _ = model.apply({"params": params, **mutable}, batch, train=True,
                            mutable=list(mutable))
    return loss_fn(logits, batch)[0]
