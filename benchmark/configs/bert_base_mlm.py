"""``bert_base_mlm``: the program's ``models/bert.py`` ``bert_base()`` at the
sizes in ``bert_base_mlm.json``, trained as ``examples/train_bert.py`` trains
it: masked-LM loss on gathered positions, AdamW with linear warm-up and
gradient clipping."""

from __future__ import annotations

from benchmark.harness import flops


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import bert_base
    from distributeddeeplearningspark_tpu.train import losses, optim

    model = bert_base(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout_rate=cfg["hidden_dropout_prob"],
        dtype=jnp.dtype(cfg["compute_dtype"]))
    opt = cfg["optimizer"]
    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(opt["lr"], opt["warmup_steps"],
                                        opt["total_steps"])),
        opt["grad_clip"])
    return {"model": model, "loss": losses.masked_lm, "tx": tx,
            "fit_kwargs": {"tokens_per_example": traffic["seq_len"]}}


def items_per_example(cfg: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def flops_per_item(cfg: dict, traffic: dict) -> float:
    return flops.bert_mlm_flops_per_token(
        hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
        max_predictions=traffic["max_predictions"])


def program_loss(model, loss_fn, params, mutable, batch):
    """The program's model and loss, deterministic (no dropout)."""
    del mutable
    return loss_fn(model.apply({"params": params}, batch, train=False),
                   batch)[0]


def flash_shapes(cfg: dict, traffic: dict) -> dict | None:
    """Per chip and per step: one attention per layer, run by the flash
    kernels when the window is at least 512 long (``ops/attention.py``)."""
    if traffic["seq_len"] < 512:
        return None
    heads = cfg["num_attention_heads"]
    return {"calls": cfg["num_hidden_layers"],
            "batch": traffic["per_chip_batch"], "heads": heads,
            "seq": traffic["seq_len"],
            "head_dim": cfg["hidden_size"] // heads, "bytes_per_element": 2}
