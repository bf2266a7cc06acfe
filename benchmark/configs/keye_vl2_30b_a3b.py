"""``keye_vl2_30b_a3b``: the program's ``models/sparse_decoder.py`` at the
widths in ``keye_vl2_30b_a3b.json`` (one chip's share of an eight-chip
deployment of four of the 48 layers; the file says how it was cut), trained
as ``examples/train_sparse_moe_lm.py`` trains it: the three-term loss
``losses.sparse_moe_lm``, AdamW with linear warm-up and gradient clipping."""

from __future__ import annotations

import functools
import json
import os

from benchmark.harness import flops_sparse


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.sparse_decoder import (
        SparseDecoderConfig,
        SparseDecoderLM,
    )
    from distributeddeeplearningspark_tpu.train import losses, optim

    if traffic["vocab_size"] != cfg["vocab_size"]:
        raise ValueError(f"the traffic draws ids from {traffic['vocab_size']}, "
                         f"the model holds {cfg['vocab_size']}")
    sa, assumed = cfg["sa_config"], cfg["assumed_values"]
    model = SparseDecoderLM(SparseDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        num_experts=cfg["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        router_aux_weight=assumed["router_aux_loss_coef"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        index_loss_weight=assumed["indexer_loss_weight"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"])))
    opt = cfg["optimizer"]
    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(opt["lr"], opt["warmup_steps"],
                                        opt["total_steps"])),
        opt["grad_clip"])
    return {"model": model, "loss": losses.sparse_moe_lm, "tx": tx,
            "fit_kwargs": {"tokens_per_example": traffic["seq_len"]}}


def items_per_example(cfg: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def flops_per_item(cfg: dict, traffic: dict) -> float:
    sa = cfg["sa_config"]
    return flops_sparse.sparse_moe_lm_flops_per_token(
        hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        router_width=cfg["num_local_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"][1],
        expert_size=cfg["moe_intermediate_size"],
        vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"])


def _weigh(params, weights: dict):
    """``params`` unchanged, the cotangent of each leaf times the leaf's
    weight (``check.grad_leaf_weights``, by path; 1 where none is given):
    the flattened gradient the harness compares is then the gradient in
    units in which the named leaves are not lost beside the largest."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def scaled(x, w):
        return x

    scaled.defvjp(lambda x, w: (x, None), lambda w, _, g: (g * w,))

    def one(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        return scaled(x, float(weights[name])) if name in weights else x

    return jax.tree_util.tree_map_with_path(one, params)


def expert_probe(model, params, batch):
    """The energy of the routed experts' output, ``sum over layers of
    mean_t |y_t|^2``, through the program's own ``RoutedExperts`` (its router,
    its sort, its grouped products, the kernels held) in FLOAT32, on an input
    that is the same to the last bit on both sides: the RMS-normed embedding
    rows of the window's tokens. In float32 the program and the reference
    route alike (in bf16 they do not: a token whose eighth and ninth
    probability lie within bf16's rounding goes elsewhere, which is why the
    experts' gradient cannot show a dropped assignment), so an assignment
    that is dropped is output that is missing."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.moe import RoutedExperts

    c = model.cfg
    layer = RoutedExperts(
        c.hidden_size, c.expert_size, c.num_experts, c.experts_per_token,
        held=c.experts_held, norm_topk=c.norm_topk_prob, dtype=jnp.float32,
        param_dtype=jnp.float32)
    x = params["token_embed"]["embedding"].astype(jnp.float32)[
        batch["input_ids"]]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c.rms_eps)

    def energy(moe):
        y, _ = layer.apply({"params": moe}, x)
        return jnp.mean(jnp.sum(jnp.square(y.astype(jnp.float32)), -1))

    with jax.default_matmul_precision("highest"):
        return jnp.sum(jax.lax.map(energy, params["layers"]["moe"]))


def program_loss(model, loss_fn, params, mutable, batch):
    """What the harness compares with the reference's (``check.why`` in the
    configuration's file): the program's model and its three-term loss (no
    dropout to turn off), each leaf's cotangent weighted, plus the logarithm
    of :func:`expert_probe`, which carries no gradient."""
    import jax
    import jax.numpy as jnp

    del mutable
    check = _own_cfg()["check"]
    loss = loss_fn(model.apply(
        {"params": _weigh(params, check.get("grad_leaf_weights", {}))},
        batch, train=False), batch)[0]
    return loss + jax.lax.stop_gradient(
        jnp.log(expert_probe(model, params, batch)))


@functools.cache
def _own_cfg() -> dict:
    with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as f:
        return json.load(f)


def dsa_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each sparse-attention kernel, per chip."""
    sa = cfg["sa_config"]
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"],
            "block": sa["q_chunk_size"], "bytes_per_element": 2}
