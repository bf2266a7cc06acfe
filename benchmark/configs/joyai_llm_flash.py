"""``joyai_llm_flash``: the program's ``models/hybrid_decoder.py`` with
``"latent_attention"`` layers at the widths in ``joyai_llm_flash.json`` (one
chip's share of a 32-chip deployment of five of the 40 layers and the
multi-token-prediction module; the file says how it was cut), trained as
``examples/train_latent_moe_lm.py`` trains it: ``losses.latent_moe_lm``,
AdamW with linear warm-up and gradient clipping, the router's bias moved by
the step outside the gradient."""

from __future__ import annotations

import functools
import json
import os
import re

from benchmark.harness import flops_mla, runner

#: added to ``latents_spread`` before its logarithm is taken (the reference's)
LATENTS_FLOOR = 1e-4
#: tokens a pass of the probe: the ``tokens x k`` buffers of a float32 expert
#: layer (k = 8) over a whole 16,384-window would weigh 4 GB beside the state
PROBE_ROWS = 4096


def _the_programs_layer():
    """The module that holds the layer, or a refusal for a checkout whose
    program lacks it (before any device is touched: the harness loads this
    file first)."""
    try:
        from distributeddeeplearningspark_tpu.models import hybrid_decoder
        from distributeddeeplearningspark_tpu.train import losses
        missing = [n for n, where in (("LatentAttention", hybrid_decoder),
                                      ("MTPModule", hybrid_decoder),
                                      ("latent_moe_lm", losses))
                   if not hasattr(where, n)]
    except ImportError as e:
        missing = [str(e)]
    if missing:
        raise runner.Refused([
            f"this checkout's program has no latent-attention decoder "
            f"(missing: {', '.join(missing)}): it cannot run a model whose "
            f"query/key and value heads differ in size, with a shared expert "
            f"and a multi-token-prediction module"])
    return hybrid_decoder


_the_programs_layer()


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.train import losses, optim

    hd = _the_programs_layer()
    if traffic["vocab_size"] != cfg["vocab_size"]:
        raise ValueError(f"the traffic draws ids from {traffic['vocab_size']}, "
                         f"the model holds {cfg['vocab_size']}")
    if (cfg["n_group"], cfg["topk_group"], cfg["n_shared_experts"],
            cfg["rope_interleave"]) != (1, 1, 1, True):
        raise ValueError("the program selects in one group, holds one shared "
                         "expert and rotates adjacent pairs (the published "
                         "values)")
    model = hd.HybridDecoderLM(hd.HybridDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=(hd.LATENT,) * cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        norm_topk_prob=cfg["norm_topk_prob"], use_expert_bias=True,
        bias_update_rate=cfg["assumed_values"]["router_bias_update_rate"],
        train_router=cfg["train_router"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        shared_expert_size=(cfg["n_shared_experts"]
                            * cfg["moe_intermediate_size"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=cfg["assumed_values"]["mtp_loss_weight"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"])))
    opt = cfg["optimizer"]
    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(opt["lr"], opt["warmup_steps"],
                                        opt["total_steps"])),
        opt["grad_clip"])
    return {"model": model, "loss": losses.latent_moe_lm, "tx": tx,
            "fit_kwargs": {"tokens_per_example": traffic["seq_len"]}}


def items_per_example(cfg: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def flops_per_item(cfg: dict, traffic: dict) -> float:
    return flops_mla.latent_moe_lm_flops_per_token(
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        mtp_layers=cfg["num_nextn_predict_layers"],
        heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        router_width=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"][1],
        expert_size=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"], vocab_size=cfg["vocab_size"],
        seq_len=traffic["seq_len"], train_router=cfg["train_router"])


def mla_attn_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each flash kernel of a latent-attention layer, per
    chip."""
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": cfg["num_attention_heads"],
            "qk_head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"], "bytes_per_element": 2}


def mla_proj_shapes(cfg: dict, traffic: dict) -> list[tuple[int, ...]]:
    """The result shapes that only XLA's part of latent attention produces
    (``tokens`` = the chip's rows x the window): the query latent, the joint
    key-value down-projection, the key-value latent, q or k at 32 heads of
    192, and the up-projected ``[k_nope | v]`` at 32 heads of 256."""
    tokens = traffic["per_chip_batch"] * traffic["seq_len"]
    heads = cfg["num_attention_heads"]
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return [(tokens, cfg["q_lora_rank"]),
            (tokens, cfg["kv_lora_rank"] + rot),
            (tokens, cfg["kv_lora_rank"]),
            (heads, nope + rot), (heads, nope + cfg["v_head_dim"])]


def _weigh(params, weights: dict):
    """``params`` unchanged, the cotangent of each leaf times the weight of
    the first pattern of ``weights`` (``check.grad_leaf_weights``: regular
    expressions searched in the leaf's path) that matches; 1 where none
    does."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def scaled(x, w):
        return x

    scaled.defvjp(lambda x, w: (x, None), lambda w, _, g: (g * w,))

    def one(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        for pattern, w in weights.items():
            if re.search(pattern, name):
                return scaled(x, float(w))
        return x

    return jax.tree_util.tree_map_with_path(one, params)


def blocks(cfg, tree: dict) -> list:
    """``[(name, dense?, the block's subtree)]`` of a parameter, bias or
    intermediates tree in the order the model runs them, a scanned period's
    unstacked, the MTP module's block last; a block the tree does not hold
    (a dense layer has no bias) gives an empty subtree."""
    import jax

    lead, period, whole, trail = cfg.layout()
    out = [(f"lead_{i}", True, tree.get(f"lead_{i}", {}))
           for i in range(len(lead))]
    for n in range(whole):
        out += [(f"periods/{n}/layer_{j}", False, jax.tree.map(
            lambda a, n=n: a[n], tree["periods"][f"layer_{j}"]))
            for j in range(len(period))]
    out += [(f"trail_{i}", False, tree[f"trail_{i}"])
            for i in range(len(trail))]
    if cfg.mtp_layers:
        out.append(("mtp/block", False, tree.get("mtp", {}).get("block", {})))
    return out


def expert_probe(model, params, mutable, batch):
    """The energy of the ROUTED experts' output (without the shared expert),
    ``sum over the five expert blocks of mean_t |y_t|^2``, through the
    program's own ``RoutedExperts`` (its sigmoid router, the selection on
    score + bias, the factor 2.5, its sort, its grouped products, the kernels
    held) in FLOAT32, on the RMS-normed embedding rows of the window's
    tokens. In float32 the program and the reference route alike (in bf16
    they do not), so an assignment that is dropped or made without the bias,
    or a weight without the factor, is output that is missing, another
    expert's or smaller."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.moe import (
        BIAS_COLLECTION,
        RoutedExperts,
    )

    c = model.cfg
    layer = RoutedExperts(
        c.hidden_size, c.expert_size, c.num_experts, c.experts_per_token,
        held=c.experts_held, norm_topk=c.norm_topk_prob, dtype=jnp.float32,
        param_dtype=jnp.float32, score="sigmoid",
        select_bias=c.use_expert_bias, routed_scale=c.routed_scaling_factor)
    x = params["token_embed"]["embedding"].astype(jnp.float32)[
        batch["input_ids"]]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c.rms_eps)
    x = x.reshape(-1, x.shape[-1])
    rows = min(PROBE_ROWS, x.shape[0])
    x = x.reshape(-1, 1, rows, x.shape[-1])
    biases = blocks(c, mutable.get(BIAS_COLLECTION, {}))
    routed_only = ("router", "w_gate", "w_up", "w_down")
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for (_, dense, lp), (_, _, bias) in zip(blocks(c, params), biases):
            if dense:
                continue
            variables = {"params": {k: lp["moe"][k] for k in routed_only}}
            if c.use_expert_bias:
                variables[BIAS_COLLECTION] = bias["moe"]

            def energy(rows_of_x, variables=variables):
                y, _ = layer.apply(variables, rows_of_x)
                return jnp.sum(jnp.square(y.astype(jnp.float32)))

            total = total + jnp.sum(jax.lax.map(energy, x)) / (
                x.shape[0] * rows)
    return total


def step_parts(model, loss_fn, params, mutable, batch) -> dict:
    """ONE forward pass of the program's model as the step runs it (compute
    dtype, kernels and window of the cell; the router's bias read, not
    moved), and of it: ``loss`` (its training loss, both terms, each leaf's
    cotangent weighted), ``lm_loss`` and ``mtp_nll`` (the two terms),
    ``attention_energy`` (the sum over the six blocks of ``mean_t |OP_t|^2``
    of ``LatentAttention``'s output ON THE BLOCK'S OWN INPUT),
    ``experts_energy`` (the sum over the five expert blocks of ``mean_t
    |y_t|^2`` of the ROUTED experts' part, which ``RoutedExperts`` sows
    beside the shared expert's), ``latents_spread`` (``LATENTS_FLOOR`` plus
    the sum over the six blocks and both latents, which ``LatentAttention``
    sows, of the variance over the tokens of a latent's mean square: next to
    nothing behind an RMSNorm) and ``rows_held_share`` (the model's
    counter). The energies are read off the operators' outputs inside that
    pass, so what they hold is the timed path itself: the flash kernels at
    192 / 128 over 16,384 rows in bf16, the sort, gathers and grouped
    products of the experts on real hidden states. The module's block ran
    over S rows, its last on a pad id: its means leave that row out, as the
    exact form has no such row."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd

    c = model.cfg
    check = _own_cfg()["check"]
    out, seen = model.apply(
        {"params": _weigh(params, check.get("grad_leaf_weights", {})),
         **mutable}, batch, train=False, mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, hd.LatentAttention))
    energy = lambda y: jnp.sum(jnp.square(y.astype(jnp.float32))) / (
        y.shape[0] * y.shape[1])
    spread = lambda latent: jnp.mean(jnp.var(jnp.mean(jnp.square(
        latent.astype(jnp.float32)), axis=-1), axis=-1))
    attention, experts, latents = 0.0, 0.0, LATENTS_FLOOR
    for name, dense, ops in blocks(c, seen["intermediates"]):
        rows = slice(None, -1) if name.startswith("mtp") else slice(None)
        (y,) = ops["self_attn"]["__call__"]
        attention = attention + energy(y[:, rows])
        ((c_q, c_kv),) = ops["self_attn"]["latents"]
        latents = latents + spread(c_q[:, rows]) + spread(c_kv[:, rows])
        if not dense:
            (y,) = ops["moe"]["routed"]
            experts = experts + energy(y[:, rows])
    loss, metrics = loss_fn(out, batch)
    return {"loss": loss, **jax.lax.stop_gradient({
        "lm_loss": metrics["lm_loss"], "mtp_nll": metrics["mtp_nll"],
        "attention_energy": attention, "experts_energy": experts,
        "latents_spread": latents,
        "rows_held_share": out["moe_rows_held_share"]})}


def parts(model, loss_fn, params, mutable, batch) -> dict:
    """The terms of :func:`program_loss` by name (``check.why`` in the
    configuration's file says what each is for): those of :func:`step_parts`
    and the float32 probe of the experts alone; but for ``loss`` none
    carries a gradient."""
    import jax

    return {**step_parts(model, loss_fn, params, mutable, batch),
            "expert_probe": jax.lax.stop_gradient(
                expert_probe(model, params, mutable, batch))}


def compared(terms: dict):
    """The ONE scalar the harness compares with the reference's: the loss,
    plus the logarithm of every term in ``check.term_weights`` times its
    weight; infinite, which no tolerance admits, where the experts this rank
    holds got less than ``check.held_share_floor`` of the window's
    assignments (a run whose routing has left them measures no expert and is
    refused)."""
    import jax.numpy as jnp

    check = _own_cfg()["check"]
    value = terms["loss"] + sum(w * jnp.log(terms[name]) for name, w in
                                check["term_weights"].items())
    return jnp.where(terms["rows_held_share"] >= check["held_share_floor"],
                     value, jnp.inf)


def program_loss(model, loss_fn, params, mutable, batch):
    """What the harness compares with the reference's ``loss``."""
    return compared(parts(model, loss_fn, params, mutable, batch))


@functools.cache
def _own_cfg() -> dict:
    with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as f:
        return json.load(f)
