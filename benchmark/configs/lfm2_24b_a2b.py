"""``lfm2_24b_a2b``: the program's ``models/hybrid_decoder.py`` at the widths
in ``lfm2_24b_a2b.json`` (one chip's share of an eight-chip deployment of
five of the 40 layers; the file says how it was cut), trained as
``examples/train_hybrid_moe_lm.py`` trains it: ``losses.hybrid_moe_lm``,
AdamW with linear warm-up and gradient clipping, the router's bias moved by
the step outside the gradient."""

from __future__ import annotations

import functools
import json
import os

from benchmark.harness import flops_hybrid

#: tokens a pass of the probe: the ``tokens x k`` buffers of a float32 expert
#: layer over a whole 32,768-window would weigh 4 GB beside the state
PROBE_ROWS = 8192


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
        HybridDecoderConfig,
        HybridDecoderLM,
    )
    from distributeddeeplearningspark_tpu.train import losses, optim

    if traffic["vocab_size"] != cfg["vocab_size"]:
        raise ValueError(f"the traffic draws ids from {traffic['vocab_size']}, "
                         f"the model holds {cfg['vocab_size']}")
    if cfg["routed_scaling_factor"] != 1:
        raise ValueError(f"routed_scaling_factor {cfg['routed_scaling_factor']}"
                         f": the program's experts weigh a token's experts by "
                         f"their normalised scores alone (the published 1)")
    model = HybridDecoderLM(HybridDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_eps=cfg["norm_eps"], max_position=cfg["max_position_embeddings"],
        conv_taps=cfg["conv_L_cache"],
        intermediate_size=cfg["intermediate_size"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        use_expert_bias=cfg["use_expert_bias"],
        bias_update_rate=cfg["assumed_values"]["router_bias_update_rate"],
        train_router=cfg["train_router"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"])))
    opt = cfg["optimizer"]
    tx = optim.with_grad_clip(
        optim.adamw(optim.warmup_linear(opt["lr"], opt["warmup_steps"],
                                        opt["total_steps"])),
        opt["grad_clip"])
    return {"model": model, "loss": losses.hybrid_moe_lm, "tx": tx,
            "fit_kwargs": {"tokens_per_example": traffic["seq_len"]}}


def items_per_example(cfg: dict, traffic: dict) -> int:
    return int(traffic["seq_len"])


def flops_per_item(cfg: dict, traffic: dict) -> float:
    return flops_hybrid.hybrid_moe_lm_flops_per_token(
        hidden_size=cfg["hidden_size"], layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], intermediate_size=cfg["intermediate_size"],
        router_width=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"][1],
        expert_size=cfg["moe_intermediate_size"],
        vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
        pairs_share=flops_hybrid.in_document_pairs_share(traffic),
        train_router=cfg["train_router"])


def shortconv_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each short-convolution kernel, per chip."""
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "channels": cfg["hidden_size"], "taps": cfg["conv_L_cache"],
            "bytes_per_element": 2}


def flash_causal_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each flash kernel, per chip, but for the share of
    the causal pairs inside a document, which the reader takes from the
    step's own counter."""
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "bytes_per_element": 2}


def _weigh(params, weights: dict):
    """``params`` unchanged, the cotangent of each leaf times the leaf's
    weight (``check.grad_leaf_weights``, by path; 1 where none is given)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def scaled(x, w):
        return x

    scaled.defvjp(lambda x, w: (x, None), lambda w, _, g: (g * w,))

    def one(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        return scaled(x, float(weights[name])) if name in weights else x

    return jax.tree_util.tree_map_with_path(one, params)


def layers(cfg, tree: dict) -> list:
    """``[(kind, dense?, the layer's subtree)]`` of a parameter (or bias)
    tree in layer order, a scanned period's unstacked; a layer the tree does
    not hold (a dense layer has no bias) gives an empty subtree."""
    import jax

    lead, period, whole, trail = cfg.layout()
    out = [(kind, True, tree.get(f"lead_{i}", {}))
           for i, kind in enumerate(lead)]
    for n in range(whole):
        out += [(kind, False, jax.tree.map(
            lambda a, n=n: a[n], tree["periods"][f"layer_{j}"]))
            for j, kind in enumerate(period)]
    return out + [(kind, False, tree[f"trail_{i}"])
                  for i, kind in enumerate(trail)]


def _normed_embedding_rows(model, params, batch):
    """The window's embedding rows, RMS-normed, in float32: an input that is
    the same to the last bit on both sides of the comparison."""
    import jax
    import jax.numpy as jnp

    x = params["token_embed"]["embedding"].astype(jnp.float32)[
        batch["input_ids"]]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + model.cfg.rms_eps)


def expert_probe(model, params, mutable, batch):
    """The energy of the routed experts' output, ``sum over expert layers of
    mean_t |y_t|^2``, through the program's own ``RoutedExperts`` (its
    sigmoid router, the selection on score + bias, its sort, its grouped
    products, the kernels held) in FLOAT32, on the RMS-normed embedding rows
    of the window's tokens. In float32 the program and the reference route
    alike (in bf16 they do not, which is why the experts' gradient cannot
    show a dropped or misrouted assignment: PERF.md, PR 26), so an assignment
    that is dropped, or made on the score without the bias, is output that
    is missing or another expert's."""
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
        select_bias=c.use_expert_bias)
    x = _normed_embedding_rows(model, params, batch)
    x = x.reshape(-1, x.shape[-1])
    rows = min(PROBE_ROWS, x.shape[0])
    x = x.reshape(-1, 1, rows, x.shape[-1])
    biases = layers(c, mutable.get(BIAS_COLLECTION, {}))
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for (_, dense, lp), (_, _, bias) in zip(layers(c, params), biases):
            if dense:
                continue
            variables = {"params": lp["moe"]}
            if c.use_expert_bias:
                variables[BIAS_COLLECTION] = bias["moe"]

            def energy(rows_of_x, variables=variables):
                y, _ = layer.apply(variables, rows_of_x)
                return jnp.sum(jnp.square(y.astype(jnp.float32)))

            total = total + jnp.sum(jax.lax.map(energy, x)) / (
                x.shape[0] * rows)
    return total


def _reached(seg, taps: int):
    """``[B, S]`` segment ids -> the positions a ``taps``-tap operator
    reaches back from across a document's start: the first ``taps - 1`` of
    every document. Counted here, not by the model's ``document_positions``:
    a probe must look at the same positions whatever the model does with
    positions."""
    import jax
    import jax.numpy as jnp

    idx = jnp.broadcast_to(jnp.arange(seg.shape[1], dtype=jnp.int32),
                           seg.shape)
    starts = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], jnp.bool_), seg[:, 1:] != seg[:, :-1]], 1)
    in_document = idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    return (in_document < taps - 1)[..., None]


def _segments(batch):
    import jax.numpy as jnp

    seg = batch.get("segment_ids")
    return (jnp.zeros(batch["input_ids"].shape, jnp.int32) if seg is None
            else seg.astype(jnp.int32))


def boundary_probe(model, params, batch):
    """The energy of the short convolutions' output AT THE POSITIONS THEIR
    TAPS REACH BACK ACROSS A DOCUMENT'S START (the first ``taps - 1`` of every
    document), ``sum over convolution layers of the mean over those positions
    of |OP(RMSNorm_op(x))_t|^2``, through the program's own ``ShortConv`` (its
    projections, its kernels on a TPU) in FLOAT32 on the RMS-normed embedding
    rows: the layer alone, where both sides compute alike to rounding."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    from distributeddeeplearningspark_tpu.models.llama import RMSNorm

    c = dataclasses.replace(model.cfg, dtype=jnp.float32)
    x = _normed_embedding_rows(model, params, batch)
    seg = _segments(batch)
    reached = _reached(seg, c.conv_taps)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for kind, _, lp in layers(c, params):
            if kind != hd.CONV:
                continue
            h = RMSNorm(c.rms_eps, jnp.float32).apply(
                {"params": lp["operator_norm"]}, x)
            y = hd.ShortConv(c).apply({"params": lp["conv"]}, h, seg)
            total = total + jnp.sum(jnp.where(
                reached, jnp.square(y.astype(jnp.float32)), 0.0)) / jnp.sum(
                reached)
    return total


def step_parts(model, loss_fn, params, mutable, batch) -> dict:
    """ONE forward pass of the program's model as the step runs it (compute
    dtype, kernels and window of the cell; the router's bias read, not
    moved), and of it: ``loss`` (its training loss, each leaf's cotangent
    weighted), ``experts_energy`` (the sum over the expert layers of ``mean_t
    |y_t|^2`` of ``RoutedExperts``' output ON THE LAYER'S OWN INPUT),
    ``boundary_energy`` (the sum over the convolution layers of the mean,
    over the first ``taps - 1`` positions of every document, of ``ShortConv``'s
    output squared) and ``rows_held_share`` (the model's counter). The two
    energies are read off the operators' outputs inside that pass
    (``capture_intermediates``), so what they hold is the timed path itself:
    ``shortconv_fwd`` with its segment cut at 32,768 rows in bf16, the sort,
    gathers and grouped products of the experts on real hidden states."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    from distributeddeeplearningspark_tpu.models.moe import RoutedExperts

    c = model.cfg
    check = _own_cfg()["check"]
    out, seen = model.apply(
        {"params": _weigh(params, check.get("grad_leaf_weights", {})),
         **mutable}, batch, train=False, mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, (hd.ShortConv, RoutedExperts)))
    reached = _reached(_segments(batch), c.conv_taps)
    square = lambda y: jnp.square(y.astype(jnp.float32))
    experts, boundary = 0.0, 0.0
    for kind, dense, ops in layers(c, seen["intermediates"]):
        if kind == hd.CONV:
            (y,) = ops["conv"]["__call__"]
            boundary = boundary + jnp.sum(
                jnp.where(reached, square(y), 0.0)) / jnp.sum(reached)
        if not dense:
            ((y, _),) = ops["moe"]["__call__"]
            experts = experts + jnp.sum(square(y)) / reached.size
    return {"loss": loss_fn(out, batch)[0], **jax.lax.stop_gradient({
        "experts_energy": experts, "boundary_energy": boundary,
        "rows_held_share": out["moe_rows_held_share"]})}


def parts(model, loss_fn, params, mutable, batch) -> dict:
    """The terms of :func:`program_loss` by name (``check.why`` in the
    configuration's file says what each is for): those of
    :func:`step_parts` and the two float32 probes of the layers alone; but
    for ``loss`` none carries a gradient."""
    import jax

    return {**step_parts(model, loss_fn, params, mutable, batch),
            **jax.lax.stop_gradient({
                "expert_probe": expert_probe(model, params, mutable, batch),
                "boundary_probe": boundary_probe(model, params, batch)})}


def compared(terms: dict):
    """The ONE scalar the harness compares with the reference's: the loss,
    plus the logarithm of every other term times its weight in
    ``check.term_weights``; infinite, which no tolerance admits, where the
    experts this rank holds got less than ``check.held_share_floor`` of the
    window's assignments (a run whose routing has left them measures no
    expert and is refused)."""
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
