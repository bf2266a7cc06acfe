"""``nemotron3_nano_30b_a3b``: the program's ``models/hybrid_decoder.py`` with
blocks of ONE sublayer (``"mamba2"``, ``"experts"``, ``"bare_attention"``) at
the widths in ``nemotron3_nano_30b_a3b.json`` (one chip's share of a 16-chip
deployment of seven of the 52 layers; the file says how it was cut), trained
as ``examples/train_ssm_moe_lm.py`` trains it: ``losses.hybrid_moe_lm``,
AdamW with linear warm-up and gradient clipping, the router's bias moved by
the step outside the gradient."""

from __future__ import annotations

import functools
import importlib.util
import json
import os

from benchmark.harness import flops_ssm, runner

#: tokens a pass of the probe: the ``tokens x k`` buffers of a float32 expert
#: layer (k = 6) over a whole 16,384-window would weigh 2 GB beside the state
PROBE_ROWS = 4096
#: positions after a document's first that ``boundary_energy`` reads (the
#: reference's)
REACH = 16
KINDS = {"M": "MAMBA", "E": "EXPERTS", "*": "BARE_ATTENTION"}


def _refuse_a_program_without_the_layer():
    """A refusal for a checkout whose program lacks the state-space scan,
    before any device is touched (the harness loads this file first). Asked
    of the import system, not by importing: the program's modules come in
    later through ``from <package> import Session``, inside the span
    ``dls.start/import`` that ``start_import_s`` reads (an import by name
    here would pass by it: PERF.md section 7 (g))."""
    try:
        found = importlib.util.find_spec(
            "distributeddeeplearningspark_tpu.ops.ssd")
    except ImportError:
        found = None
    if found is None:
        raise runner.Refused([
            "this checkout's program has no state-space decoder (no "
            "ops/ssd.py): it cannot run a model of Mamba-2 layers, relu² "
            "experts and attention without positions, each a block of its "
            "own"])


_refuse_a_program_without_the_layer()


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    from distributeddeeplearningspark_tpu.train import losses, optim

    if traffic["vocab_size"] != cfg["vocab_size"]:
        raise ValueError(f"the traffic draws ids from {traffic['vocab_size']}, "
                         f"the model holds {cfg['vocab_size']}")
    pattern = cfg["hybrid_override_pattern"]
    if (cfg["n_group"], cfg["topk_group"], cfg["n_shared_experts"],
            cfg["mlp_hidden_act"], cfg["mamba_hidden_act"]) != (
            1, 1, 1, "relu2", "silu") or set(pattern) - set(KINDS) or len(
            pattern) != cfg["num_hidden_layers"]:
        raise ValueError("the program selects in one group, holds one shared "
                         "expert, runs relu² experts and a SiLU in its "
                         "state-space layers (the published values), and "
                         "knows the pattern's M, E and * (it has no dense "
                         "'-' layer)")
    model = hd.HybridDecoderLM(hd.HybridDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(getattr(hd, KINDS[k]) for k in pattern),
        num_dense_layers=0, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rms_eps=cfg["layer_norm_epsilon"],
        max_position=cfg["max_position_embeddings"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state_size=cfg["ssm_state_size"],
        ssm_conv_taps=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        norm_topk_prob=cfg["norm_topk_prob"], use_expert_bias=True,
        bias_update_rate=cfg["assumed_values"]["router_bias_update_rate"],
        train_router=cfg["train_router"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        shared_expert_size=(cfg["n_shared_experts"]
                            * cfg["moe_shared_expert_intermediate_size"]),
        expert_form=cfg["mlp_hidden_act"],
        tie_embeddings=cfg["tie_word_embeddings"],
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
    from benchmark.harness import flops_hybrid

    return flops_ssm.ssm_moe_lm_flops_per_token(
        hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], groups=cfg["n_groups"],
        state_size=cfg["ssm_state_size"], chunk=cfg["chunk_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], router_width=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"][1],
        expert_size=cfg["moe_intermediate_size"],
        shared_size=(cfg["n_shared_experts"]
                     * cfg["moe_shared_expert_intermediate_size"]),
        vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
        pairs_share=flops_hybrid.in_document_pairs_share(traffic),
        train_router=cfg["train_router"])


def ssd_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of one layer's scan, per chip, and how many layers
    run it."""
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": cfg["mamba_num_heads"],
            "head_dim": cfg["mamba_head_dim"], "groups": cfg["n_groups"],
            "state_size": cfg["ssm_state_size"], "chunk": cfg["chunk_size"],
            "bytes_per_element": 2,
            "layers": cfg["hybrid_override_pattern"].count("M")}


def mamba_conv_width(cfg: dict, traffic: dict) -> int:
    """The channels of the state-space layers' convolution (``xBC``): the
    result width that only it produces."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            + 2 * cfg["n_groups"] * cfg["ssm_state_size"])


def flash_causal_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each flash kernel, per chip, but for the share of
    the causal pairs inside a document, which the reader takes from the
    step's own counter (for the readers of ``lfm2_24b_a2b.fit_seg32k`` once
    their lists name this cell)."""
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "bytes_per_element": 2}


def blocks(cfg, tree: dict) -> list:
    """``[(kind, the block's subtree)]`` of a parameter, bias or
    intermediates tree in the order the model runs them, a scanned period's
    unstacked; a block the tree does not hold (only expert blocks have a
    bias) gives an empty subtree."""
    import jax

    _, period, whole, trail = cfg.layout()
    out = []
    for n in range(whole):
        out += [(kind, jax.tree.map(
            lambda a, n=n: a[n],
            tree.get("periods", {}).get(f"layer_{j}", {})))
            for j, kind in enumerate(period)]
    return out + [(kind, tree.get(f"trail_{i}", {}))
                  for i, kind in enumerate(trail)]


def expert_probe(model, params, mutable, batch):
    """The energy of the ROUTED experts' output (without the shared expert),
    ``sum over the expert blocks of mean_t |y_t|^2``, through the program's
    own ``RoutedExperts`` (its sigmoid router, the selection on score + bias,
    the factor 2.5, its sort, its two grouped products around relu², the
    kernels held) in FLOAT32, on the RMS-normed embedding rows of the
    window's tokens. In float32 the program and the reference route alike
    (in bf16 they do not), so an assignment that is dropped or made without
    the bias, a weight without the factor, or a relu that is not squared, is
    output that is missing, another expert's or of another size."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    from distributeddeeplearningspark_tpu.models.moe import (
        BIAS_COLLECTION,
        RoutedExperts,
    )

    c = model.cfg
    layer = RoutedExperts(
        c.hidden_size, c.expert_size, c.num_experts, c.experts_per_token,
        held=c.experts_held, norm_topk=c.norm_topk_prob, dtype=jnp.float32,
        param_dtype=jnp.float32, score="sigmoid",
        select_bias=c.use_expert_bias, routed_scale=c.routed_scaling_factor,
        expert_form=c.expert_form)
    x = params["token_embed"]["embedding"].astype(jnp.float32)[
        batch["input_ids"]]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c.rms_eps)
    x = x.reshape(-1, x.shape[-1])
    rows = min(PROBE_ROWS, x.shape[0])
    x = x.reshape(-1, 1, rows, x.shape[-1])
    biases = blocks(c, mutable.get(BIAS_COLLECTION, {}))
    routed_only = ("router", "w_up", "w_down")
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for (kind, lp), (_, bias) in zip(blocks(c, params), biases):
            if kind != hd.EXPERTS:
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


def _after_boundary(seg):
    """``[B, S]`` segment ids -> the first ``REACH`` positions of every
    document but a row's first. Counted here, not by the model: a term must
    look at the same positions whatever the model does with documents."""
    import jax
    import jax.numpy as jnp

    idx = jnp.broadcast_to(jnp.arange(seg.shape[1], dtype=jnp.int32),
                           seg.shape)
    starts = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], jnp.bool_), seg[:, 1:] != seg[:, :-1]], 1)
    first = jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    return (first > 0) & (idx - first < REACH)


def step_parts(model, loss_fn, params, mutable, batch) -> dict:
    """ONE forward pass of the program's model as the step runs it (compute
    dtype, kernels and window of the cell; the router's bias read, not
    moved), and of it: ``loss`` (its training loss), ``ssm_energy`` (the sum
    over the Mamba blocks of ``mean_t |SUB_t|^2`` of ``Mamba2Mixer``'s output
    ON THE BLOCK'S OWN INPUT), ``scan_energy`` (the same of the SCAN's
    output ``y_t = h_t C_t + D x_t``, which the mixer sows: before the gated
    norm, which gives every position the same size whatever the scan
    gave it), ``boundary_energy`` (``scan_energy`` with the mean taken
    over the first ``REACH`` positions of every document but the window's
    first, mean over the rows), ``attention_energy`` (of the attention
    blocks), ``experts_energy`` (the sum over the expert blocks of ``mean_t
    |y_t|^2`` of the ROUTED experts' part, which ``RoutedExperts`` sows
    beside the shared expert's) and ``rows_held_share`` (the model's
    counter). The energies are read off the operators' outputs inside that
    pass, so what they hold is the timed path itself: the chunked scan with
    its resets and the convolution at 16,384 rows in bf16, the flash kernels
    causal with segment ids at 32 / 2 heads, the sort, gathers and grouped
    products of the experts on real hidden states."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd

    c = model.cfg
    out, seen = model.apply(
        {"params": params, **mutable}, batch, train=False,
        mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, (hd.Mamba2Mixer, hd.CausalAttention)))
    seg = batch.get("segment_ids")
    seg = (jnp.zeros(batch["input_ids"].shape, jnp.int32) if seg is None
           else seg.astype(jnp.int32))
    reached = _after_boundary(seg)
    square = lambda y: jnp.sum(jnp.square(y.astype(jnp.float32)), -1)
    mean = lambda e: jnp.sum(e) / e.size
    ssm, scan, boundary, attention, experts = 0.0, 0.0, 0.0, 0.0, 0.0
    for kind, ops in blocks(c, seen["intermediates"]):
        if kind == hd.MAMBA:
            ((y, _),) = ops["mixer"]["__call__"]
            ssm = ssm + mean(square(y))
            (y,) = ops["mixer"]["scan"]                  # [B, S, H, P]
            y = square(y.reshape(*y.shape[:2], -1))
            scan = scan + mean(y)
            boundary = boundary + jnp.mean(
                jnp.sum(jnp.where(reached, y, 0.0), axis=1)
                / jnp.maximum(jnp.sum(reached, axis=1), 1))
        elif kind == hd.BARE_ATTENTION:
            (y,) = ops["self_attn"]["__call__"]
            attention = attention + mean(square(y))
        elif kind == hd.EXPERTS:
            (y,) = ops["moe"]["routed"]
            experts = experts + mean(square(y))
    return {"loss": loss_fn(out, batch)[0], **jax.lax.stop_gradient({
        "ssm_energy": ssm, "scan_energy": scan, "boundary_energy": boundary,
        "attention_energy": attention, "experts_energy": experts,
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
