"""``laguna_xs2``: the program's ``models/hybrid_decoder.py`` with layers of
kind ``"sliding_attention"`` (a band of 512 positions, 64 query heads, plain
rotary at 1e4) among ``"full_attention"`` ones (48 query heads, YaRN on the
leading half of the head), a per-head output gate on both, a dense first
layer, then 256-way softmax routing scaled by 2.5 beside an ungated shared
expert, at the widths in ``laguna_xs2.json`` (one chip's share of a 16-chip
deployment of five of the 40 layers; the file says how it was cut), trained
as ``examples/train_window_moe_lm.py`` trains it: ``losses.hybrid_moe_lm``
(next-token cross-entropy plus Switch's balance term), AdamW with linear
warm-up and gradient clipping."""

from __future__ import annotations

import functools
import importlib.util
import json
import os

from benchmark.harness import flops_hybrid, flops_window, runner

#: tokens a pass of the expert probe: the ``tokens x k`` buffers of a
#: float32 expert layer (k = 8) over a whole 16,384-window would weigh 2 GB
#: beside the state
PROBE_ROWS = 4096
#: the size of a spike's key in the window probe: a query's score for a
#: spike is 128 / sqrt(128) = 11.3, for every other key 0
SPIKE = 128.0
KINDS = {"full_attention": "ATTENTION", "sliding_attention": "SLIDING"}


def _refuse_a_program_without_the_band():
    """A refusal for a checkout whose program has no band regime of the
    flash kernels and no sliding-window layer, before any device is touched
    (the harness loads this file first). Asked of the import system and of
    the modules' source text, not by importing them: the program's modules
    come in later through ``from <package> import Session``, inside the span
    ``dls.start/import`` that ``start_import_s`` reads (PERF.md section 7
    (g))."""
    wanted = {"distributeddeeplearningspark_tpu.ops.flash_attention":
              "flash_window_fwd",
              "distributeddeeplearningspark_tpu.models.hybrid_decoder":
              '"sliding_attention"'}
    for module, mark in wanted.items():
        try:
            found = importlib.util.find_spec(module)
        except ImportError:
            found = None
        text = ""
        if found is not None and found.origin and os.path.exists(
                found.origin):
            with open(found.origin) as f:
                text = f.read()
        if mark not in text:
            raise runner.Refused([
                f"this checkout's program has no band regime of the flash "
                f"kernels or no sliding-window layer ({module} lacks "
                f"{mark}): it cannot run a model of sliding-window layers "
                f"among full ones"])


_refuse_a_program_without_the_band()


def _kinds(cfg: dict) -> tuple[int, int, int]:
    """``(dense layers, full layers' query heads, sliding layers')``, with
    the refusals of a configuration this program does not build."""
    mlp, heads = cfg["mlp_layer_types"], cfg["num_attention_heads_per_layer"]
    kinds = cfg["layer_types"]
    dense = mlp.index("sparse") if "sparse" in mlp else len(mlp)
    by_kind = {k: {h for t, h in zip(kinds, heads) if t == k} for k in KINDS}
    if (set(kinds) - set(KINDS) or set(mlp[:dense]) != {"dense"}
            or set(mlp[dense:]) - {"sparse"}
            or not len(mlp) == len(heads) == len(kinds)
            == cfg["num_hidden_layers"]
            or any(len(h) > 1 for h in by_kind.values())
            or cfg["attention_bias"] or cfg["moe_apply_router_weight_on_input"]
            or not cfg["gating"]):
        raise ValueError("the program builds full and sliding attention "
                         "layers of one head count a kind, dense layers "
                         "first, no attention bias, a per-head gate, and "
                         "router weights on the experts' outputs")
    full, sliding = (next(iter(by_kind[k]), None) for k in KINDS)
    return dense, full, sliding


def build(cfg: dict, traffic: dict) -> dict:
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    from distributeddeeplearningspark_tpu.train import losses, optim

    if traffic["vocab_size"] != cfg["vocab_size"]:
        raise ValueError(f"the traffic draws ids from {traffic['vocab_size']}, "
                         f"the model holds {cfg['vocab_size']}")
    dense, full_heads, sliding_heads = _kinds(cfg)
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"],
            sliding["partial_rotary_factor"]) != ("yarn", "default", 1):
        raise ValueError("the full layers' rotary is YaRN's, the sliding "
                         "layers' the default over the whole head (the "
                         "published values)")
    model = hd.HybridDecoderLM(hd.HybridDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(getattr(hd, KINDS[k]) for k in cfg["layer_types"]),
        num_dense_layers=dense, num_heads=full_heads or sliding_heads,
        sliding_num_heads=sliding_heads,
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(full["rope_theta"]),
        partial_rotary_factor=full["partial_rotary_factor"],
        yarn_factor=float(full["factor"]),
        yarn_original_max_position=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        rope_attention_factor=float(full["attention_factor"]),
        sliding_rope_theta=float(sliding["rope_theta"]),
        sliding_window=cfg["sliding_window"], qk_norm=False,
        attention_gate=cfg["gating"], rms_eps=cfg["rms_norm_eps"],
        max_position=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        norm_topk_prob=cfg["assumed_values"]["norm_topk_prob"],
        router_score="softmax", use_expert_bias=False,
        router_aux_loss_coef=cfg["assumed_values"]["router_aux_loss_coef"],
        train_router=cfg["train_router"],
        routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        shared_expert_size=cfg["shared_expert_intermediate_size"],
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
    return flops_window.window_moe_lm_flops_per_token(
        hidden_size=cfg["hidden_size"], layer_types=cfg["layer_types"],
        heads_per_layer=cfg["num_attention_heads_per_layer"],
        mlp_layer_types=cfg["mlp_layer_types"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        intermediate_size=cfg["intermediate_size"],
        router_width=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"][1],
        expert_size=cfg["moe_intermediate_size"],
        shared_size=cfg["shared_expert_intermediate_size"],
        vocab_size=cfg["vocab_size"], seq_len=traffic["seq_len"],
        pairs_share=flops_hybrid.in_document_pairs_share(traffic),
        window_share=flops_window.window_pairs_share(
            traffic, cfg["sliding_window"]),
        # (Switch's term gives the router its backward products whatever
        # ``train_router`` says of the routing's gradient)
        train_router=True)


def flash_causal_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each flash kernel of a FULL layer, per chip, but for
    the share of the causal pairs inside a document, which the reader takes
    from the step's own counter (the readers of ``lfm2_24b_a2b.fit_seg32k``
    once their lists name this cell)."""
    _, full_heads, _ = _kinds(cfg)
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": full_heads, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "bytes_per_element": 2}


def flash_window_shapes(cfg: dict, traffic: dict) -> dict:
    """One execution of each band kernel (a SLIDING layer's), per chip, but
    for the share of the window's pairs inside a document, which the reader
    takes from the step's own counter ``window_pairs_share``."""
    _, _, sliding_heads = _kinds(cfg)
    return {"batch": traffic["per_chip_batch"], "seq": traffic["seq_len"],
            "heads": sliding_heads, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "window": cfg["sliding_window"],
            "bytes_per_element": 2}


def blocks(cfg, tree: dict) -> list:
    """``[(kind, the block's subtree)]`` of a parameter or intermediates
    tree in the order the model runs them: the leading dense layers, a
    scanned period's unstacked, the trailing layers."""
    import jax

    lead, period, whole, trail = cfg.layout()
    out = [(kind, tree.get(f"lead_{i}", {})) for i, kind in enumerate(lead)]
    for n in range(whole):
        out += [(kind, jax.tree.map(
            lambda a, n=n: a[n],
            tree.get("periods", {}).get(f"layer_{j}", {})))
            for j, kind in enumerate(period)]
    return out + [(kind, tree.get(f"trail_{i}", {}))
                  for i, kind in enumerate(trail)]


def expert_probe(model, params, batch):
    """The energy of the ROUTED experts' output (without the shared expert),
    ``sum over the expert blocks of mean_t |y_t|^2``, through the program's
    own ``RoutedExperts`` (its softmax over all 256, the top 8 renormalised,
    the factor 2.5, its sort, its three grouped products, the kernels held)
    in FLOAT32, on the RMS-normed embedding rows of the window's tokens. In
    float32 the program and the reference route alike (in bf16 they do
    not), so an assignment that is dropped, a weight that is not
    renormalised or lacks its factor, is output that is missing, another
    expert's or of another size."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models.moe import RoutedExperts

    c = model.cfg
    layer = RoutedExperts(
        c.hidden_size, c.expert_size, c.num_experts, c.experts_per_token,
        held=c.experts_held, norm_topk=c.norm_topk_prob, dtype=jnp.float32,
        param_dtype=jnp.float32, score=c.router_score,
        routed_scale=c.routed_scaling_factor, expert_form=c.expert_form)
    x = params["token_embed"]["embedding"].astype(jnp.float32)[
        batch["input_ids"]]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c.rms_eps)
    x = x.reshape(-1, x.shape[-1])
    rows = min(PROBE_ROWS, x.shape[0])
    x = x.reshape(-1, 1, rows, x.shape[-1])
    routed_only = ("router", "w_gate", "w_up", "w_down")
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for _, lp in blocks(c, params):
            if "moe" not in lp:
                continue
            variables = {"params": {k: lp["moe"][k] for k in routed_only}}

            def energy(rows_of_x, variables=variables):
                y, _ = layer.apply(variables, rows_of_x)
                return jnp.sum(jnp.square(y.astype(jnp.float32)))

            total = total + jnp.sum(jax.lax.map(energy, x)) / (
                x.shape[0] * rows)
    return total


def window_edge_probe(model, batch):
    """What the band lets through at its EDGE, through the program's own
    attention call as a sliding layer makes it (``hybrid_decoder.
    dot_product_attention`` with the layer's window and the batch's segment
    ids: the band kernels, in the compute dtype, at the sliding layers' head
    counts). Every key whose position is a multiple of the window is a
    SPIKE (its key ``SPIKE`` times a unit vector that every query is; its
    value one of two unit vectors, by the parity of the multiple); every
    other key is zero. A query sees, inside its document, the spikes at
    distances ``0 .. window - 1``: with the window right, a query whose
    position is ``0`` or ``window - 1`` past a multiple sees exactly ONE
    (``|o|^2`` near 1; fewer if its document began in between), one key
    more sees two of them there (``|o|^2`` near 1/2), one key less none
    (near 0). The probe is ``mean |o|^2`` over those edge queries of the
    window's first row and every head."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd

    c = model.cfg
    w, d = c.sliding_window, c.head_dim
    seg = batch.get("segment_ids")
    s = batch["input_ids"].shape[1]
    seg = (jnp.zeros((1, s), jnp.int32) if seg is None
           else seg[:1].astype(jnp.int32))
    t = jnp.arange(s)
    spike = (t % w == 0)
    unit = jnp.zeros((d,), jnp.float32).at[0].set(1.0)
    heads = c.sliding_num_heads or c.num_heads
    q = jnp.broadcast_to(unit, (1, s, heads, d)).astype(c.dtype)
    k = jnp.broadcast_to(jnp.where(spike[:, None], SPIKE * unit, 0.0)[
        None, :, None, :], (1, s, c.num_kv_heads, d)).astype(c.dtype)
    parity = jax.nn.one_hot(1 + (t // w) % 2, d) * spike[:, None]
    v = jnp.broadcast_to(parity[None, :, None, :],
                         (1, s, c.num_kv_heads, d)).astype(c.dtype)
    o = hd.dot_product_attention(q, k, v, causal=True, segment_ids=seg,
                                 window=w).astype(jnp.float32)
    edge = (t % w == 0) | (t % w == w - 1)
    energy = jnp.sum(jnp.square(o[0]), axis=-1)              # [S, heads]
    return jnp.sum(jnp.where(edge[:, None], energy, 0.0)) / (
        jnp.sum(edge) * heads)


def rotary_probe(model, batch):
    """The rotary embedding of both layer kinds as the program makes it
    (``hybrid_decoder.document_positions`` of the batch's segment ids, and
    ``rotary_embedding`` under ``hybrid_decoder.rotary_of`` of the kind) in
    float32, applied to the all-ones head: ``sum over the two kinds of
    mean_t |rot(1, pos_t) - 1|^2 / head_dim``. It reads the positions'
    restart at each document (the attention's scores see only differences
    of positions inside one document, which a restart does not move), the
    frequencies of each kind (YaRN's on the full layers, theta's on the
    sliding ones), the share of the head that turns, and the factor on cos
    and sin."""
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd
    from distributeddeeplearningspark_tpu.models.llama import (
        rotary_embedding,
    )

    c = model.cfg
    seg = batch.get("segment_ids")
    seg = (jnp.zeros(batch["input_ids"].shape, jnp.int32) if seg is None
           else seg.astype(jnp.int32))
    pos = hd.document_positions(seg)
    ones = jnp.ones((*pos.shape, 1, c.head_dim), jnp.float32)
    total = 0.0
    for sliding in (False, True):
        r = rotary_embedding(ones, pos, **hd.rotary_of(c, sliding))
        total = total + jnp.mean(jnp.sum(jnp.square(r - 1.0), -1)) / c.head_dim
    return total


def step_parts(model, loss_fn, params, batch) -> dict:
    """ONE forward pass of the program's model as the step runs it (compute
    dtype, kernels and window of the cell), and of it: ``loss`` (its
    training loss, the Switch term included), ``window_attention_energy``
    (the sum over the sliding blocks of ``mean_t |OP_t|^2`` of
    ``CausalAttention``'s output, gate and ``wo`` included, ON THE BLOCK'S
    OWN INPUT), ``full_attention_energy`` (the same of the full blocks),
    ``experts_energy`` (the sum over the expert blocks of ``mean_t |y_t|^2``
    of the ROUTED experts' part, which ``RoutedExperts`` sows beside the
    shared expert's) and ``rows_held_share`` (the model's counter). The
    energies are read off the operators' outputs inside that pass, so what
    they hold is the timed path itself: the band kernels at 64 / 8 heads
    and the causal ones at 48 / 8 with segment ids, the sort, gathers and
    grouped products of the experts on real hidden states."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearningspark_tpu.models import hybrid_decoder as hd

    c = model.cfg
    out, seen = model.apply(
        {"params": params}, batch, train=False, mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, hd.CausalAttention))
    square = lambda y: jnp.sum(jnp.square(y.astype(jnp.float32)), -1)
    mean = lambda e: jnp.sum(e) / e.size
    energy = {hd.SLIDING: 0.0, hd.ATTENTION: 0.0}
    experts = 0.0
    for kind, ops in blocks(c, seen["intermediates"]):
        (y,) = ops["self_attn"]["__call__"]
        energy[kind] = energy[kind] + mean(square(y))
        if "moe" in ops:
            (y,) = ops["moe"]["routed"]
            experts = experts + mean(square(y))
    return {"loss": loss_fn(out, batch)[0], **jax.lax.stop_gradient({
        "window_attention_energy": energy[hd.SLIDING],
        "full_attention_energy": energy[hd.ATTENTION],
        "experts_energy": experts,
        "rows_held_share": out["moe_rows_held_share"]})}


def parts(model, loss_fn, params, mutable, batch) -> dict:
    """The terms of :func:`program_loss` by name (``check.why`` in the
    configuration's file says what each is for): those of :func:`step_parts`
    and the three probes; but for ``loss`` none carries a gradient. (The
    model holds no mutable collection: ``mutable`` is empty.)"""
    import jax

    del mutable
    return {**step_parts(model, loss_fn, params, batch),
            **jax.lax.stop_gradient({
                "expert_probe": expert_probe(model, params, batch),
                "window_edge_probe": window_edge_probe(model, batch),
                "rotary_probe": rotary_probe(model, batch)})}


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
