"""Plain reference for ``joyai_llm_flash``: the forward pass and the training
loss of JoyAI-LLM-Flash's decoder (``model_type`` ``joyai_llm_flash``, whose
keys are DeepSeek-V3's one for one) in straightforward float32 ``jax.numpy``,
written from the model's ``config.json`` and arXiv:2412.19437 sections 2.1-2.2
(latent attention, eq. 1-11; the router, eq. 12-16; multi-token prediction,
eq. 21-25) and, where those are silent, from the items under ``assumed`` in
the configuration's file. No kernel and no module of the program: a mask
makes the attention, ``jax.lax.top_k`` the selection, a loop walks the
experts held, and gradients are ``jax.grad`` of this. It reads the program's
parameter tree and its bias collection by their names and nothing else of it.

One block, ``x [S, 2048]``, ``t`` a position, RMSNorm eps 1e-6::

    h  = x + ATT(RMSNorm_op(x));   x' = h + FFN(RMSNorm_ffn(h))
    ATT: c_q = RMSNorm(x Wdq) (1536);  [q_nope | q_rot] = c_q Wuq  (32 heads
         of 128 + 64);  [c | k_r] = x Wdkv (512 + 64);  c_kv = RMSNorm(c);
         [k_nope | v] = c_kv Wukv  (32 heads of 128 + 128)
         q_rot, k_r <- rotary(., t) over ADJACENT pairs (2i, 2i+1), theta
         32e6; k_r is ONE head that all 32 query heads read
         p[t, .] = softmax over {s <= t} of (q_nope[t].k_nope[s] +
         q_rot[t].k_r[s]) / sqrt(192);  ATT = concat_h(p v_h) Wo
    FFN layer 0: W2(silu(W1 x) * W3 x)   (7168)
    FFN else:    s = sigmoid(x Wg) (256);  E_t = top-8 of (s + b)  (one
                 group: n_group 1, topk_group 1)
                 g_e = 2.5 * s_e / (sum_{E_t} s + 1e-20)   (no gradient through
                 g where the configuration says ``train_router`` false)
                 sum over e in E_t HELD here of g_e expert_e(x) + shared(x),
                 each a SwiGLU of 768
    L_main = mean next-token cross-entropy of RMSNorm_final(x) W_head over
             every position but the window's last

Multi-token prediction, depth 1, in the EXACT form over ``S - 1`` rows, ``h``
the last block's output before the final norm::

    u_t = W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(h_t)],  t < S - 1
    one more block (ATT + its own router, experts, shared expert) over u
    L_mtp = mean over t < S - 2 of the cross-entropy of
            RMSNorm_mtp(.) W_head (the MAIN head) against x_{t+2}
    L = L_main + 0.1 * L_mtp

Departures from the released code, each an item of ``assumed``: the loop is
over the experts HELD (the deployment's share); the bias ``b`` is read from
the ``mutable`` argument, where the release keeps a buffer
``e_score_correction_bias``, and how it moves is the program's step, not the
forward pass; the release permutes the rotary dimensions to the half-split
layout before rotating them, which gives the same scores as rotating the
adjacent pairs in place, as here.

Sized to run beside the trainer's state at one 16,384-window: what is per
position runs a block of rows at a time (``lax.map``) under
``jax.checkpoint``; attention one head at a time, 512 queries against all
keys (the largest live array is ``[512, S]``), the heads' parts of the output
projection adding up; the experts held are a Python loop inside a block of
rows; every layer is under ``jax.checkpoint``, and the four expert layers,
one function of four sets of arrays, are one ``lax.scan`` (written out one
after the other they cost the chip's compiler four minutes a run).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

ROWS = 2048          # rows of a per-position block
QUERIES = 512        # queries of an attention block (of one head)
NEG = float("-inf")
#: added to ``latents_spread`` before its logarithm is taken: far above what
#: bf16 rounding and a learned scale leave behind a norm (2e-7 over the six
#: blocks at these widths), far below 2 / 1536 a block
LATENTS_FLOOR = 1e-4


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def by_rows(fn, *arrays, rows=ROWS):
    """``fn`` over blocks of rows of ``arrays`` (same leading size), one
    block after the other, each under ``jax.checkpoint``; the outputs'
    blocks joined again."""
    n = arrays[0].shape[0]
    step = min(rows, n)
    pad = -n % step
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        -1, step, *a.shape[1:])
    out = jax.lax.map(jax.checkpoint(lambda args: fn(*args)),
                      tuple(cut(a) for a in arrays))
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:])[:n], out)


def summed_over(groups, part):
    """``sum_g part(g)`` for ``g < groups``, one after the other, each under
    ``jax.checkpoint`` (the sum itself outside it)."""
    part = jax.checkpoint(part)
    first = part(jnp.int32(0))
    if groups == 1:
        return first
    total, _ = jax.lax.scan(lambda acc, g: (acc + part(g), None), first,
                            jnp.arange(1, groups, dtype=jnp.int32))
    return total


def positions(seg):
    """``[S]`` segment ids -> the index of each position in its document."""
    idx = jnp.arange(seg.shape[0])
    starts = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0))


def _rotary(x, pos, theta):
    """``[S, d]`` rotated over ADJACENT pairs ``(2i, 2i+1)`` by the angle
    ``pos * theta^(-2i/d)``, the result in the same layout."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(x, p, norm, seg, pos, cfg):
    """Causal latent attention of the block's input ``x [S, hidden]``, output
    projection included: the two latents and the shared rotary key once, then
    one head at a time (heads mix nothing), the heads' parts of the output
    projection adding up."""
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    s = x.shape[0]
    n = min(QUERIES, s)
    where = jnp.arange(s)

    def latents(rows, at):
        rows = _rms(rows, norm, eps)
        c_kr = rows @ p["wkv_a"]["kernel"]
        return (_rms(rows @ p["wq_a"]["kernel"], p["q_norm"]["scale"], eps),
                _rms(c_kr[:, :rank], p["kv_norm"]["scale"], eps),
                _rotary(c_kr[:, rank:], at, theta))

    c_q, c_kv, k_rot = by_rows(latents, x, pos)   # [S, 1536], [S, 512], [S, 64]
    spread = jax.lax.stop_gradient(token_spread(c_q) + token_spread(c_kv))
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rot))

    def part(g):
        head = lambda w, axis: jax.lax.dynamic_index_in_dim(
            w, g, axis, keepdims=False)
        wq = head(p["wq_b"]["kernel"], 1)         # [1536, 192]
        wkv = head(p["wkv_b"]["kernel"], 1)       # [512, 256]
        wo = head(p["wo"]["kernel"], 0)           # [128, hidden]
        q = c_q @ wq
        q_nope, q_rot = q[:, :nope], _rotary(q[:, nope:], pos, theta)
        kv = c_kv @ wkv
        k_nope, v = kv[:, :nope], kv[:, nope:]
        # (S - 1 rows, the module's, fill no whole number of blocks: the
        # queries are padded, the padded rows' outputs cut off again)
        pad = -s % n
        q_nope, q_rot = (jnp.pad(a, ((0, pad), (0, 0))) for a in (q_nope, q_rot))
        q_seg = jnp.pad(seg, (0, pad))

        @jax.checkpoint
        def block(t0):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, n)
            logits = (cut(q_nope) @ k_nope.T + cut(q_rot) @ k_rot.T) * scale
            ok = (where[None, :] <= (t0 + jnp.arange(n))[:, None]) & (
                seg[None, :] == cut(q_seg)[:, None])
            return jax.nn.softmax(jnp.where(ok, logits, NEG), axis=-1) @ v

        o = jax.lax.map(block, jnp.arange(0, s + pad, n)).reshape(-1, dv)[:s]
        return o @ wo

    return summed_over(cfg["num_attention_heads"], part), spread


def token_spread(latent):
    """The variance over the tokens of a latent's mean square a token: next
    to nothing behind an RMSNorm (every token's is the mean of the scale
    squared, to rounding), 2 / width of its square and more without one."""
    return jnp.var(jnp.mean(jnp.square(latent), axis=-1))


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routed(h, moe, bias, cfg, held=None):
    """Normed tokens ``[T, hidden]`` -> the held ROUTED experts' part of the
    layer (without the shared expert). ``held = (first, count)`` defaults to
    the configuration's; the kernels ``w_*`` hold exactly those experts, the
    router and ``bias`` all of them."""
    first, count = held or cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    score = jax.nn.sigmoid(h @ moe["router"])
    _, idx = jax.lax.top_k(score + bias, k)
    gate = jnp.take_along_axis(score, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]
    if not cfg.get("train_router", True):
        gate = jax.lax.stop_gradient(gate)

    @jax.checkpoint
    def add_expert(y, expert):   # the loop over the experts held
        j, w_gate, w_up, w_down = expert
        g = jnp.sum(gate * (idx == first + j), axis=-1)
        return y + g[:, None] * swiglu(h, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        jnp.arange(count), moe["w_gate"], moe["w_up"], moe["w_down"]))
    return y


def shared(h, moe):
    """The shared expert every token passes (``n_shared_experts`` 1)."""
    return swiglu(h, moe["shared_gate"]["kernel"], moe["shared_up"]["kernel"],
                  moe["shared_down"]["kernel"])


def expert_layers(params, mutable, cfg):
    """``(parameters, biases)`` of the MAIN model's expert layers as the
    program keeps them, stacked on a leading axis (``periods/layer_0``: the
    period is one layer), for :func:`jax.lax.scan`: the layers are one
    function of different arrays, and written out one after the other they
    cost the compiler four times the program."""
    count = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    bias = _bias(mutable, cfg, "periods", "layer_0")
    return params["periods"]["layer_0"], jnp.broadcast_to(
        bias, (count, cfg["router_width"]))


def _bias(mutable, cfg, *path):
    """The selection bias the program keeps under ``path`` of its
    collection; zeros where it keeps none."""
    node = (mutable or {}).get("router_bias", {})
    for key in (*path, "moe", "bias"):
        node = node.get(key) if isinstance(node, dict) else None
    return (jnp.zeros((cfg["router_width"],), jnp.float32) if node is None
            else node)


def layer(x, seg, pos, dense, p, bias, cfg):
    """``[S, hidden] -> ([S, hidden], attention, experts, latents)``: one
    block. ``attention`` is the operator's output squared and summed,
    ``experts`` the ROUTED experts' (0 for a dense layer), ``latents`` the
    two latents' :func:`token_spread`: what :func:`forward` sums."""
    eps = cfg["rms_norm_eps"]
    mixed, spread = latent_attention(
        x, p["self_attn"], p["operator_norm"]["scale"], seg, pos, cfg)

    def rest(rows, mixed_rows):
        rows = rows + mixed_rows
        h = _rms(rows, p["ffn_norm"]["scale"], eps)
        if dense:
            mlp = p["mlp"]
            return (rows + swiglu(h, mlp["w1"]["kernel"], mlp["w3"]["kernel"],
                                  mlp["w2"]["kernel"]),
                    jnp.zeros(rows.shape[:1]))
        y = routed(h, p["moe"], bias, cfg)
        return (rows + y + shared(h, p["moe"]),
                jnp.sum(jnp.square(jax.lax.stop_gradient(y)), -1))

    out, energy = by_rows(rest, x, mixed)
    return (out, jnp.sum(jnp.square(jax.lax.stop_gradient(mixed))),
            jnp.sum(energy), spread)


def head_nll(x, norm, head, labels, eps):
    """Summed cross-entropy of rows ``x [N, hidden]`` under ``norm`` and the
    head against ``labels [N]``, a block of rows at a time."""
    def block(rows, targets):
        logp = jax.nn.log_softmax(_rms(rows, norm, eps) @ head, axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    return jnp.sum(by_rows(block, x, labels, rows=1024))


def forward(p, mutable, batch, cfg):
    """The model's training loss on float32 parameters ``p`` (both terms),
    and what the forward pass that gives it saw on the way (the
    configuration's ``step_parts``): ``attention_energy``, the sum over the
    six blocks of ``mean_t |ATT(RMSNorm_op(x))_t|^2``, ``experts_energy``,
    the sum over the five expert blocks of ``mean_t |y_t|^2`` of the ROUTED
    experts' output on the block's own input, ``latents_spread``,
    ``LATENTS_FLOOR`` plus the sum over the six blocks and both latents of
    :func:`token_spread`, and the two loss terms."""
    ids = batch["input_ids"]
    segs = batch.get("segment_ids")
    segs = jnp.zeros_like(ids) if segs is None else segs
    eps = cfg["rms_norm_eps"]
    embedding, head = p["token_embed"]["embedding"], p["lm_head"]
    block = lambda dense: jax.checkpoint(
        lambda x, seg, pos, lp, bias: layer(x, seg, pos, dense, lp, bias, cfg))
    main, mtp, attention, experts, latents = 0.0, 0.0, 0.0, 0.0, 0.0
    for row_ids, seg in zip(ids, segs):   # a window at a time
        s = row_ids.shape[0]
        pos = positions(seg)
        x = embedding[row_ids]
        for i in range(cfg["first_k_dense_replace"]):
            x, att, _, spread = block(True)(x, seg, pos, p[f"lead_{i}"], None)
            attention, latents = attention + att / s, latents + spread

        def expert_layer(x, layer_params):
            x, *seen = block(False)(x, seg, pos, *layer_params)
            return x, seen

        x, (att, exp, spread) = jax.lax.scan(expert_layer, x,
                                             expert_layers(p, mutable, cfg))
        attention = attention + jnp.sum(att) / s
        experts = experts + jnp.sum(exp) / s
        latents = latents + jnp.sum(spread)
        main = main + head_nll(x[:-1], p["final_norm"]["scale"], head,
                               row_ids[1:], eps)
        if cfg["num_nextn_predict_layers"]:
            m = p["mtp"]
            u = by_rows(lambda nxt, h: jnp.concatenate(
                [_rms(nxt, m["enorm"]["scale"], eps),
                 _rms(h, m["hnorm"]["scale"], eps)], -1)
                @ m["eh_proj"]["kernel"], embedding[row_ids[1:]], x[:-1])
            u, att, exp, spread = block(False)(
                u, seg[:-1], pos[:-1], m["block"],
                _bias(mutable, cfg, "mtp", "block"))
            attention = attention + att / (s - 1)
            experts = experts + exp / (s - 1)
            latents = latents + spread
            mtp = mtp + head_nll(u[:-1], m["final_norm"]["scale"], head,
                                 row_ids[2:], eps)
    rows = ids.shape[0]
    lm_loss = main / (rows * (ids.shape[1] - 1))
    mtp_nll = mtp / (rows * (ids.shape[1] - 2))
    weight = cfg["assumed_values"]["mtp_loss_weight"]
    return lm_loss + weight * mtp_nll, {
        "attention_energy": attention / rows, "experts_energy": experts / rows,
        "latents_spread": LATENTS_FLOOR + latents / rows,
        "lm_loss": lm_loss, "mtp_nll": mtp_nll}


def training_loss(p, mutable, batch, cfg):
    """The model's loss on float32 parameters ``p``."""
    return forward(p, mutable, batch, cfg)[0]


def weigh(params, weights):
    """Each leaf unchanged, its cotangent times the weight of the FIRST
    pattern of ``weights`` (``check.grad_leaf_weights`` of the
    configuration's file, regular expressions searched in the leaf's path)
    that matches; 1 where none does."""
    @jax.custom_vjp
    def scaled(x, w):
        return x

    scaled.defvjp(lambda x, w: (x, w), lambda w, g: (g * w, None))

    def one(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        for pattern, w in weights.items():
            if re.search(pattern, name):
                return scaled(x, jnp.float32(w))
        return x

    return jax.tree_util.tree_map_with_path(one, params)


def expert_probe(p, mutable, ids, cfg):
    """``sum over the five expert blocks of mean_t |y_t|^2`` of the ROUTED
    experts' part on the RMS-normed embedding rows of ``ids`` (the probe of
    the configuration's ``program_loss``: what it is for is said there)."""
    x = p["token_embed"]["embedding"][ids]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    x = x.reshape(-1, x.shape[-1])
    def energy(block):
        moe, bias = block
        y = by_rows(lambda rows: routed(rows, moe, bias, cfg), x, rows=4096)
        return jnp.mean(jnp.sum(jnp.square(y), -1))

    stacked, biases = expert_layers(p, mutable, cfg)
    total = jnp.sum(jax.lax.map(energy, (stacked["moe"], biases)))
    if cfg["num_nextn_predict_layers"]:
        total = total + energy((p["mtp"]["block"]["moe"],
                                _bias(mutable, cfg, "mtp", "block")))
    return total


def parts(params, mutable, batch, cfg):
    """The terms of :func:`loss`, by the names the configuration's ``parts``
    gives the program's: ``loss`` (each leaf's cotangent weighted), the
    energies of :func:`forward` and the float32 probe; but for ``loss`` none
    carries a gradient."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        # (the probe first: after the forward pass its temporaries would lie
        # on top of what that keeps for the backward pass)
        probe = jax.lax.stop_gradient(
            expert_probe(p, mutable, batch["input_ids"], cfg))
        weights = cfg["check"].get("grad_leaf_weights", {})
        value, seen = forward(weigh(p, weights), mutable, batch, cfg)
        return {"loss": value, **jax.lax.stop_gradient(seen),
                "expert_probe": probe}


def compared(terms, cfg):
    """The ONE scalar of the comparison from :func:`parts`' terms: the loss,
    plus the logarithm of every term in ``check.term_weights`` times its
    weight (``check.why`` in the configuration's file says what each term is
    for and why it weighs what it does)."""
    return terms["loss"] + sum(w * jnp.log(terms[name]) for name, w in
                               cfg["check"]["term_weights"].items())


def loss(params, mutable, batch, cfg):
    """What the harness compares with the configuration's
    ``program_loss``."""
    return compared(parts(params, mutable, batch, cfg), cfg)
