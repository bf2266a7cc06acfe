"""Plain reference for ``lfm2_24b_a2b``: the forward pass and the training
loss of LFM2-24B-A2B's decoder (HF ``lfm2_moe``) in straightforward float32
``jax.numpy``, written from the model's ``config.json`` (widths, head counts,
``layer_types``, ``num_dense_layers``, ``conv_L_cache``) and, where the
config is silent, from the items listed under ``assumed`` in the
configuration's file. No kernel and no module of the program: ``jnp`` shifts
make the convolution, a mask makes the attention, ``jax.lax.top_k`` the
selection, a loop walks the experts held, and gradients are ``jax.grad`` of
this. It reads the program's parameter tree and its bias collection by their
names and nothing else of it.

One block, ``x [S, hidden]``, ``seg[t]`` the document of position ``t``,
``pos[t] = t -`` the first position of that document in the window::

    h  = x + OP(RMSNorm_op(x));   x' = h + FFN(RMSNorm_ffn(h))
    OP conv:       (B, C, u) = split3(x Win);  v = B * u
                   z[t] = sum_{j<3} w[:, j] * v[t-2+j] [t-2+j >= 0 and
                          seg[t-2+j] = seg[t]]
                   OP = (C * z) Wout
    OP attention:  q, k = rotary(RMSNorm_head(x Wq), pos), rotary(RMSNorm_head(
                   x Wk), pos); p[t, .] = softmax over {s <= t, seg[s] =
                   seg[t]} of q[t].k[s] / 8;  OP = (p v) Wo
    FFN l < num_dense_layers:  W2(silu(W1 x) * W3 x)
    FFN otherwise: s = sigmoid(x Wg); E_t = top-4 of (s + b)
                   g_e = s_e / (sum_{E_t} s + 1e-6)    (routed_scaling_factor 1;
                   no gradient through g where the configuration says
                   ``train_router`` false: a share without its exchange)
                   sum over e in E_t HELD here of g_e W2_e(silu(W1_e x) * W3_e x)
    logits = RMSNorm_final(x) Embed^T; mean next-token cross-entropy over
    every position but the window's last

Departures from HF's code, each an item of ``assumed``: HF's convolution and
attention know nothing of packed documents (the masks ``seg[..] = seg[t]``
and the restarting ``pos`` are this file's), HF's ``lfm2_moe`` computes every
expert (here the loop is over the experts HELD, the deployment's share), and
the bias ``b`` is read from the ``mutable`` argument, where HF keeps a buffer
``expert_bias``; how the bias moves is the program's step, not the forward
pass, and is not part of this file.

Sized to run beside the trainer's state at one 32,768-window: whatever is
per position (projections, feed-forwards, the head) runs a block of rows at
a time, one block after the other (``lax.map``) under ``jax.checkpoint``;
the convolution runs 512 channels at a time and attention one key-value head
with its four query heads at a time, 256 queries against all keys (the largest
live arrays are ``[4, 256, S]``), the groups' parts of the output projection
adding up; the experts held are a Python loop inside a block of rows; every
layer is under ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 2048          # rows of a per-position block
QUERIES = 256        # queries of an attention block (of one key-value head)
CHANNELS = 512       # channels of a convolution group
REACHED = 2048       # the most positions boundary_rows gathers of a window
NEG = float("-inf")


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


def positions(seg):
    """``[S]`` segment ids -> the index of each position in its document."""
    idx = jnp.arange(seg.shape[0])
    starts = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0))


def _rotary(x, pos, theta):
    """``[S, heads, d]``, rotate-half convention, positions ``pos [S]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summed_over(groups, part):
    """``sum_g part(g)`` for ``g < groups``, one group after the other, each
    under ``jax.checkpoint`` (the sum itself outside it, so that no running
    sum is kept for the backward pass)."""
    part = jax.checkpoint(part)
    first = part(jnp.int32(0))
    if groups == 1:
        return first
    total, _ = jax.lax.scan(lambda acc, g: (acc + part(g), None), first,
                            jnp.arange(1, groups, dtype=jnp.int32))
    return total


def short_conv(x, p, norm, seg, eps):
    """The gated short convolution of the block's input ``x [S, hidden]``,
    output projection included. A depthwise convolution mixes no channels, so
    it runs ``CHANNELS`` channels at a time and the groups' parts of the
    output projection add up."""
    s, hidden = x.shape
    taps = p["taps"].shape[1]
    width = min(CHANNELS, hidden)
    w_in, w_out = p["in_proj"]["kernel"], p["out_proj"]["kernel"]

    def part(g):
        cols = lambda third: jax.lax.dynamic_slice(
            w_in, (0, third * hidden + g * width), (hidden, width))
        w_b, w_c, w_u = cols(0), cols(1), cols(2)
        w = jax.lax.dynamic_slice(p["taps"], (g * width, 0), (width, taps))

        def gates(rows):
            h = _rms(rows, norm, eps)
            return (h @ w_b) * (h @ w_u), h @ w_c

        v, c = by_rows(gates, x)
        z = w[:, taps - 1] * v
        for d in range(1, taps):
            shifted = jnp.pad(v, ((d, 0), (0, 0)))[:s]
            ok = (jnp.arange(s) >= d) & (
                jnp.pad(seg, (d, 0), constant_values=-1)[:s] == seg)
            z = z + w[:, taps - 1 - d] * jnp.where(ok[:, None], shifted, 0.0)
        rows_out = jax.lax.dynamic_slice(w_out, (g * width, 0),
                                         (width, hidden))
        return by_rows(lambda cz: cz @ rows_out, c * z)

    return summed_over(hidden // width, part)


def attention(x, p, norm, seg, pos, cfg):
    """Causal grouped-query attention inside a document of the block's input
    ``x [S, hidden]``, output projection included: one key-value head with
    its query heads at a time (heads mix nothing), the heads' parts of the
    output projection adding up."""
    eps = cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    kv_heads = cfg["num_key_value_heads"]
    group = cfg["num_attention_heads"] // kv_heads
    s = x.shape[0]
    n = min(QUERIES, s)
    where = jnp.arange(s)

    def part(g):
        heads = lambda w, count, axis: jax.lax.dynamic_slice_in_dim(
            w, g * count, count, axis)
        wq = heads(p["wq"]["kernel"], group, 1)      # [hidden, group, d]
        wk = heads(p["wk"]["kernel"], 1, 1)[:, 0]    # [hidden, d]
        wv = heads(p["wv"]["kernel"], 1, 1)[:, 0]
        wo = heads(p["wo"]["kernel"], group, 0)      # [group, d, hidden]

        def qkv(rows, at):
            rows = _rms(rows, norm, eps)
            q = jnp.einsum("sh,hnd->snd", rows, wq)
            k = (rows @ wk)[:, None, :]
            return (_rotary(_rms(q, p["q_norm"]["scale"], eps), at, theta),
                    _rotary(_rms(k, p["k_norm"]["scale"], eps), at,
                            theta)[:, 0], rows @ wv)

        q, k, v = by_rows(qkv, x, pos)               # [S, group, d], [S, d]
        d = q.shape[-1]

        @jax.checkpoint
        def block(t0):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, n)
            logits = jnp.einsum("tnd,sd->nts", cut(q), k) / jnp.sqrt(
                jnp.float32(d))
            ok = (where[None, :] <= cut(where)[:, None]) & (
                seg[None, :] == cut(seg)[:, None])
            probs = jax.nn.softmax(jnp.where(ok, logits, NEG), axis=-1)
            return jnp.einsum("nts,sd->tnd", probs, v)

        o = jax.lax.map(block, jnp.arange(0, s, n)).reshape(s, group, d)
        return by_rows(lambda rows: jnp.einsum("snd,ndh->sh", rows, wo), o)

    return summed_over(kv_heads, part)


def swiglu(h, p):
    return (jax.nn.silu(h @ p["w1"]["kernel"]) * (h @ p["w3"]["kernel"])
            ) @ p["w2"]["kernel"]


def routed(h, moe, bias, cfg, held=None):
    """Normed tokens ``[T, hidden]`` -> the held experts' part of the layer.
    ``held = (first, count)`` defaults to the configuration's; the kernels
    ``w_*`` hold exactly those experts, the router and ``bias`` all of them."""
    first, count = held or cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    score = jax.nn.sigmoid(h @ moe["router"])
    _, idx = jax.lax.top_k(score + bias, k)
    gate = jnp.take_along_axis(score, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-6)
    if not cfg.get("train_router", True):
        gate = jax.lax.stop_gradient(gate)
    y = jnp.zeros_like(h)
    for j in range(count):   # the loop over the experts held
        g = jnp.sum(gate * (idx == first + j), axis=-1)
        hid = jax.nn.silu(h @ moe["w_gate"][j]) * (h @ moe["w_up"][j])
        y = y + g[:, None] * (hid @ moe["w_down"][j])
    return y


def _unstacked(a, n):
    """Period ``n`` of a stacked leaf; a reshape where there is one period
    (a slice is a copy, and a layer's parameters weigh 0.35 GB)."""
    return a.reshape(a.shape[1:]) if a.shape[0] == 1 else a[n]


def layers_of(params, mutable, cfg):
    """``[(kind, dense?, the layer's parameters, its bias or None)]`` in
    layer order, from the program's tree: ``lead_<i>`` the leading dense
    layers, ``periods/layer_<j>`` the scanned periods' layers stacked on
    their first axis, ``trail_<i>`` what is left."""
    kinds = list(cfg["layer_types"])
    dense = cfg["num_dense_layers"]
    rest = kinds[dense:]
    period = next((n for n in range(1, len(rest) + 1)
                   if all(rest[i] == rest[i - n]
                          for i in range(n, len(rest)))), 0)
    whole = len(rest) // period if period else 0
    zeros = jnp.zeros((cfg["router_width"],), jnp.float32)

    def bias_of(*path):
        """The bias the program keeps under ``path`` of its collection, or
        None where it keeps none (then the layer selects on zeros)."""
        node = (mutable or {}).get("router_bias", {})
        for key in (*path, "moe", "bias"):
            node = node.get(key) if isinstance(node, dict) else None
        return node

    out = [(kinds[i], True, params[f"lead_{i}"], None) for i in range(dense)]
    for n in range(whole):
        for j in range(period):
            b = bias_of("periods", f"layer_{j}")
            out.append((rest[j], False,
                        jax.tree.map(lambda a, n=n: _unstacked(a, n),
                                     params["periods"][f"layer_{j}"]),
                        zeros if b is None else b[n]))
    for i, kind in enumerate(rest[whole * period:]):
        b = bias_of(f"trail_{i}")
        out.append((kind, False, params[f"trail_{i}"],
                    zeros if b is None else b))
    return out


def layer(x, seg, pos, kind, dense, p, bias, cfg):
    """``[S, hidden] -> ([S, hidden], experts)``: one block. What mixes
    positions (the taps, attention) runs on whole columns, a group of
    channels or of heads at a time; the residuals, the second norm and the
    feed-forward are per position and run a block of rows at a time.
    ``experts`` is the routed experts' output squared and summed (0 for a
    dense layer): what :func:`forward` sums."""
    eps = cfg["norm_eps"]
    if kind == "conv":
        mixed = short_conv(x, p["conv"], p["operator_norm"]["scale"], seg, eps)
    else:
        mixed = attention(x, p["self_attn"], p["operator_norm"]["scale"], seg,
                          pos, cfg)

    def rest(rows, mixed_rows):
        rows = rows + mixed_rows
        h = _rms(rows, p["ffn_norm"]["scale"], eps)
        if dense:
            return rows + swiglu(h, p["mlp"]), jnp.zeros(rows.shape[:1])
        y = routed(h, p["moe"], bias, cfg)
        return rows + y, jnp.sum(jnp.square(jax.lax.stop_gradient(y)), -1)

    out, energy = by_rows(rest, x, mixed)
    return out, jnp.sum(energy)


def boundary_rows(x, p, norm, pos, eps):
    """The convolution operator's output AT THE POSITIONS ITS TAPS REACH BACK
    ACROSS A DOCUMENT'S START (the first ``taps - 1`` of every document),
    squared and summed. The operator's output at ``t``
    needs the block's input at ``t - taps + 1 .. t`` alone, so those rows are
    gathered and computed apart from :func:`short_conv` (a few hundred
    positions of a window; more than ``REACHED`` of them give NaN, which no
    tolerance admits)."""
    hidden = x.shape[1]
    taps = p["taps"].shape[1]
    reached = pos < taps - 1
    count = jnp.sum(reached)
    size = min(REACHED, x.shape[0])
    at = jnp.nonzero(reached, size=size, fill_value=0)[0]
    w_b, w_c, w_u = (p["in_proj"]["kernel"][:, i * hidden:(i + 1) * hidden]
                     for i in range(3))
    z, c = 0.0, None
    for d in range(taps):
        h = _rms(x[jnp.maximum(at - d, 0)], norm, eps)
        inside = (pos[at] >= d)[:, None]        # t - d is in t's document
        z = z + p["taps"][:, taps - 1 - d] * jnp.where(
            inside, (h @ w_b) * (h @ w_u), 0.0)
        c = h @ w_c if d == 0 else c
    y = (c * z) @ p["out_proj"]["kernel"]
    valid = (jnp.arange(size) < count)[:, None]
    energy = jnp.sum(jnp.where(valid, y * y, 0.0))
    return jnp.where(count <= size, energy, jnp.nan)


def window_nll(x, p, ids, eps):
    """Summed next-token cross-entropy of one window ``x [S, hidden]`` under
    the final norm and the tied head, every position but the last, a block
    of positions at a time."""
    s = x.shape[0]
    embedding = p["token_embed"]["embedding"]

    def block(rows, labels, counts):
        logp = jax.nn.log_softmax(
            _rms(rows, p["final_norm"]["scale"], eps) @ embedding.T, axis=-1)
        return -counts * jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]

    return jnp.sum(by_rows(block, x, jnp.roll(ids, -1),
                           (jnp.arange(s) < s - 1).astype(jnp.float32),
                           rows=1024))


def forward(p, mutable, batch, cfg):
    """The model's loss on float32 parameters ``p``, and what the forward
    pass that gives it saw on the way (the configuration's ``step_parts``):
    ``experts_energy``, the sum over the expert layers of ``mean_t |y_t|^2``
    of the routed experts' output on the layer's own input, and
    ``boundary_energy``, the sum over the convolution layers of the mean,
    over the first ``taps - 1`` positions of every document, of
    ``|OP(RMSNorm_op(x))_t|^2``."""
    ids = batch["input_ids"]
    segs = batch.get("segment_ids")
    segs = jnp.zeros_like(ids) if segs is None else segs
    total, experts, boundary, reached = 0.0, 0.0, 0.0, 0.0
    for row_ids, seg in zip(ids, segs):   # a window at a time: no operator
        x = p["token_embed"]["embedding"][row_ids]   # mixes them
        pos = positions(seg)
        reached = reached + jnp.sum(pos < cfg["conv_L_cache"] - 1)
        for kind, dense, lp, bias in layers_of(p, mutable, cfg):
            if kind == "conv":
                boundary = boundary + boundary_rows(*jax.lax.stop_gradient((
                    x, lp["conv"], lp["operator_norm"]["scale"])), pos,
                    cfg["norm_eps"])
            x, energy = jax.checkpoint(
                lambda x, lp, bias, kind=kind, dense=dense: layer(
                    x, seg, pos, kind, dense, lp, bias, cfg))(x, lp, bias)
            experts = experts + energy
        total = total + window_nll(x, p, row_ids, cfg["norm_eps"])
    return total / (ids.shape[0] * (ids.shape[1] - 1)), {
        "experts_energy": experts / ids.size,
        "boundary_energy": boundary / reached}


def training_loss(p, mutable, batch, cfg):
    """The model's loss on float32 parameters ``p``."""
    return forward(p, mutable, batch, cfg)[0]


def weigh(params, weights):
    """Each leaf unchanged, its cotangent times the leaf's weight
    (``check.grad_leaf_weights`` of the configuration's file, by path)."""
    @jax.custom_vjp
    def scaled(x, w):
        return x

    scaled.defvjp(lambda x, w: (x, w), lambda w, g: (g * w, None))

    def one(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        return scaled(x, jnp.float32(weights[name])) if name in weights else x

    return jax.tree_util.tree_map_with_path(one, params)


def expert_probe(p, mutable, ids, cfg):
    """``sum over expert layers of mean_t |y_t|^2`` of the experts' part on
    the RMS-normed embedding rows of ``ids`` (the probe of the
    configuration's ``program_loss``: what it is for is said there)."""
    x = p["token_embed"]["embedding"][ids]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"])
    x = x.reshape(-1, x.shape[-1])
    total = 0.0
    for _, dense, lp, bias in layers_of(p, mutable, cfg):
        if not dense:
            y = by_rows(lambda rows, lp=lp, bias=bias: routed(
                rows, lp["moe"], bias, cfg), x, rows=8192)
            total = total + jnp.mean(jnp.sum(jnp.square(y), -1))
    return total


def boundary_probe(p, batch, cfg):
    """``sum over convolution layers of the mean, over the first taps - 1
    positions of every document, of |OP(RMSNorm_op(x))_t|^2`` on the
    RMS-normed embedding rows of the window (the second probe of the
    configuration's ``program_loss``)."""
    ids = batch["input_ids"]
    segs = batch.get("segment_ids")
    segs = jnp.zeros_like(ids) if segs is None else segs
    eps = cfg["norm_eps"]
    total, count = 0.0, 0.0
    for row_ids, seg in zip(ids, segs):
        x = p["token_embed"]["embedding"][row_ids]
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        reached = (positions(seg) < cfg["conv_L_cache"] - 1)[:, None]
        count = count + jnp.sum(reached)
        for kind, _, lp, _ in layers_of(p, {}, cfg):
            if kind == "conv":
                y = short_conv(x, lp["conv"], lp["operator_norm"]["scale"],
                               seg, eps)
                total = total + jnp.sum(jnp.where(reached, y * y, 0.0))
    return total / count


def parts(params, mutable, batch, cfg):
    """The terms of :func:`loss`, by the names the configuration's ``parts``
    gives the program's: ``loss`` (each leaf's cotangent weighted), the two
    energies of :func:`forward` and the two float32 probes; but for ``loss``
    none carries a gradient."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        # (the probes first: after the forward pass their temporaries would
        # lie on top of what it keeps for the backward pass)
        probes = jax.lax.stop_gradient({
            "expert_probe": expert_probe(p, mutable, batch["input_ids"], cfg),
            "boundary_probe": boundary_probe(p, batch, cfg)})
        weights = cfg["check"].get("grad_leaf_weights", {})
        value, seen = forward(weigh(p, weights), mutable, batch, cfg)
        return {"loss": value, **jax.lax.stop_gradient(seen), **probes}


def compared(terms, cfg):
    """The ONE scalar of the comparison from :func:`parts`' terms: the loss,
    plus the logarithm of every other term times its weight in
    ``check.term_weights`` (``check.why`` in the configuration's file says
    what each term is for and why it weighs what it does)."""
    return terms["loss"] + sum(w * jnp.log(terms[name]) for name, w in
                               cfg["check"]["term_weights"].items())


def loss(params, mutable, batch, cfg):
    """What the harness compares with the configuration's
    ``program_loss``."""
    return compared(parts(params, mutable, batch, cfg), cfg)
