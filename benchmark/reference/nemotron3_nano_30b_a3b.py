"""Plain reference for ``nemotron3_nano_30b_a3b``: the forward pass and the
training loss of NVIDIA-Nemotron-3-Nano-30B-A3B's decoder (``model_type``
``nemotron_h``) in straightforward float32 ``jax.numpy``, written from the
model's ``config.json``, Mamba-2's paper (Dao and Gu, arXiv:2405.21060: the
recurrence, section 2; the block, section 7) and the Nemotron-H report
(arXiv:2504.03624 section 2.1: the pattern of one-sublayer blocks, attention
without positional embedding) and, where those are silent, from the items
under ``assumed`` in the configuration's file. No kernel and no module of the
program: the state-space recurrence is a SEQUENTIAL ``lax.scan`` over
positions (the program's is the chunked form; this one must not share its
algorithm), the convolution is shifted sums, a mask makes the attention,
``jax.lax.top_k`` the selection, a loop walks the experts held, and gradients
are ``jax.grad`` of this. It reads the program's parameter tree and its bias
collection by their names and nothing else of it.

Every block is ONE sublayer, ``x [S, 2688]``, RMSNorm eps 1e-5::

    x' = x + SUB(RMSNorm(x))
    M: [z | xBC | dt] = x Win  (4096, 6144 = 4096 + 2 x 8 x 128, and 64)
       xBC[t] = silu(b + sum_{j<4} w[:, j] xBC[t - 3 + j]) over the terms in
       t's document;  [x | B | C] = split(xBC), B and C in 8 groups of 128
       dt = softplus(dt + dt_bias);  A = -exp(A_log)         (64 heads)
       h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T   (a head: x_t 64 wide, h 64
       x 128, head h reading group h // 8; h_{t-1} = 0 where t is a
       document's first position);  y_t = h_t C_t + D x_t
       y = RMSNorm_groups(y * silu(z)) * w   (8 groups of 512, gate FIRST)
       SUB = y Wout
    *: 32 query heads and 2 key-value heads of 128 (query head g reads
       key-value head g // 16), p[t, .] = softmax over {s <= t in t's
       document} of q[t].k[s] / sqrt(128), NO positional embedding, no bias,
       no per-head norm;  SUB = concat_g(p v) Wo
    E: s = sigmoid(x Wg) (128);  E_t = top-6 of (s + b)   (one group)
       g_e = 2.5 * s_e / (sum_{E_t} s + 1e-20)  (no gradient through g where
       the configuration says ``train_router`` false)
       SUB = sum over e in E_t HELD here of g_e Wdown_e relu(Wup_e x)^2
             + Wdown_s relu(Wup_s x)^2      (1856 wide; the shared one 3712)
    L = mean next-token cross-entropy of RMSNorm_final(x) W_head over every
        position but the window's last (a target across a boundary is kept)

Departures from the released code, each an item of ``assumed``: the loop is
over the experts HELD (the deployment's share); the bias ``b`` is read from
the ``mutable`` argument, where the release keeps a buffer
``e_score_correction_bias``; documents are isolated (the release's
``seq_idx`` path of ``mamba_ssm``); ``time_step_limit`` is (0, inf), so
``dt`` is not clipped.

Sized to run beside the trainer's state at one 16,384-window. The backward
pass of a 16,384-step scan would keep a 2-MB state a position and layer, 34
GB; so the recurrence is a scan of ``STATES`` checkpointed scans of
``STATES`` positions (128 x 128), which keeps 128 states at a time and is
still position by position. What is per position runs a block of rows at a
time under ``jax.checkpoint``; a Mamba layer one GROUP of heads at a time
(a group's heads read its own ``B`` and ``C``, the gated norm is a group's,
the groups' parts of ``W_out`` add up); attention one head at a time, 512
queries against all keys; the experts held are a loop inside a block of
rows; every layer is under ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS = 2048          # rows of a per-position block
QUERIES = 512        # queries of an attention block (of one head)
STATES = 128         # positions of an inner scan of the recurrence
#: positions after a document's first that ``boundary_energy`` reads
REACH = 16
NEG = float("-inf")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def by_rows(fn, *arrays, rows=None):
    """``fn`` over blocks of rows of ``arrays`` (same leading size), one
    block after the other, each under ``jax.checkpoint``; the outputs'
    blocks joined again."""
    n = arrays[0].shape[0]
    step = min(rows or ROWS, n)
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
    total, _ = jax.lax.scan(
        lambda acc, g: (jax.tree.map(jnp.add, acc, part(g)), None), first,
        jnp.arange(1, groups, dtype=jnp.int32))
    return total


def starts(seg):
    """``[S]`` segment ids -> the positions that are a document's first."""
    return jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])


def after_boundary(seg):
    """``[S]`` bool: the first ``REACH`` positions of every document but the
    window's first (what ``boundary_energy`` is a mean over: where a state
    not reset, or a tap that crosses, is most of what a position holds)."""
    idx = jnp.arange(seg.shape[0])
    first = jax.lax.cummax(jnp.where(starts(seg), idx, 0))
    return (first > 0) & (idx - first < REACH)


def short_conv(v, w, b, seg):
    """``silu(b + sum_j w[:, j] v[t - 3 + j])`` as shifted sums, a term
    counted where ``t - 3 + j`` lies in ``t``'s document."""
    s, taps = v.shape[0], w.shape[1]
    z = w[:, taps - 1] * v
    for d in range(1, taps):
        shifted = jnp.concatenate([jnp.zeros_like(v[:d]), v[:s - d]])
        same = jnp.concatenate([jnp.zeros((d,), jnp.bool_),
                                seg[d:] == seg[:s - d]])
        z = z + w[:, taps - 1 - d] * jnp.where(same[:, None], shifted, 0.0)
    return jax.nn.silu(z + b)


def recurrence(x, dt, a, bm, cm, first):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t``,
    position by position, ``h`` zeroed before a document's first position:
    ``x [S, H, P], dt [S, H], a [H], bm, cm [S, G, N] (head h reads group h
    // (H / G)), first [S] bool -> y [S, H, P]``. A scan of checkpointed
    scans (module docstring)."""
    s, h, p = x.shape
    g, n = bm.shape[1:]
    group_of = jnp.arange(h) // (h // g)
    inner = min(STATES, s)
    pad = -s % inner
    cut = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
        -1, inner, *t.shape[1:])

    def position(state, at):
        xt, dtt, bt, ct, new = at
        bt, ct = bt[group_of], ct[group_of]              # [H, N]
        state = jnp.where(new, 0.0, state)
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, rows):
        return jax.lax.scan(position, state, rows)

    _, y = jax.lax.scan(block, jnp.zeros((h, p, n), jnp.float32),
                        tuple(cut(t) for t in (x, dt, bm, cm, first)))
    return y.reshape(-1, h, p)[:s]


def mamba(x, p, norm, seg, cfg):
    """``(the Mamba-2 sublayer of the block's input x [S, hidden], output
    projection included; the squared norm a position of the scan's output y_t
    = h_t C_t + D x_t, before the gated norm)``. One GROUP at a time: the
    heads of a group read that group's ``B`` and ``C`` and no other's, the
    gated norm is over a group's own 512 channels, and the groups' parts of
    the output projection add up; so nothing wider than a group's columns of
    ``W_in`` is alive at once."""
    eps = cfg["layer_norm_epsilon"]
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, r = heads * hp, heads // g
    wide = inner // g                      # a group's channels of z and x
    s = x.shape[0]
    first = starts(seg)
    cut = jax.lax.dynamic_slice_in_dim

    def part(j):
        # the group's columns of W_in = [z | x | B | C | dt], and its rows
        # of the convolution (over [x | B | C]) and of W_out
        spans = ((j * wide, wide), (inner + j * wide, wide),
                 (2 * inner + j * n, n), (2 * inner + g * n + j * n, n),
                 (2 * inner + 2 * g * n + j * r, r))
        w_in = jnp.concatenate([cut(p["in_proj"]["kernel"], at, size, 1)
                                for at, size in spans], axis=1)
        conv_rows = lambda t: jnp.concatenate(
            [cut(t, at - inner, size, 0) for at, size in spans[1:4]], axis=0)
        zxbcdt = by_rows(lambda rows: _rms(rows, norm, eps) @ w_in, x)
        z, xbc, dt = (zxbcdt[:, :wide], zxbcdt[:, wide:2 * wide + 2 * n],
                      zxbcdt[:, 2 * wide + 2 * n:])
        xbc = short_conv(xbc, conv_rows(p["conv_taps"]),
                         conv_rows(p["conv_bias"]), seg)
        xs = xbc[:, :wide].reshape(s, r, hp)
        of_head = lambda t: cut(t, j * r, r, 0)
        y = recurrence(
            xs, jax.nn.softplus(dt + of_head(p["dt_bias"])),
            -jnp.exp(of_head(p["A_log"])), xbc[:, None, wide:wide + n],
            xbc[:, None, wide + n:], first)
        y = (y + of_head(p["D"])[:, None] * xs).reshape(s, wide)
        gated = y * jax.nn.silu(z)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, -1, keepdims=True) + eps)
        return ((gated * cut(p["norm"], j * wide, wide, 0))
                @ cut(p["out_proj"]["kernel"], j * wide, wide, 0),
                jnp.sum(jnp.square(jax.lax.stop_gradient(y)), axis=1))

    return summed_over(g, part)


def attention(x, p, norm, seg, cfg):
    """Causal grouped-query attention of the block's input inside a
    document, no positional embedding, output projection included: one query
    head at a time, the heads' parts of the output projection adding up."""
    eps = cfg["layer_norm_epsilon"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s = x.shape[0]
    n = min(QUERIES, s)
    pad = -s % n
    where = jnp.arange(s)
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg["head_dim"]))

    def part(g):
        head = lambda w, i, axis: jax.lax.dynamic_index_in_dim(
            w, i, axis, keepdims=False)
        kv = g // (heads // kv_heads)
        qkv = jnp.concatenate([head(p["wq"]["kernel"], g, 1),
                               head(p["wk"]["kernel"], kv, 1),
                               head(p["wv"]["kernel"], kv, 1)], axis=1)
        q, k, v = jnp.split(
            by_rows(lambda rows: _rms(rows, norm, eps) @ qkv, x), 3, axis=1)
        q = jnp.pad(q, ((0, pad), (0, 0)))
        q_seg = jnp.pad(seg, (0, pad))

        @jax.checkpoint
        def block(t0):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, n)
            ok = (where[None, :] <= (t0 + jnp.arange(n))[:, None]) & (
                seg[None, :] == cut(q_seg)[:, None])
            return jax.nn.softmax(
                jnp.where(ok, cut(q) @ k.T * scale, NEG), axis=-1) @ v

        o = jax.lax.map(block, jnp.arange(0, s + pad, n)).reshape(
            -1, v.shape[-1])[:s]
        return o @ head(p["wo"]["kernel"], g, 0)

    return summed_over(heads, part)


def relu2(h, w_up, w_down):
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


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
        j, w_up, w_down = expert
        g = jnp.sum(gate * (idx == first + j), axis=-1)
        return y + g[:, None] * relu2(h, w_up, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        jnp.arange(count), moe["w_up"], moe["w_down"]))
    return y


def experts(x, moe, norm, bias, cfg):
    """``(the expert sublayer of the block's input, the routed part's
    squared norm a position)``."""
    def rows_of(rows):
        h = _rms(rows, norm, cfg["layer_norm_epsilon"])
        y = routed(h, moe, bias, cfg)
        return (y + relu2(h, moe["shared_up"]["kernel"],
                          moe["shared_down"]["kernel"]),
                jnp.sum(jnp.square(jax.lax.stop_gradient(y)), -1))

    return by_rows(rows_of, x)


def layers(params, mutable, cfg):
    """``[(kind, the block's parameters, its router's bias or None)]`` in
    layer order, from the program's tree: it keeps the whole periods of the
    pattern (the shortest stretch the pattern repeats with) stacked on a
    leading axis under ``periods/layer_<place in the period>``, what is left
    under ``trail_<i>``; the bias in the collection ``router_bias`` alike."""
    pattern = cfg["hybrid_override_pattern"]
    n = len(pattern)
    period = next(p for p in range(1, n + 1)
                  if all(pattern[i] == pattern[i - p] for i in range(p, n)))
    whole = n // period

    def at(tree, i):
        if i >= whole * period:
            return tree.get(f"trail_{i - whole * period}")
        node = tree.get("periods", {}).get(f"layer_{i % period}")
        return None if node is None else jax.tree.map(
            lambda a: a[i // period], node)

    biases = (mutable or {}).get("router_bias", {})
    out = []
    for i, kind in enumerate(pattern):
        bias = None
        if kind == "E":
            node = at(biases, i)
            bias = (jnp.zeros((cfg["router_width"],), jnp.float32)
                    if node is None else node["moe"]["bias"])
        out.append((kind, at(params, i), bias))
    return out


def head_nll(x, norm, head, labels, eps):
    """Summed cross-entropy of rows ``x [N, hidden]`` under ``norm`` and the
    head against ``labels [N - 1]``, the targets of all rows but the last
    (which has none and adds nothing), a block of rows at a time."""
    def block(rows, targets, counts):
        logp = jax.nn.log_softmax(_rms(rows, norm, eps) @ head, axis=-1)
        return -counts * jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    counts = jnp.arange(x.shape[0]) < labels.shape[0]
    return jnp.sum(by_rows(block, x, jnp.pad(labels, (0, 1)),
                           counts.astype(jnp.float32), rows=1024))


def forward(p, mutable, batch, cfg):
    """The model's training loss on float32 parameters ``p``, and what the
    forward pass that gives it saw on the way (the configuration's
    ``step_parts``): ``ssm_energy``, the sum over the Mamba blocks of
    ``mean_t |SUB_t|^2`` of the sublayer's output on the block's own input;
    ``scan_energy``, the same of the scan's output ``y_t = h_t C_t + D x_t``
    before the gated norm (which gives every position the same size,
    whatever the scan gave it); ``boundary_energy``, ``scan_energy`` with
    the mean over the first ``REACH`` positions of every document but the
    window's first; ``attention_energy``
    of the attention blocks; ``experts_energy``, the sum over the expert
    blocks of ``mean_t |y_t|^2`` of the ROUTED experts' part alone."""
    ids = batch["input_ids"]
    segs = batch.get("segment_ids")
    segs = jnp.zeros_like(ids) if segs is None else segs
    eps = cfg["layer_norm_epsilon"]
    blocks = layers(p, mutable, cfg)
    square = lambda y: jnp.sum(jnp.square(jax.lax.stop_gradient(y)), -1)
    total = 0.0
    seen = dict.fromkeys(("ssm_energy", "scan_energy", "boundary_energy",
                          "attention_energy", "experts_energy"), 0.0)
    names = ("ssm_energy", "scan_energy", "boundary_energy",
             "attention_energy", "experts_energy")

    def block(x, seg, reached, kind, lp, bias):
        """One block: ``(x + SUB(RMSNorm(x)), what it adds to ``seen``)``."""
        norm, e = lp["norm"]["scale"], dict.fromkeys(names, 0.0)
        if kind == "M":
            y, scanned = mamba(x, lp["mixer"], norm, seg, cfg)
            e["ssm_energy"] = jnp.mean(square(y))
            e["scan_energy"] = jnp.mean(scanned)
            e["boundary_energy"] = jnp.sum(
                jnp.where(reached, scanned, 0.0)) / jnp.maximum(
                jnp.sum(reached), 1)
        elif kind == "*":
            y = attention(x, lp["self_attn"], norm, seg, cfg)
            e["attention_energy"] = jnp.mean(square(y))
        elif kind == "E":
            y, energy = experts(x, lp["moe"], norm, bias, cfg)
            e["experts_energy"] = jnp.mean(energy)
        else:
            raise ValueError(f"layer kind {kind!r}: this reference knows "
                             f"M, * and E")
        return x + y, e

    def stretch(kinds):
        """A few blocks in a row, each under ``jax.checkpoint`` and the
        stretch under one more: what is kept for the backward pass is the
        residual stream at the stretches' starts, and inside the ONE stretch
        being taken backward at its blocks' (two levels where one would keep
        it at every block of the window)."""
        def run(x, seg, reached, layer_params):
            e = dict.fromkeys(names, 0.0)
            for kind, (lp, bias) in zip(kinds, layer_params):
                x, more = jax.checkpoint(
                    lambda x, lp, bias, kind=kind: block(
                        x, seg, reached, kind, lp, bias))(x, lp, bias)
                e = {k: e[k] + more[k] for k in names}
            return x, e
        return jax.checkpoint(run)

    size = max(1, round(len(blocks) ** 0.5))
    for row_ids, seg in zip(ids, segs):   # a window at a time
        x = p["token_embed"]["embedding"][row_ids]
        reached = after_boundary(seg)
        for at in range(0, len(blocks), size):
            some = blocks[at:at + size]
            x, e = stretch(tuple(k for k, _, _ in some))(
                x, seg, reached, [(lp, bias) for _, lp, bias in some])
            seen = {k: seen[k] + e[k] for k in names}
        total = total + head_nll(x, p["final_norm"]["scale"], p["lm_head"],
                                 row_ids[1:], eps)
    rows = ids.shape[0]
    return total / (rows * (ids.shape[1] - 1)), {
        k: v / rows for k, v in seen.items()}


def training_loss(p, mutable, batch, cfg):
    """The model's loss on float32 parameters ``p``."""
    return forward(p, mutable, batch, cfg)[0]


def expert_probe(p, mutable, ids, cfg):
    """``sum over the expert blocks of mean_t |y_t|^2`` of the ROUTED
    experts' part on the RMS-normed embedding rows of ``ids`` (the probe of
    the configuration's ``program_loss``: what it is for is said there)."""
    x = p["token_embed"]["embedding"][ids]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    x = x.reshape(-1, x.shape[-1])
    total = 0.0
    for kind, lp, bias in layers(p, mutable, cfg):
        if kind == "E":
            y = by_rows(lambda rows, lp=lp, bias=bias: routed(
                rows, lp["moe"], bias, cfg), x, rows=4096)
            total = total + jnp.mean(jnp.sum(jnp.square(y), -1))
    return total


def parts(params, mutable, batch, cfg):
    """The terms of :func:`loss`, by the names the configuration's ``parts``
    gives the program's: ``loss``, the energies of :func:`forward` and the
    float32 probe; but for ``loss`` none carries a gradient."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        # (the probe first: after the forward pass its temporaries would lie
        # on top of what that keeps for the backward pass)
        probe = jax.lax.stop_gradient(
            expert_probe(p, mutable, batch["input_ids"], cfg))
        value, seen = forward(p, mutable, batch, cfg)
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
