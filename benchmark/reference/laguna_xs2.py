"""Plain reference for ``laguna_xs2``: the forward pass and the training loss
of poolside's Laguna-XS.2 decoder (``model_type`` ``laguna``) in
straightforward float32 ``jax.numpy``, written from the model's
``config.json``, YaRN's paper (Peng et al., arXiv:2309.00071, as
``transformers`` computes ``rope_type`` ``yarn``) and, where those are
silent, from the items under ``assumed`` in the configuration's file. No
kernel and no module of the program: attention is a softmax under a mask
built from positions and segment ids, one query head and one block of
queries at a time, ``jax.lax.top_k`` the selection, a loop walks the experts
held, and gradients are ``jax.grad`` of this. It reads the program's
parameter tree by its names and nothing else of it.

A block, ``x [S, 2048]``, RMSNorm eps 1e-6, ``seg[t]`` the document of
position ``t``, ``pos[t] = t -`` the first position of that document::

    h = RMSNorm(x);  x' = x + ATTN(h);  x'' = x' + FFN(RMSNorm(x'))
    ATTN: q_g = h Wq_g (128), k, v = h Wk_c, h Wv_c for key-value head c =
          g // (heads / 8); q, k rotated by pos; p[t, .] = softmax over the
          ALLOWED s of q[t].k[s] / sqrt(128); o_g = (p v) sigmoid(h Wgate)_g;
          ATTN = sum_g o_g Wo_g
      full layer (48 heads): allowed s <= t, seg[s] = seg[t]; rotary on the
          FIRST 64 of 128 dimensions (rotate-half within them, the other 64
          untouched) at YaRN's frequencies, cos and sin times 1.4158883
      sliding layer (64 heads): allowed 0 <= t - s < 512, seg[s] = seg[t];
          rotary over all 128 dimensions at theta 1e4
    FFN dense (layer 0): W2(silu(W1 x) * W3 x), 8192 wide
    FFN experts: P = softmax(x Wr) over all 256, in float32; E_t = top 8 of
          P; g_e = 2.5 P_e / sum_{E_t} P;  FFN = sum over e in E_t HELD here
          of g_e W2_e(silu(W1_e x) * W3_e x) + the shared SwiGLU (512 wide,
          no gate of its own)
    L = mean next-token cross-entropy of RMSNorm_final(x) W_head over every
        position but the window's last (a target across a boundary is
        kept) + 0.001 x the sum over the expert layers of Switch's term
        256 sum_e f_e mean_t P_e (f_e the share of the 8 T assignments)

YaRN, d = 64, base 5e5, factor 64, original 4096, beta_fast 64, beta_slow 1:
``f_i = base^(-2i/d)``; ``lo = floor(d ln(4096 / (64 2 pi)) / (2 ln base))``,
``hi = ceil(d ln(4096 / (1 2 pi)) / (2 ln base))``, clamped to [0, d - 1];
``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``; ``inv_freq_i = f_i / 64
ramp_i + f_i (1 - ramp_i)``.

Departures from the published description, each an item of ``assumed``: the
loop is over the experts HELD (the deployment's share, without its
exchange); documents are isolated in both kinds of layer and positions
restart with each; the router, the gate's shape and the shared expert's lack
of a gate are the readings ``assumed`` gives. A sliding layer's block of
queries is compared with the keys from ``window`` positions before its first
query to its last (every key the mask can allow, and more); the mask alone
decides.

Sized to run beside the trainer's state at one 16,384-window: attention one
query head at a time, 512 queries a block under ``jax.checkpoint``, the
heads' parts of the output projection adding up; what is per position a
block of rows at a time; every layer under ``jax.checkpoint``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 2048          # rows of a per-position block
QUERIES = 512        # queries of an attention block (of one head)
NEG = -1e30          # (finite: a padded query with no key stays finite)
#: the window probe's spike (the configuration's ``SPIKE``): a spike's
#: score is SPIKE / sqrt(head_dim), every other key's 0
SPIKE = 128.0


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


def summed_over(count, part):
    """``sum_g part(g)`` for ``g < count``, one after the other, each under
    ``jax.checkpoint``."""
    part = jax.checkpoint(part)
    first = part(jnp.int32(0))
    if count == 1:
        return first
    total, _ = jax.lax.scan(
        lambda acc, g: (jax.tree.map(jnp.add, acc, part(g)), None), first,
        jnp.arange(1, count, dtype=jnp.int32))
    return total


def positions(seg):
    """``[S]`` segment ids -> each position's index inside its document."""
    idx = jnp.arange(seg.shape[0])
    start = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
    return idx - jax.lax.cummax(jnp.where(start, idx, 0))


def yarn_inv_freq(d, base, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies by the module docstring's formula (float32
    [d / 2])."""
    def c(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    lo = min(max(math.floor(c(beta_fast)), 0), d - 1)
    hi = min(max(math.ceil(c(beta_slow)), 0), d - 1)
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rotary(kind, cfg):
    """``(rotated dimensions, inv_freq [rot / 2], factor on cos and sin)``
    of a layer kind, from ``rope_parameters``."""
    rp = cfg["rope_parameters"][kind]
    d = cfg["head_dim"]
    rot = int(d * rp.get("partial_rotary_factor", 1.0))
    if rp["rope_type"] == "yarn":
        return rot, yarn_inv_freq(
            rot, float(rp["rope_theta"]), float(rp["factor"]),
            rp["original_max_position_embeddings"], float(rp["beta_fast"]),
            float(rp["beta_slow"])), float(rp["attention_factor"])
    return rot, (float(rp["rope_theta"]) ** (
        -np.arange(0, rot, 2, dtype=np.float64) / rot)).astype(
        np.float32), 1.0


def rotate(x, pos, rope):
    """``x [S, D]`` rotated by ``pos [S]``: rotate-half within the leading
    ``rot`` dimensions, cos and sin times the factor, the rest untouched."""
    rot, inv_freq, factor = rope
    angles = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2, rest = x[:, :rot // 2], x[:, rot // 2:rot], x[:, rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=1)


def attention(h, p, seg, pos, kind, heads, cfg):
    """The attention sublayer of the normed input ``h [S, hidden]``, gate
    and output projection included: one query head at a time, the heads'
    parts of the output projection adding up."""
    s, d = h.shape[0], cfg["head_dim"]
    group = heads // cfg["num_key_value_heads"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    rope = rotary(kind, cfg)
    n = min(QUERIES, s)
    pad = -s % n
    scale = 1.0 / math.sqrt(d)
    # a sliding layer's block of queries meets the keys from ``reach``
    # positions before its first query on (zeros in front of the window's)
    reach = 0 if window is None else window
    keys = s if window is None else n + reach

    def part(g):
        head = lambda w, i, axis: jax.lax.dynamic_index_in_dim(
            w, i, axis, keepdims=False)
        c = g // group
        w = jnp.concatenate([head(p["wq"]["kernel"], g, 1),
                             head(p["wk"]["kernel"], c, 1),
                             head(p["wv"]["kernel"], c, 1)], axis=1)
        q, k, v = jnp.split(by_rows(lambda rows: rows @ w, h), 3, axis=1)
        q, k = rotate(q, pos, rope), rotate(k, pos, rope)
        front = lambda a, fill=0: jnp.pad(
            a, ((reach, pad),) + ((0, 0),) * (a.ndim - 1),
            constant_values=fill)
        qp, q_seg = jnp.pad(q, ((0, pad), (0, 0))), jnp.pad(seg, (0, pad))
        kp, vp, k_seg = front(k), front(v), front(seg, -1)

        @jax.checkpoint
        def block(t0):
            qs = jax.lax.dynamic_slice_in_dim(qp, t0, n)
            at = t0 if window is not None else 0
            ks = jax.lax.dynamic_slice_in_dim(kp, at, keys)
            vs = jax.lax.dynamic_slice_in_dim(vp, at, keys)
            ids = jax.lax.dynamic_slice_in_dim(k_seg, at, keys)
            i = t0 + jnp.arange(n)[:, None]
            j = at - reach + jnp.arange(keys)[None, :]
            ok = (j <= i) & (ids[None, :] == jax.lax.dynamic_slice_in_dim(
                q_seg, t0, n)[:, None])
            if window is not None:
                ok = ok & (i - j < window)
            return jax.nn.softmax(
                jnp.where(ok, qs @ ks.T * scale, NEG), axis=-1) @ vs

        o = jax.lax.map(block, jnp.arange(0, s + pad, n)).reshape(-1, d)[:s]
        o = o * jax.nn.sigmoid(h @ head(p["gate"]["kernel"], g, 1))[:, None]
        return o @ head(p["wo"]["kernel"], g, 0)

    return summed_over(heads, part)


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def routed(h, moe, cfg, held=None):
    """Normed tokens ``[T, hidden]`` -> ``(the held ROUTED experts' part of
    the layer, without the shared expert; the router's probabilities [T,
    256]; the chosen experts [T, 8])``. ``held = (first, count)`` defaults
    to the configuration's; the kernels ``w_*`` hold exactly those experts,
    the router all of them."""
    first, count = held or cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ moe["router"], axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    if cfg["assumed_values"]["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * cfg["moe_routed_scaling_factor"]
    if not cfg.get("train_router", True):
        gate = jax.lax.stop_gradient(gate)

    @jax.checkpoint
    def add_expert(y, expert):   # the loop over the experts held
        j, w_gate, w_up, w_down = expert
        g = jnp.sum(gate * (idx == first + j), axis=-1)
        return y + g[:, None] * swiglu(h, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        jnp.arange(count), moe["w_gate"], moe["w_up"], moe["w_down"]))
    return y, probs, idx


def experts(x, moe, norm, cfg):
    """``(the expert sublayer of the block's input, the routed part's
    squared norm a position, the router's probabilities, the chosen
    experts)``."""
    def rows_of(rows):
        h = _rms(rows, norm, cfg["rms_norm_eps"])
        y, probs, idx = routed(h, moe, cfg)
        shared = swiglu(h, moe["shared_gate"]["kernel"],
                        moe["shared_up"]["kernel"],
                        moe["shared_down"]["kernel"])
        return (y + shared, jnp.sum(jnp.square(jax.lax.stop_gradient(y)), -1),
                probs, idx)

    return by_rows(rows_of, x)


def switch_term(probs, idx, width):
    """Switch's balance term of one expert layer over all its tokens:
    ``width sum_e f_e mean_t P_e``."""
    assignments = idx.size
    f = jnp.zeros((width,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (
        assignments)
    return width * jnp.sum(f * jnp.mean(probs, axis=0))


def layers(params, cfg):
    """``[(attention kind, heads, dense?, the layer's parameters)]`` in
    layer order, from the program's tree: the leading dense layers under
    ``lead_<i>``, the whole periods of what follows (the shortest stretch
    whose layer kinds repeat) stacked on a leading axis under
    ``periods/layer_<place>``, what is left under ``trail_<i>``."""
    kinds, mlp = cfg["layer_types"], cfg["mlp_layer_types"]
    heads = cfg["num_attention_heads_per_layer"]
    lead = mlp.index("sparse") if "sparse" in mlp else len(mlp)
    rest = kinds[lead:]
    period = next((p for p in range(1, len(rest) + 1)
                   if all(rest[i] == rest[i - p]
                          for i in range(p, len(rest)))), 1)
    whole = len(rest) // period
    out = []
    for i, kind in enumerate(kinds):
        r = i - lead
        if r < 0:
            lp = params[f"lead_{i}"]
        elif r < whole * period:
            lp = jax.tree.map(lambda a, r=r: a[r // period],
                              params["periods"][f"layer_{r % period}"])
        else:
            lp = params[f"trail_{r - whole * period}"]
        out.append((kind, heads[i], mlp[i] == "dense", lp))
    return out


def head_nll(x, norm, head, labels, eps):
    """Summed cross-entropy of rows ``x [N, hidden]`` under ``norm`` and the
    head against ``labels [N - 1]``, the targets of all rows but the last,
    a block of rows at a time."""
    def block(rows, targets, counts):
        logp = jax.nn.log_softmax(_rms(rows, norm, eps) @ head, axis=-1)
        return -counts * jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    counts = jnp.arange(x.shape[0]) < labels.shape[0]
    return jnp.sum(by_rows(block, x, jnp.pad(labels, (0, 1)),
                           counts.astype(jnp.float32), rows=1024))


NAMES = ("window_attention_energy", "full_attention_energy", "experts_energy")


def forward(p, batch, cfg):
    """The model's training loss on float32 parameters ``p`` (the Switch
    term included), and what the forward pass that gives it saw on the way
    (the configuration's ``step_parts``): ``window_attention_energy``, the
    sum over the sliding blocks of ``mean_t |ATTN_t|^2``;
    ``full_attention_energy``, of the full blocks; ``experts_energy``, the
    sum over the expert blocks of ``mean_t |y_t|^2`` of the ROUTED part."""
    ids = batch["input_ids"]
    segs = batch.get("segment_ids")
    segs = jnp.zeros_like(ids) if segs is None else segs
    eps = cfg["rms_norm_eps"]
    width = cfg["router_width"]
    blocks = layers(p, cfg)
    square = lambda y: jnp.sum(jnp.square(jax.lax.stop_gradient(y)), -1)
    seen = dict.fromkeys(NAMES, 0.0)
    switch = [([], []) for _ in blocks]     # a layer's probs, choices by row
    total = 0.0

    def block(x, seg, pos, kind, heads, dense, lp):
        """One layer: ``(x'', its energies, the router's probabilities and
        choices or None)``."""
        e = dict.fromkeys(NAMES, 0.0)
        h = _rms(x, lp["operator_norm"]["scale"], eps)
        y = attention(h, lp["self_attn"], seg, pos, kind, heads, cfg)
        e["window_attention_energy" if kind == "sliding_attention"
          else "full_attention_energy"] = jnp.mean(square(y))
        x = x + y
        norm = lp["ffn_norm"]["scale"]
        if dense:
            mlp = lp["mlp"]
            y = by_rows(lambda rows: swiglu(
                _rms(rows, norm, eps), mlp["w1"]["kernel"],
                mlp["w3"]["kernel"], mlp["w2"]["kernel"]), x)
            return x + y, e, None
        y, energy, probs, idx = experts(x, lp["moe"], norm, cfg)
        e["experts_energy"] = jnp.mean(energy)
        return x + y, e, (probs, idx)

    for row_ids, seg in zip(ids, segs):   # a window at a time
        x = p["token_embed"]["embedding"][row_ids]
        pos = positions(seg)
        for n, (kind, heads, dense, lp) in enumerate(blocks):
            x, e, routing = jax.checkpoint(
                lambda x, lp, kind=kind, heads=heads, dense=dense: block(
                    x, seg, pos, kind, heads, dense, lp))(x, lp)
            seen = {k: seen[k] + e[k] for k in NAMES}
            if routing is not None:
                switch[n][0].append(routing[0])
                switch[n][1].append(routing[1])
        total = total + head_nll(x, p["final_norm"]["scale"], p["lm_head"],
                                 row_ids[1:], eps)
    rows = ids.shape[0]
    aux = sum(switch_term(jnp.concatenate(pr), jnp.concatenate(ix), width)
              for pr, ix in switch if pr)
    coef = cfg["assumed_values"]["router_aux_loss_coef"]
    return (total / (rows * (ids.shape[1] - 1)) + coef * aux,
            {k: v / rows for k, v in seen.items()})


def training_loss(p, batch, cfg):
    """The model's loss on float32 parameters ``p``."""
    return forward(p, batch, cfg)[0]


def expert_probe(p, ids, cfg):
    """``sum over the expert blocks of mean_t |y_t|^2`` of the ROUTED
    experts' part on the RMS-normed embedding rows of ``ids`` (the probe of
    the configuration's ``program_loss``: what it is for is said there)."""
    x = p["token_embed"]["embedding"][ids]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    x = x.reshape(-1, x.shape[-1])
    total = 0.0
    for _, _, dense, lp in layers(p, cfg):
        if not dense:
            y = by_rows(lambda rows, lp=lp: routed(rows, lp["moe"], cfg)[0],
                        x, rows=4096)
            total = total + jnp.mean(jnp.sum(jnp.square(y), -1))
    return total


def window_edge_probe(batch, cfg):
    """The configuration's ``window_edge_probe`` from its definition: keys
    at multiples of the window are spikes (score ``SPIKE / sqrt(head_dim)``
    against 0, value the unit vector 1 or 2 by the multiple's parity), every
    other key's value is zero; ``mean |o_t|^2`` over the queries ``t`` at 0
    and at ``window - 1`` past a multiple, of the first row, ``o_t`` the
    softmax over the keys ``0 <= t - s < window`` of ``t``'s document."""
    w, d = cfg["sliding_window"], cfg["head_dim"]
    seg = batch.get("segment_ids")
    s = batch["input_ids"].shape[1]
    seg = jnp.zeros((s,), jnp.int32) if seg is None else seg[0]
    t = np.arange(s)
    edge = t[(t % w == 0) | (t % w == w - 1)]
    j = jnp.asarray(edge[:, None] - np.arange(w)[None, :])   # the band's keys
    ok = (j >= 0) & (seg[jnp.maximum(j, 0)] == seg[edge][:, None])
    spike = (j % w == 0) & ok
    p = jax.nn.softmax(jnp.where(ok, jnp.where(spike, SPIKE / math.sqrt(d),
                                               0.0), NEG), axis=-1)
    first = jnp.sum(jnp.where(spike & ((j // w) % 2 == 0), p, 0.0), -1)
    second = jnp.sum(jnp.where(spike & ((j // w) % 2 == 1), p, 0.0), -1)
    return jnp.mean(first ** 2 + second ** 2)


def rotary_probe(batch, cfg):
    """The configuration's ``rotary_probe`` from its definition: ``sum over
    the two kinds of mean_t |rot(1, pos_t) - 1|^2 / head_dim``."""
    ids = batch["input_ids"]
    segs = batch.get("segment_ids")
    segs = jnp.zeros_like(ids) if segs is None else segs
    d = cfg["head_dim"]
    total = 0.0
    for kind in ("full_attention", "sliding_attention"):
        rope = rotary(kind, cfg)
        r = jnp.stack([rotate(jnp.ones((ids.shape[1], d), jnp.float32),
                              positions(seg), rope) for seg in segs])
        total = total + jnp.mean(jnp.sum(jnp.square(r - 1.0), -1)) / d
    return total


def parts(params, mutable, batch, cfg):
    """The terms of :func:`loss`, by the names the configuration's ``parts``
    gives the program's: ``loss``, the energies of :func:`forward` and the
    three probes; but for ``loss`` none carries a gradient."""
    del mutable    # (the model holds no collection but its parameters)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        probe = jax.lax.stop_gradient(
            expert_probe(p, batch["input_ids"], cfg))
        value, seen = forward(p, batch, cfg)
        return {"loss": value, **jax.lax.stop_gradient(seen),
                "expert_probe": probe,
                "window_edge_probe": window_edge_probe(batch, cfg),
                "rotary_probe": rotary_probe(batch, cfg)}


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
