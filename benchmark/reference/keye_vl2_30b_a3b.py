"""Plain reference for ``keye_vl2_30b_a3b``: the language model's forward
pass and its three-term training loss in straightforward float32
``jax.numpy``, written from the model's ``config.json`` (widths, head
counts, ``sa_config``), the Qwen3-MoE family's conventions where the config
is silent (per-head q/k RMSNorm, softmax-then-top-k routing with
renormalised gates, Switch's balance loss) and the DeepSeek-V3.2-Exp
report, section 2, for the indexer and its loss (the catalog's
``described_as`` points there; every such item is listed under ``assumed``
in the configuration's file). No kernel and no module of the program:
``jax.lax.top_k`` makes the selection, a loop walks the experts, and
gradients are ``jax.grad`` of this. It reads the program's parameter tree by
its names and nothing else of it.

One block, ``x`` ``[S, hidden]``, ``h = RMSNorm(x)``::

    q, k, v = h Wq, h Wk, h Wv; q, k = rotary(RMSNorm_head(q, k))
    u = stop_gradient(h); qI = rotary(u WqI); kI = rotary(LayerNorm(u WkI))
    w = u Ww * heads_I^-0.5 * dim_I^-0.5
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
    S_t = lax.top_k(I[t, :t+1], min(t + 1, topk))   (ties to the lower s)
    p_h[t, .] = softmax over S_t of q_h[t] . k[s] / sqrt(128); o = p v
    target = sum_h p_h / its sum, detached
    L_I = mean_t KL(target[t] || softmax over S_t of I[t])
    x = x + o Wo
    g = softmax(RMSNorm(x) Wr) over all experts; top 8, renormalised
    x = x + sum over the token's experts HELD here of g_e * SwiGLU_e

Sized to run beside the trainer's state (configuration file, ``check``):
queries go through attention 128 at a time, one block after the other
(``lax.map``) under ``jax.checkpoint``, so the largest live arrays are
``[32, 128, S]``; the layers are a ``lax.scan`` over their stacked
parameters; the head's logits are made 128 positions at a time, and the experts are a
``lax.scan`` over the kernels held.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 128
NEG = float("-inf")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rotary(x, theta):
    """[S, heads, d], rotate-half convention, positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def selection(index_q, index_k, index_w, t0, topk):
    """Scores and selection of queries ``t0 .. t0 + n``: ``(I [n, S],
    selected [n, S] bool)``."""
    n, s = index_q.shape[0], index_k.shape[0]
    dots = jnp.einsum("tjd,sd->tjs", index_q, index_k)
    scores = jnp.einsum("tjs,tj->ts", jnp.maximum(dots, 0.0), index_w)
    t = t0 + jnp.arange(n)
    causal = jnp.arange(s)[None, :] <= t[:, None]
    # a zero is +0.0: top_k's total order puts -0.0 below it, and relu's
    # zeros times weights of either sign leave both
    scores = jnp.where(causal, jnp.where(scores == 0.0, 0.0, scores), NEG)
    vals, idx = jax.lax.top_k(scores, min(topk, s))
    # a query with fewer than topk keys behind it takes them all: what
    # top_k returned past them are -inf entries from above the diagonal
    hit = jnp.zeros((n, s), jnp.bool_).at[
        jnp.arange(n)[:, None], idx].max(vals > NEG)
    return scores, hit


def _attend_block(q, k, v, index_q, index_k, index_w, t0, *, topk, group):
    """Queries ``t0 .. t0 + n`` of one sequence -> (o [n, H, D], kl [n])."""
    scores, sel = selection(index_q, index_k, index_w, t0, topk)
    n, heads, d = q.shape
    # query head i reads key-value head i // group
    qg = q.reshape(n, heads // group, group, d)
    logits = jnp.einsum("tngd,snd->ngts", qg, k) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(jnp.where(sel, logits, NEG), axis=-1)
    o = jnp.einsum("ngts,snd->tngd", probs, v).reshape(n, heads, d)
    probs = probs.reshape(heads, n, -1)
    target = jax.lax.stop_gradient(probs).sum(0)
    target = target / target.sum(-1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(sel, scores, NEG), axis=-1)
    on = target > 0.0
    kl = jnp.sum(jnp.where(on, target * (jnp.log(jnp.where(on, target, 1.0))
                                         - jnp.where(on, logq, 0.0)), 0.0), -1)
    return o, kl


def attention(x, p, cfg):
    """One sequence ``[S, hidden]`` -> (attention output [S, hidden], L_I)."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sa = cfg["sa_config"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    h = _rms(x, p["attention_norm"]["scale"], eps)
    q = jnp.einsum("sh,hnd->snd", h, p["wq"]["kernel"])
    k = jnp.einsum("sh,hnd->snd", h, p["wk"]["kernel"])
    v = jnp.einsum("sh,hnd->snd", h, p["wv"]["kernel"])
    q = _rotary(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rotary(_rms(k, p["k_norm"]["scale"], eps), theta)
    u = jax.lax.stop_gradient(h)
    qi = _rotary(jnp.einsum("sh,hnd->snd", u, p["index_wq"]["kernel"]), theta)
    ki = _layer_norm(u @ p["index_wk"]["kernel"], p["index_k_norm"], eps)
    ki = _rotary(ki[:, None, :], theta)[:, 0, :]
    wi = (u @ p["index_w"]["kernel"]) * (
        sa["indexer_num_heads"] ** -0.5 * sa["indexer_head_dim"] ** -0.5)

    s = x.shape[0]
    n = min(BLOCK, s)

    @jax.checkpoint
    def block(t0):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, n)
        return _attend_block(cut(q), k, v, cut(qi), ki, cut(wi), t0,
                             topk=sa["topk"], group=group)

    # a block of queries at a time, one after the other (lax.map): blocks
    # written out as a Python loop are independent, and XLA is then free to
    # hold several blocks' [heads, n, S] arrays at once
    o, kl = jax.lax.map(block, jnp.arange(0, s, n))
    o = o.reshape(s, *o.shape[2:])
    return jnp.einsum("snd,ndh->sh", o, p["wo"]["kernel"]), jnp.mean(kl)


def routed(h, moe, cfg, held=None):
    """Normed tokens ``[T, hidden]`` -> (the held experts' part of the
    layer, balance loss). ``held = (first, count)`` defaults to the
    configuration's; the kernels ``w_*`` hold exactly those experts, the
    router all of them."""
    first, count = held or cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ moe["router"], axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)

    @jax.checkpoint
    def one_expert(y, expert):   # the loop over the experts held
        j, w_gate, w_up, w_down = expert
        g = jnp.sum(gate * (idx == first + j), axis=-1)
        hid = jax.nn.silu(h @ w_gate) * (h @ w_up)
        return y + g[:, None] * (hid @ w_down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(count), moe["w_gate"], moe["w_up"], moe["w_down"]))
    e = probs.shape[-1]
    frac = jnp.sum(jax.nn.one_hot(idx.reshape(-1), e), axis=0) / idx.size
    return y, e * jnp.sum(frac * probs.mean(0))


def experts(x, p, cfg, held=None):
    """``[T, hidden]`` -> (the held experts' part of the layer, balance
    loss): :func:`routed` on the block's normed input."""
    return routed(_rms(x, p["mlp_norm"]["scale"], cfg["rms_norm_eps"]),
                  p["moe"], cfg, held)


def layer(x, p, cfg):
    """``[B, S, hidden]`` -> (x, L_I of this layer, balance loss)."""
    outs, kls = [], []
    for row in x:  # a sequence at a time: attention never mixes them
        o, kl = attention(row, p, cfg)
        outs.append(o)
        kls.append(kl)
    x = x + jnp.stack(outs)
    b, s, hdim = x.shape
    y, aux = experts(x.reshape(b * s, hdim), p, cfg)
    return x + y.reshape(b, s, hdim), jnp.mean(jnp.stack(kls)), aux


def next_token_nll(x, kernel, ids):
    """Mean next-token cross-entropy of ``x`` [B, S, hidden] under the head,
    a block of positions at a time (the [S, vocabulary] logits of a whole
    window are 0.6 GB, and their cotangent as much again)."""
    b, s, h = x.shape
    rows = x[:, :-1].reshape(-1, h)
    labels = ids[:, 1:].reshape(-1)
    n = min(BLOCK, rows.shape[0])
    pad = -rows.shape[0] % n
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    weight = jnp.pad(jnp.ones_like(labels, jnp.float32), (0, pad))
    labels = jnp.pad(labels, (0, pad))

    @jax.checkpoint
    def block(args):
        r, l, w = args
        logp = jax.nn.log_softmax(r @ kernel, axis=-1)
        return -jnp.sum(w * jnp.take_along_axis(logp, l[:, None], -1)[:, 0])

    cut = lambda a: a.reshape(-1, n, *a.shape[1:])
    return jnp.sum(jax.lax.map(block, (cut(rows), cut(labels), cut(weight)))
                   ) / (b * (s - 1))


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


def expert_probe(p, ids, cfg):
    """``sum over layers of mean_t |y_t|^2`` of the experts' part on the
    RMS-normed embedding rows of ``ids`` (the probe of the configuration's
    ``program_loss``: what it is for is said there)."""
    x = p["token_embed"]["embedding"][ids]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    x = x.reshape(-1, x.shape[-1])

    def energy(moe):
        return jnp.mean(jnp.sum(jnp.square(routed(x, moe, cfg)[0]), -1))

    return jnp.sum(jax.lax.map(energy, p["layers"]["moe"]))


def training_loss(p, batch, cfg):
    """The model's three-term loss on float32 parameters ``p``."""
    a = cfg["assumed_values"]
    ids = batch["input_ids"]
    x = p["token_embed"]["embedding"][ids]

    @jax.checkpoint
    def one(x, lp):
        x, kl, aux = layer(x, lp, cfg)
        return x, (kl, aux)

    # the layers' parameters are stacked on their first axis
    x, (kl, aux) = jax.lax.scan(one, x, p["layers"])
    x = _rms(x, p["final_norm"]["scale"], cfg["rms_norm_eps"])
    return (next_token_nll(x, p["lm_head"]["kernel"], ids)
            + a["router_aux_loss_coef"] * jnp.sum(aux)
            + a["indexer_loss_weight"] * jnp.sum(kl))


def loss(params, mutable, batch, cfg):
    """What the harness compares with the configuration's ``program_loss``:
    :func:`training_loss` with each leaf's cotangent weighted, plus the
    logarithm of :func:`expert_probe`, which carries no gradient
    (``check.why`` in the configuration's file says what each is for)."""
    del mutable
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        probe = jax.lax.stop_gradient(jnp.log(expert_probe(
            p, batch["input_ids"], cfg)))
        weights = cfg["check"].get("grad_leaf_weights", {})
        return training_loss(weigh(p, weights), batch, cfg) + probe
