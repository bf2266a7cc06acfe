"""Plain reference for ``bert_base_mlm``: BERT's forward pass and MLM loss in
straightforward float32 ``jax.numpy``, written from Devlin et al.
(arXiv:1810.04805, section 3 and appendix A) and Vaswani et al.
(arXiv:1706.03762, section 3.2). No kernel, no flax module of the program;
gradients are ``jax.grad`` of this. It reads the program's parameter tree by
its names (the same parameters, copied over) and nothing else of it.

Departures from the paper, each because the configuration as run has them:

* GELU in its tanh form (google-research/bert ``modeling.gelu``), which is
  also what the program's ``nn.gelu`` computes;
* layer-norm epsilon from the configuration file (1e-6, the program's; the
  published checkpoint used 1e-12 - below float32 resolution here);
* no dropout: the comparison runs both sides in evaluation mode;
* the MLM head runs on the ``mlm_positions`` of the batch only (the original
  TPU BERT's ``masked_lm_positions``), and the loss is the weighted mean of
  the cross-entropy at those positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, key_mask, p, heads):
    b, s, h = x.shape
    d = h // heads
    q = jnp.einsum("bsh,hnd->bnsd", x, p["query"]["kernel"]) \
        + p["query"]["bias"][None, :, None, :]
    k = jnp.einsum("bsh,hnd->bnsd", x, p["key"]["kernel"]) \
        + p["key"]["bias"][None, :, None, :]
    v = jnp.einsum("bsh,hnd->bnsd", x, p["value"]["kernel"]) \
        + p["value"]["bias"][None, :, None, :]
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / jnp.sqrt(float(d))
    scores = jnp.where(key_mask[:, None, None, :] > 0, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bnkd->bqnd", probs, v)
    return jnp.einsum("bqnd,ndh->bqh", ctx, p["out"]["kernel"]) + p["out"]["bias"]


def logits(params, batch, cfg):
    """MLM logits [B, P, vocab] at ``batch['mlm_positions']``."""
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    enc = params["encoder"]
    ids = batch["input_ids"]
    table = params["token_embeddings"]["embedding"]
    types = batch.get("token_type_ids", jnp.zeros_like(ids))
    x = (table[ids]
         + enc["position_embeddings"]["embedding"][None, :ids.shape[1]]
         + enc["type_embeddings"]["embedding"][types])
    x = _layer_norm(x, enc["embeddings_ln"], eps)
    key_mask = batch.get("attention_mask", jnp.ones_like(ids))
    for i in range(cfg["num_hidden_layers"]):
        p = enc[f"layer_{i}"]
        x = _layer_norm(x + _attention(x, key_mask, p["attention"], heads),
                        p["attention_ln"], eps)
        y = _gelu(x @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"])
        y = y @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]
        x = _layer_norm(x + y, p["mlp_ln"], eps)
    pos = batch["mlm_positions"].astype(jnp.int32)
    x = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    x = _gelu(x @ params["mlm_dense"]["kernel"] + params["mlm_dense"]["bias"])
    x = _layer_norm(x, params["mlm_ln"], eps)
    return x @ table.T + params["mlm_bias"]


def loss(params, mutable, batch, cfg):
    del mutable  # BERT has no mutable state
    with jax.default_matmul_precision("highest"):
        lg = logits(jax.tree.map(lambda a: a.astype(jnp.float32), params),
                    batch, cfg)
        logp = jax.nn.log_softmax(lg, axis=-1)
        labels = batch["mlm_labels"].astype(jnp.int32)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        w = batch["mlm_weights"].astype(jnp.float32)
        return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
