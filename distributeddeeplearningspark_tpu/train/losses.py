"""Loss functions for the five contract workloads (BASELINE.json configs).

Each takes (model outputs, batch dict) and returns (scalar loss, metrics dict).
All reductions are plain global means: under GSPMD with the batch sharded over
(data, fsdp), a ``jnp.mean`` over the batch axis *is* the cross-replica
average the reference obtains via NCCL all-reduce of per-GPU means.

Losses whose denominator is NOT the example count (token-weighted LM losses)
include a ``"weight"`` metric — :meth:`~..trainer.Trainer.evaluate` uses it to
aggregate per-batch means exactly across unequal batches (the tail-batch fix,
VERDICT r1 weak-#3); the train loop strips it from logs.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax


def _row_mask(batch: dict[str, Any]) -> jax.Array | None:
    """Per-row eval weights (1 real / 0 padding), present only on padded
    remainder batches (data/feed.py ``_pad_to_shards``). Losses that see it
    MUST exclude mask-0 rows from every mean and report the real count as
    ``"weight"`` — that is what makes sharded eval exact (r3 missing-#5)."""
    m = batch.get("eval_mask")
    return None if m is None else m.astype(jnp.float32)


def softmax_xent(logits: jax.Array, batch: dict[str, Any]) -> tuple[jax.Array, dict]:
    """Classification (LeNet-5/MNIST, ResNet-50/ImageNet): mean CE + accuracy.

    Reports top-5 accuracy too when there are >5 classes — the second
    standard ImageNet number (top-k via one sort, no loop)."""
    labels = batch["label"]
    per_ex = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    w = _row_mask(batch)
    if w is None:
        loss = per_ex.mean()
        acc = (jnp.argmax(logits, -1) == labels).mean()
        metrics = {"loss": loss, "accuracy": acc}
        if logits.shape[-1] > 5:
            top5 = jax.lax.top_k(logits, 5)[1]
            metrics["top5_accuracy"] = (top5 == labels[:, None]).any(-1).mean()
        return loss, metrics
    denom = jnp.maximum(w.sum(), 1.0)
    loss = (per_ex * w).sum() / denom
    acc = ((jnp.argmax(logits, -1) == labels) * w).sum() / denom
    metrics = {"loss": loss, "accuracy": acc, "weight": denom}
    if logits.shape[-1] > 5:
        top5 = jax.lax.top_k(logits, 5)[1]
        metrics["top5_accuracy"] = (
            (top5 == labels[:, None]).any(-1) * w).sum() / denom
    return loss, metrics


def masked_lm(logits: jax.Array, batch: dict[str, Any]) -> tuple[jax.Array, dict]:
    """BERT MLM: CE over masked positions only, weighted mean.

    ``batch['mlm_labels']`` holds target ids, ``batch['mlm_weights']`` is 1.0
    at masked positions / 0.0 elsewhere.
    """
    labels = batch["mlm_labels"]
    weights = batch["mlm_weights"].astype(jnp.float32)
    em = _row_mask(batch)
    if em is not None:  # padded eval rows contribute zero mask weight
        weights = weights * em[:, None]
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = (per_tok * weights).sum() / denom
    acc = ((jnp.argmax(logits, -1) == labels) * weights).sum() / denom
    return loss, {"loss": loss, "mlm_accuracy": acc, "weight": denom}


def binary_xent(logits: jax.Array, batch: dict[str, Any]) -> tuple[jax.Array, dict]:
    """CTR prediction (Wide&Deep/DLRM on Criteo): sigmoid BCE + accuracy."""
    labels = batch["label"].astype(jnp.float32)
    logits = logits.reshape(labels.shape)
    per_ex = optax.sigmoid_binary_cross_entropy(logits, labels)
    hit = ((logits > 0) == (labels > 0.5))
    w = _row_mask(batch)
    if w is None:
        return per_ex.mean(), {"loss": per_ex.mean(), "accuracy": hit.mean()}
    denom = jnp.maximum(w.sum(), 1.0)
    loss = (per_ex * w).sum() / denom
    return loss, {"loss": loss, "accuracy": (hit * w).sum() / denom,
                  "weight": denom}


def _reduce_next_token(per_tok: jax.Array, batch: dict[str, Any]
                       ) -> tuple[jax.Array, dict]:
    """Shared LM reduction: optional shifted loss_mask, weighted mean,
    (loss, perplexity, weight) metrics — one definition for both the
    materialized and the fused head path."""
    mask = batch.get("loss_mask")
    em = _row_mask(batch)
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
    elif em is not None:
        mask = jnp.ones_like(per_tok)
    if em is not None:  # padded eval rows: zero token weight end-to-end
        mask = mask * em[:, None]
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per_tok * mask).sum() / denom
    else:
        denom = jnp.float32(per_tok.size)
        loss = per_tok.mean()
    return loss, {"loss": loss, "perplexity": jnp.exp(loss), "weight": denom}


def causal_lm_fused(outputs: dict[str, jax.Array], batch: dict[str, Any]
                    ) -> tuple[jax.Array, dict]:
    """Next-token CE fused with the LM head (train/fused_ce.py).

    ``outputs`` is the ``{"hidden", "lm_head"}`` dict a model configured
    with ``fused_head_loss=True`` returns — the [B,S,V] logits (and their
    backward cotangent) never materialize. Same metrics contract as
    :func:`causal_lm`.
    """
    from distributeddeeplearningspark_tpu.train.fused_ce import (
        chunked_softmax_xent,
        is_fused_output,
    )

    if not is_fused_output(outputs):
        raise TypeError(
            "causal_lm_fused needs the {'hidden', 'lm_head'} dict a model "
            "with fused_head_loss=True returns; this model produced "
            f"{type(outputs).__name__} — either set the config flag or use "
            "losses.causal_lm")
    hidden = outputs["hidden"][:, :-1]
    labels = batch["input_ids"][:, 1:]
    per_tok = chunked_softmax_xent(hidden, outputs["lm_head"], labels)
    loss, metrics = _reduce_next_token(per_tok, batch)
    return _add_moe_aux(loss, metrics, outputs)


def sparse_moe_lm(outputs: dict[str, jax.Array], batch: dict[str, Any]
                  ) -> tuple[jax.Array, dict]:
    """The three-term loss of :class:`~..models.sparse_decoder.SparseDecoderLM`:
    next-token cross-entropy over the vocabulary the model holds (fused with
    the head, as :func:`causal_lm_fused`), the router's balance loss and the
    indexer's KL, both weighted by the model. ``loss`` is their sum; ``lm_loss``
    the first alone (``perplexity`` is of that). The model's counters
    (``sparse_decoder.COUNTERS``) ride along into the step's metrics. The indexer's input is
    detached inside the model, so the KL trains the indexer and nothing else,
    and the cross-entropy nothing of the indexer."""
    from distributeddeeplearningspark_tpu.models.sparse_decoder import COUNTERS
    from distributeddeeplearningspark_tpu.train.fused_ce import (
        chunked_softmax_xent,
    )

    per_tok = chunked_softmax_xent(outputs["hidden"][:, :-1],
                                   outputs["lm_head"],
                                   batch["input_ids"][:, 1:])
    lm_loss, metrics = _reduce_next_token(per_tok, batch)
    loss = lm_loss + outputs["moe_aux"] + outputs["index_kl"]
    metrics = {**metrics, "loss": loss, "lm_loss": lm_loss,
               "moe_aux": outputs["moe_aux"],
               "dsa_index_kl": outputs["index_kl"],
               **{k: outputs[k] for k in COUNTERS}}
    return loss, metrics


def hybrid_moe_lm(outputs: dict[str, jax.Array], batch: dict[str, Any]
                  ) -> tuple[jax.Array, dict]:
    """The loss of :class:`~..models.hybrid_decoder.HybridDecoderLM`:
    next-token cross-entropy over the vocabulary the model holds, fused with
    the (tied) head, at every position but the window's last; a target across
    a document boundary is kept. The sigmoid router has no loss term: it is
    balanced by its bias, which the step moves outside the gradient. A
    softmax router's Switch term (``moe_aux``, already weighted and summed
    over the expert layers by the model) is added: ``loss = lm_loss +
    moe_aux``. The model's counters (``hybrid_decoder.COUNTERS`` and, of a
    model with state-space or sliding-window layers, ``SSM_COUNTERS`` or
    ``WINDOW_COUNTERS``) ride along into the step's metrics."""
    from distributeddeeplearningspark_tpu.models.hybrid_decoder import (
        COUNTERS,
        SSM_COUNTERS,
        WINDOW_COUNTERS,
    )
    from distributeddeeplearningspark_tpu.train.fused_ce import (
        chunked_softmax_xent,
    )

    per_tok = chunked_softmax_xent(outputs["hidden"][:, :-1],
                                   outputs["lm_head"],
                                   batch["input_ids"][:, 1:])
    loss, metrics = _reduce_next_token(per_tok, batch)
    if "moe_aux" in outputs:
        metrics = {**metrics, "lm_loss": loss, "moe_aux": outputs["moe_aux"]}
        loss = loss + outputs["moe_aux"]
        metrics["loss"] = loss
    return loss, {**metrics, **{k: outputs[k] for k in COUNTERS},
                  **{k: outputs[k] for k in SSM_COUNTERS + WINDOW_COUNTERS
                     if k in outputs}}


def latent_moe_lm(outputs: dict[str, jax.Array], batch: dict[str, Any]
                  ) -> tuple[jax.Array, dict]:
    """:func:`hybrid_moe_lm` plus the multi-token-prediction term of a
    :class:`~..models.hybrid_decoder.HybridDecoderLM` with ``mtp_layers = 1``:
    ``loss = lm_loss + mtp_weight * mtp_nll``. ``mtp_nll`` is the module's
    hidden states through the SAME head against the token TWO positions on,
    at every row but the window's last two (the module ran over all S rows,
    its last on a pad id); ``lm_loss`` the next-token term alone
    (``perplexity`` is of that). Head and embedding get gradients from both."""
    from distributeddeeplearningspark_tpu.train.fused_ce import (
        chunked_softmax_xent,
    )

    lm_loss, metrics = hybrid_moe_lm(outputs, batch)
    per_tok = chunked_softmax_xent(outputs["mtp_hidden"][:, :-2],
                                   outputs["lm_head"],
                                   batch["input_ids"][:, 2:])
    # (the reduction shifts a ``loss_mask`` by one; this term's by two)
    mtp_nll, _ = _reduce_next_token(per_tok, {
        k: v[:, 1:] if k == "loss_mask" else v for k, v in batch.items()})
    loss = lm_loss + outputs["mtp_weight"] * mtp_nll
    return loss, {**metrics, "loss": loss, "lm_loss": lm_loss,
                  "mtp_nll": mtp_nll}


def _add_moe_aux(loss, metrics, outputs) -> tuple[jax.Array, dict]:
    """Fold a model-reported (already-weighted) MoE load-balance loss in."""
    if isinstance(outputs, dict) and "moe_aux" in outputs:
        aux = outputs["moe_aux"]
        loss = loss + aux
        metrics = {**metrics, "loss": loss, "moe_aux": aux}
    return loss, metrics


def causal_lm(logits: jax.Array, batch: dict[str, Any]) -> tuple[jax.Array, dict]:
    """Next-token CE (Llama-2 LoRA fine-tune); respects ``loss_mask`` if
    given. MoE models return ``{"logits", "moe_aux"}`` — the (already
    config-weighted) load-balance term is added and reported."""
    outputs = logits
    if isinstance(logits, dict):
        if "logits" not in logits:
            raise TypeError(
                "model returned the fused-head dict (fused_head_loss=True) — "
                "pair it with losses.causal_lm_fused")
        logits = outputs["logits"]
    labels = batch["input_ids"][:, 1:]
    logits = logits[:, :-1]
    per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    loss, metrics = _reduce_next_token(per_tok, batch)
    return _add_moe_aux(loss, metrics, outputs)
