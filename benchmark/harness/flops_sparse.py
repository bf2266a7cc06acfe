"""Analytic operations and bytes of the sparse-attention, routed-expert
decoder (``keye_vl2_30b_a3b``), from shapes alone, by ``flops.py``'s two
conventions: MODEL operations are what the mathematics needs (two per
multiply-accumulate of every matrix product, forward x 3, no recomputation,
element-wise work left out) and count the SELECTION, not ``s^2``; KERNEL
operations are what each Pallas kernel of ``ops/indexed_attention.py`` does
as it is written, one execution, over the tiles it does not skip."""

from __future__ import annotations


def mean_selected_keys(seq_len: int, topk: int) -> float:
    """Mean over the queries of a causal window of ``min(t + 1, topk)``."""
    k = min(topk, seq_len)
    return (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len


def sparse_moe_lm_flops_per_token(
        *, hidden_size: int, num_hidden_layers: int, num_attention_heads: int,
        num_key_value_heads: int, head_dim: int, index_heads: int,
        index_head_dim: int, topk: int, router_width: int,
        experts_per_token: int, experts_held: int, expert_size: int,
        vocab_size: int, seq_len: int, train: bool = True) -> float:
    """Per position of a causal ``seq_len`` window.

    A layer: q, k, v and output projections; the indexer's three projections;
    its scores over the causal prefix (mean ``(seq_len + 1) / 2`` keys, one
    ``index_head_dim`` product a head and key); the attention products QK^T
    and PV over the SELECTED keys (mean ``min(t + 1, topk)``) for every query
    head; the router over its full width; and the experts HELD HERE: a token
    sends ``experts_per_token`` assignments over ``router_width`` experts, so
    ``experts_per_token * experts_held / router_width`` of them land here IN
    EXPECTATION under balanced routing (1 for 8 of 128 with 16 held): the
    count is of that expectation, not of a run's routing. The loss's target
    reuses the attention's probabilities and needs no product. Then the head
    over the vocabulary that is held. Look-ups are not products.
    """
    h, d = hidden_size, head_dim
    qo = num_attention_heads * d
    kv = num_key_value_heads * d
    proj = 2.0 * (h * qo + 2 * h * kv + qo * h)
    index_proj = 2.0 * h * (index_heads * index_head_dim + index_head_dim
                            + index_heads)
    index_scores = (seq_len + 1) / 2 * 2.0 * index_heads * index_head_dim
    attend = mean_selected_keys(seq_len, topk) * 2 * 2.0 * num_attention_heads * d
    router = 2.0 * h * router_width
    experts = (experts_per_token * experts_held / router_width
               * 3 * 2.0 * h * expert_size)
    layer = proj + index_proj + index_scores + attend + router + experts
    fwd = num_hidden_layers * layer + 2.0 * h * vocab_size
    return fwd * (3 if train else 1)


def dsa_kernels(*, batch: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, index_heads: int, index_head_dim: int,
                block: int = 512, bytes_per_element: int = 2
                ) -> dict[str, dict[str, float]]:
    """Operations and least bytes of ONE execution of each kernel, keyed by
    the name it has in the trace. A kernel walks the ``n (n + 1) / 2`` tiles
    of ``block x block`` on or under the diagonal and skips the rest; the
    selection leaves no tile under the diagonal empty, so every one is
    computed in full (masked-dense). One product below is ``2 * block^2 *
    depth`` operations a tile.

    * ``dsa_index_fwd``: a product of depth ``index_head_dim`` for each index
      head. Reads qI, kI, w; writes the scores, all ``seq^2`` of them (the
      tiles above the diagonal are filled with -inf), in float32.
    * ``dsa_index_select``: threshold and tie cut of every query by radix
      select: compares and adds, no product, so no operation of the kind the
      chip's peak counts; reads the scores once (float32), writes two numbers
      a query. Bound by memory.
    * ``dsa_index_bwd``: the scores again, dq and dk: 3 products a head.
      Reads qI, kI, w and the causal half of the cotangent (f32); writes
      dqI, dkI, dw in float32.
    * ``dsa_attend_fwd`` 2 products a query head (QK^T, PV);
      ``dsa_attend_bwd_dq`` 3 (QK^T, dP, dQ); ``dsa_attend_bwd_dkv`` 4 (QK^T,
      dV, dP, dK). Each reads the causal half of the int8 mask once and its
      tensors once (the flash kernels' convention in ``flops.py``).
    * ``dsa_kl_target``: QK^T for every query head; reads q, k, the
      log-sum-exps, the mask and the scores; writes the cotangent (f32).
    """
    n = seq // block
    tiles = batch * n * (n + 1) / 2
    tile = 2.0 * block * block
    e = bytes_per_element
    q = float(batch * seq * heads * head_dim * e)
    kv = float(batch * seq * kv_heads * head_dim * e)
    qi = float(batch * seq * index_heads * index_head_dim * e)
    ki = float(batch * seq * index_head_dim * e)
    wi = float(batch * seq * index_heads * 4)
    half_f32 = tiles * block * block * 4.0
    half_i8 = tiles * block * block * 1.0
    index_product = tiles * tile * index_head_dim * index_heads
    attend_product = tiles * tile * head_dim * heads
    return {
        "dsa_index_fwd": {"ops": index_product,
                          "bytes": qi + ki + wi + batch * seq * seq * 4.0},
        "dsa_index_select": {"ops": 0.0,
                             "bytes": batch * seq * seq * 4.0
                             + batch * seq * 8.0},
        "dsa_index_bwd": {"ops": 3 * index_product,
                          "bytes": qi + ki + wi + half_f32
                          + 2 * (qi + ki) + wi},
        "dsa_attend_fwd": {"ops": 2 * attend_product,
                           "bytes": 2 * q + 2 * kv + half_i8},
        "dsa_attend_bwd_dq": {"ops": 3 * attend_product,
                              "bytes": 3 * q + 2 * kv + half_i8},
        "dsa_attend_bwd_dkv": {"ops": 4 * attend_product,
                               "bytes": 2 * q + 4 * kv + half_i8},
        "dsa_kl_target": {"ops": attend_product,
                          "bytes": q + kv + half_i8 + 2 * half_f32},
    }
