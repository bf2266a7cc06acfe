"""Analytic operations and bytes of the latent-attention decoder
(``joyai_llm_flash``: DeepSeek-V3's shape: latent attention in every layer,
a dense layer before expert layers with a shared expert, an untied head and a
multi-token-prediction module), from shapes alone, by ``flops.py``'s two
conventions: MODEL operations are what the mathematics REQUIRES (two per
multiply-accumulate of every matrix product, forward x 3, no recomputation,
element-wise work left out; attention over the causal triangle at the
model's own head sizes, nothing padded); KERNEL costs are of one execution of
a kernel."""

from __future__ import annotations


def latent_attention_flops_per_token(*, hidden_size: int, heads: int,
                                     q_lora_rank: int, kv_lora_rank: int,
                                     qk_nope_head_dim: int,
                                     qk_rope_head_dim: int, v_head_dim: int,
                                     seq_len: int) -> tuple[float, float]:
    """``(projections, attention)`` forward operations a position of ONE
    latent-attention layer: the two down- and two up-projections and the
    output projection; QK^T at ``nope + rot`` and PV at ``v_head_dim`` for
    every head over the ``(seq_len + 1) / 2`` keys a query reads on average."""
    d_qk = qk_nope_head_dim + qk_rope_head_dim
    proj = 2.0 * (hidden_size * q_lora_rank + q_lora_rank * heads * d_qk
                  + hidden_size * (kv_lora_rank + qk_rope_head_dim)
                  + kv_lora_rank * heads * (qk_nope_head_dim + v_head_dim)
                  + heads * v_head_dim * hidden_size)
    attend = (seq_len + 1) / 2 * 2.0 * heads * (d_qk + v_head_dim)
    return proj, attend


def latent_moe_lm_flops_per_token(
        *, hidden_size: int, num_layers: int, num_dense_layers: int,
        mtp_layers: int, heads: int, q_lora_rank: int, kv_lora_rank: int,
        qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
        intermediate_size: int, router_width: int, experts_per_token: int,
        experts_held: int, expert_size: int, shared_experts: int,
        vocab_size: int, seq_len: int, train: bool = True,
        train_router: bool = True) -> float:
    """Per position of a ``seq_len`` window that is one causal triangle.

    Every layer: latent attention (above). A dense layer's SwiGLU: three
    products of ``hidden x intermediate``. An expert layer: the router over
    its full width, the shared expert(s) whole, and the routed experts HELD
    HERE in expectation under even routing (``experts_per_token *
    experts_held / router_width`` of a token's assignments; the step's
    counter ``moe_rows_held_share`` is what a run gave them). A router that
    is not trained has no backward products. The head over the vocabulary
    held (untied: the look-up is no product). The MTP module is REQUIRED
    work: its ``2 hidden -> hidden`` projection, one more expert block and
    one more pass of the head."""
    h = hidden_size
    proj, attend = latent_attention_flops_per_token(
        hidden_size=h, heads=heads, q_lora_rank=q_lora_rank,
        kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        seq_len=seq_len)
    swiglu = lambda width: 3 * 2.0 * h * width
    router = 2.0 * h * router_width
    experts = (swiglu(expert_size) * (
        shared_experts + experts_per_token * experts_held / router_width))
    head = 2.0 * h * vocab_size
    dense_layers = num_dense_layers
    expert_layers = num_layers - num_dense_layers + mtp_layers
    fwd = ((num_layers + mtp_layers) * (proj + attend)
           + dense_layers * swiglu(intermediate_size)
           + expert_layers * experts
           + (1 + mtp_layers) * head + mtp_layers * 2.0 * 2 * h * h)
    routers = expert_layers * router
    if not train:
        return fwd + routers
    return fwd * 3 + routers * (3 if train_router else 1)


def mla_attn_kernels(*, batch: int, seq: int, heads: int, qk_head_dim: int,
                     v_head_dim: int, bytes_per_element: int = 2
                     ) -> dict[str, dict[str, float]]:
    """The REQUIRED work of one execution of each flash kernel in the regime
    of latent attention: products over the causal triangle, ``seq (seq + 1) /
    2`` pairs a row and head, QK^T-shaped ones at ``qk_head_dim`` and
    PV-shaped ones at ``v_head_dim`` (no padded width, whatever the kernel's
    VMEM tiles hold), each array moved once: ``flash_fwd`` QK^T and PV, reads
    q, k, v, writes o; ``flash_bwd_dq`` QK^T, dO V^T and dS K, reads q, k, v,
    do, writes dq; ``flash_bwd_dkv`` QK^T, dO V^T, P^T dO and dS^T Q, reads q,
    k, v, do, writes dk, dv. The row statistics (a float a row and head) are
    left out of the bytes, so the least time is a lower bound and the share
    cannot read high for them."""
    pairs = batch * heads * seq * (seq + 1) / 2
    qk, pv = 2.0 * pairs * qk_head_dim, 2.0 * pairs * v_head_dim
    wide = float(batch * seq * heads * qk_head_dim * bytes_per_element)
    narrow = float(batch * seq * heads * v_head_dim * bytes_per_element)
    return {
        "flash_fwd": {"ops": qk + pv, "bytes": 2 * wide + 2 * narrow},
        "flash_bwd_dq": {"ops": 2 * qk + pv, "bytes": 3 * wide + 2 * narrow},
        "flash_bwd_dkv": {"ops": 2 * qk + 2 * pv,
                          "bytes": 3 * wide + 3 * narrow},
    }
