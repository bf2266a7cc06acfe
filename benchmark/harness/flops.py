"""Analytic operations and bytes, from shapes alone.

Model operations are what the forward and backward passes REQUIRE: two
floating-point operations per multiply-accumulate of every matrix
multiplication and convolution, the backward pass counted as twice the
forward (one product for the input's gradient, one for the weight's), no
recomputation, element-wise work and look-ups left out. That is the
convention published utilization figures use (PaLM, appendix B).

Kernel operations are what a kernel does AS IT IS WRITTEN, recomputation
included, because a kernel's roofline share is about the kernel, not the
model.
"""

from __future__ import annotations


def attention_matmul_flops(batch: int, heads: int, seq: int, head_dim: int,
                           *, train: bool = True) -> float:
    """Model FLOPs of one non-causal attention: QK^T and PV forward (two
    products of 2*b*h*s*s*d), dV, dP, dQ, dK backward (four more). Copied
    from the program's ``metrics.attention_matmul_flops``."""
    one = 2.0 * batch * heads * seq * seq * head_dim
    return one * (6 if train else 2)


def bert_mlm_flops_per_token(*, hidden_size: int, num_hidden_layers: int,
                             num_attention_heads: int, intermediate_size: int,
                             vocab_size: int, seq_len: int,
                             max_predictions: int, train: bool = True) -> float:
    """Per position of a ``seq_len`` window.

    Each encoder layer: Q, K, V and output projections (4 products of H x H)
    and the two feed-forward products (H x I each): 2*(4*H*H + 2*H*I). The
    attention products per position: 2 * 2*s*H forward. The MLM head runs on
    ``max_predictions`` gathered positions of every ``seq_len``: the H x H
    transform and the tied H x V decoder. The embedding look-ups are gathers,
    not products, so the 30,522 x 768 table counts once, as the decoder.
    """
    h, i = hidden_size, intermediate_size
    layer = 2.0 * (4 * h * h + 2 * h * i)
    attn = attention_matmul_flops(1, num_attention_heads, seq_len,
                                  h // num_attention_heads, train=False) / seq_len
    head = 2.0 * (h * h + h * vocab_size) * max_predictions / seq_len
    fwd = num_hidden_layers * (layer + attn) + head
    return fwd * (3 if train else 1)


def resnet_convs(*, stage_sizes: list[int], width: int, num_classes: int,
                 image_size: int) -> list[tuple[str, int, int, int, int]]:
    """Every product of a bottleneck ResNet v1.5 as ``(name, output
    positions, kernel area, channels in, channels out)``: 7x7/2 stem, 3x3/2
    max pool, then per block 1x1 -> 3x3 (carrying the stride) -> 1x1 with a
    1x1 projection on the first block of a stage, global pool, dense head."""
    out = []
    size = -(-image_size // 2)
    out.append(("stem", size * size, 49, 3, width))
    size = -(-size // 2)  # max pool
    cin = width
    for stage, blocks in enumerate(stage_sizes):
        f = width * 2 ** stage
        for b in range(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            out_size = -(-size // stride)
            tag = f"s{stage}b{b}"
            out.append((tag + ".1x1a", size * size, 1, cin, f))
            out.append((tag + ".3x3", out_size * out_size, 9, f, f))
            out.append((tag + ".1x1b", out_size * out_size, 1, f, 4 * f))
            if cin != 4 * f or stride != 1:
                out.append((tag + ".proj", out_size * out_size, 1, cin, 4 * f))
            cin, size = 4 * f, out_size
    out.append(("head", 1, 1, cin, num_classes))
    return out


def resnet_flops_per_image(*, train: bool = True, **shape) -> float:
    fwd = sum(2.0 * pos * area * cin * cout
              for _, pos, area, cin, cout in resnet_convs(**shape))
    return fwd * (3 if train else 1)


def flash_kernels(batch: int, heads: int, seq: int, head_dim: int,
                  *, bytes_per_element: int = 2) -> dict[str, dict[str, float]]:
    """Operations and least bytes of the program's three flash kernels as
    they are written (``ops/flash_attention.py``), non-causal, full blocks.

    One product is 2*b*h*s*s*d operations; one tensor is b*s*h*d elements.

    * ``fwd``: S = QK^T, O = PV: 2 products. Reads q, k, v, writes o.
    * ``bwd_dq``: recomputes S, then dP = dO V^T, dQ = dS K: 3 products.
      Reads q, k, v, do, writes dq.
    * ``bwd_dkv``: recomputes S, dV = P^T dO, dP = dO V^T, dK = dS^T Q:
      4 products. Reads q, k, v, do, writes dk, dv.

    The log-sum-exp and delta rows (b*h*s floats) are left out of the bytes:
    1/d of a tensor. Bytes are the least the kernel must move, each tensor
    once; what it re-reads per block is its own affair.
    """
    product = 2.0 * batch * heads * seq * seq * head_dim
    tensor = float(batch * seq * heads * head_dim * bytes_per_element)
    return {
        "fwd": {"ops": 2 * product, "bytes": 4 * tensor},
        "bwd_dq": {"ops": 3 * product, "bytes": 5 * tensor},
        "bwd_dkv": {"ops": 4 * product, "bytes": 6 * tensor},
    }


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
