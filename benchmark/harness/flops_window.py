"""Analytic operations and bytes of a decoder of sliding-window and full
attention layers (``laguna_xs2``: a dense full layer, then windowed and full
layers with routed experts beside a shared one), from shapes and the
traffic's length distribution alone, by ``flops.py``'s two conventions:
MODEL operations are what the mathematics REQUIRES (two per
multiply-accumulate of every matrix product, forward x 3, no recomputation,
element-wise work left out) and count a full layer's attention over the
in-document causal pairs and a window layer's over the in-document pairs
inside its window, whatever blocks a kernel walks; KERNEL costs are of one
execution of a kernel."""

from __future__ import annotations

import functools

import numpy as np

from benchmark.harness import flops_hybrid


@functools.cache
def _window_pairs_share(seq_len: int, window: int, median: float,
                        sigma: float, lo: int, hi: int) -> float:
    rng = np.random.default_rng(flops_hybrid.PAIRS_SEED)
    lens = np.clip(np.exp(rng.normal(np.log(median), sigma,
                                     flops_hybrid.PAIRS_DOCS)),
                   lo, hi).astype(np.int64) + 1           # the EOS is its
    ends = np.cumsum(lens)
    windows = int(ends[-1]) // seq_len
    cuts = np.union1d(ends[ends <= windows * seq_len],
                      np.arange(1, windows + 1) * seq_len)
    pieces = np.diff(np.concatenate(([0], cuts))).astype(np.float64)
    # a piece of length L: sum over its positions t of min(t + 1, window)
    inside = np.minimum(pieces, window)
    pairs = np.sum(inside * (inside + 1) / 2
                   + np.maximum(pieces - window, 0) * window)
    return float(pairs / (windows * seq_len * window))


def window_pairs_share(traffic: dict, window: int) -> float:
    """The EXPECTATION, under the traffic file's length distribution (as
    ``flops_hybrid.in_document_pairs_share`` draws it, same seed and count),
    of the (query, key) pairs inside one document and inside the window, ``0
    <= i - j < window``, over the window's ``S x window``: what the step's
    counter ``window_pairs_share`` reads on average. A property of the
    traffic file and of no run."""
    return _window_pairs_share(int(traffic["seq_len"]), int(window),
                               float(traffic["doc_len_median"]),
                               float(traffic["doc_len_sigma"]),
                               int(traffic["doc_len_min"]),
                               int(traffic["doc_len_max"]))


def window_moe_lm_flops_per_token(
        *, hidden_size: int, layer_types: list[str], heads_per_layer: list[int],
        mlp_layer_types: list[str], num_key_value_heads: int, head_dim: int,
        window: int, intermediate_size: int, router_width: int,
        experts_per_token: int, experts_held: int, expert_size: int,
        shared_size: int, vocab_size: int, seq_len: int, pairs_share: float,
        window_share: float, train: bool = True,
        train_router: bool = True) -> float:
    """Per position of a packed ``seq_len`` window.

    An attention layer of ``heads`` query heads: q, k, v and output
    projections, the per-head gate (``hidden x heads``), and QK^T and PV for
    every query head over its pairs: a full layer's are the in-document
    causal pairs, ``pairs_share * (seq_len + 1) / 2`` a query on average, a
    sliding layer's the in-document pairs inside the window, ``window_share
    * window`` a query. A dense layer's SwiGLU: three products of ``hidden x
    intermediate``. An expert layer: the router over its full width, the
    experts HELD HERE in expectation under balanced routing
    (``experts_per_token * experts_held / router_width`` of a token's
    assignments), and the shared expert, three products of ``hidden x
    shared_size``. A router that is not trained has no backward products.
    Then the untied head over the vocabulary held (the look-up is no
    product)."""
    h, d = hidden_size, head_dim
    kv = num_key_value_heads * d
    dense = 3 * 2.0 * h * intermediate_size
    experts = (experts_per_token * experts_held / router_width
               * 3 * 2.0 * h * expert_size + 3 * 2.0 * h * shared_size)
    router = 2.0 * h * router_width
    fwd, routers = 2.0 * h * vocab_size, 0.0
    for kind, heads, mlp in zip(layer_types, heads_per_layer,
                                mlp_layer_types):
        qo = heads * d
        pairs = (window_share * window if kind == "sliding_attention"
                 else pairs_share * (seq_len + 1) / 2)
        fwd += 2.0 * (h * qo + 2 * h * kv + qo * h + h * heads)
        fwd += pairs * 2 * 2.0 * qo
        fwd += dense if mlp == "dense" else experts
        routers += 0.0 if mlp == "dense" else router
    if not train:
        return fwd + routers
    return fwd * 3 + routers * (3 if train_router else 1)


def flash_window_kernels(*, batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, window: int, pairs_share: float,
                         bytes_per_element: int = 2
                         ) -> dict[str, dict[str, float]]:
    """The REQUIRED work of one execution of each band kernel
    (``ops/flash_attention.py`` with a ``window``): products over the
    in-document pairs inside the window, ``pairs_share * seq * window`` a row
    and query head, whatever blocks the kernel walks (a walk of the band's
    blocks computes about twice that at a block of the window's size, a walk
    of the causal triangle 16 times it at 16,384: either reads low against
    this yardstick, and never over 100%). ``flash_window_fwd`` 2 products
    (QK^T, PV), ``flash_window_bwd_dq`` 3, ``flash_window_bwd_dkv`` 4; each
    tensor moved once, the grouped keys and values at their own head
    count."""
    product = 2.0 * batch * heads * head_dim * pairs_share * seq * window
    q = float(batch * seq * heads * head_dim * bytes_per_element)
    kv = float(batch * seq * kv_heads * head_dim * bytes_per_element)
    return {
        "flash_window_fwd": {"ops": 2 * product, "bytes": 2 * q + 2 * kv},
        "flash_window_bwd_dq": {"ops": 3 * product, "bytes": 3 * q + 2 * kv},
        "flash_window_bwd_dkv": {"ops": 4 * product,
                                 "bytes": 2 * q + 4 * kv},
    }


if __name__ == "__main__":   # the traffic file's shares, for PERF.md
    import json
    import sys

    with open(sys.argv[1]) as f:
        traffic = json.load(f)
    print(flops_hybrid.in_document_pairs_share(traffic),
          window_pairs_share(traffic, int(sys.argv[2])))
