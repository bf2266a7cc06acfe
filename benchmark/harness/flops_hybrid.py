"""Analytic operations and bytes of the hybrid decoder (``lfm2_24b_a2b``:
gated short convolutions among causal attention layers over packed
documents, a dense layer before the expert layers), from shapes and the
traffic's length distribution alone, by ``flops.py``'s two conventions: MODEL
operations are what the mathematics REQUIRES (two per multiply-accumulate of
every matrix product, forward x 3, no recomputation, element-wise work left
out) and count attention over the IN-DOCUMENT causal pairs only, whatever
blocks a kernel walks; KERNEL costs are of one execution of a kernel."""

from __future__ import annotations

import functools

import numpy as np

#: the seed and the number of documents the expectation below is taken over
PAIRS_SEED, PAIRS_DOCS = 20260930, 1 << 17


@functools.cache
def _pairs_share(seq_len: int, median: float, sigma: float, lo: int, hi: int
                 ) -> float:
    rng = np.random.default_rng(PAIRS_SEED)
    lens = np.clip(np.exp(rng.normal(np.log(median), sigma, PAIRS_DOCS)),
                   lo, hi).astype(np.int64) + 1           # the EOS is its
    ends = np.cumsum(lens)
    windows = int(ends[-1]) // seq_len
    # cut every document at the window boundaries it spans: the pieces are
    # the segments the model sees
    cuts = np.union1d(ends[ends <= windows * seq_len],
                      np.arange(1, windows + 1) * seq_len)
    pieces = np.diff(np.concatenate(([0], cuts))).astype(np.float64)
    pairs = np.sum(pieces * (pieces + 1) / 2)
    return float(pairs / (windows * seq_len * (seq_len + 1) / 2))


def in_document_pairs_share(traffic: dict) -> float:
    """The EXPECTATION, under the traffic file's length distribution
    (log-normal, clipped, one EOS a document, packed back to back into
    windows of ``seq_len``), of the causal (query, key) pairs that lie inside
    one document over the window's ``S (S + 1) / 2``: computed numerically
    over ``PAIRS_DOCS`` documents drawn with the fixed ``PAIRS_SEED``, so it
    is a property of the traffic file and of no run."""
    return _pairs_share(int(traffic["seq_len"]),
                        float(traffic["doc_len_median"]),
                        float(traffic["doc_len_sigma"]),
                        int(traffic["doc_len_min"]),
                        int(traffic["doc_len_max"]))


def hybrid_moe_lm_flops_per_token(
        *, hidden_size: int, layer_types: list[str], num_dense_layers: int,
        num_attention_heads: int, num_key_value_heads: int, head_dim: int,
        intermediate_size: int, router_width: int, experts_per_token: int,
        experts_held: int, expert_size: int, vocab_size: int, seq_len: int,
        pairs_share: float, train: bool = True,
        train_router: bool = True) -> float:
    """Per position of a packed ``seq_len`` window.

    A convolution layer: the input projection to three times the width and
    the output projection (the taps are element-wise work). An attention
    layer: q, k, v and output projections, and QK^T and PV for every query
    head over the keys of the query's own document up to itself: ``pairs_share
    * (seq_len + 1) / 2`` of them on average. A dense layer's SwiGLU: three
    products of ``hidden x intermediate``. An expert layer: the router over
    its full width, and the experts HELD HERE in expectation under balanced
    routing (``experts_per_token * experts_held / router_width`` of a token's
    assignments; the step's counter ``moe_rows_held_share`` is what a run
    gave them). A router that is not trained (``train_router`` false: a share
    without its exchange) has no backward products. Then the head over the
    vocabulary held (the embedding, tied: the look-up is no product)."""
    h, d = hidden_size, head_dim
    qo, kv = num_attention_heads * d, num_key_value_heads * d
    conv = 2.0 * (h * 3 * h + h * h)
    attend = (2.0 * (h * qo + 2 * h * kv + qo * h)
              + pairs_share * (seq_len + 1) / 2 * 2 * 2.0 * qo)
    dense = 3 * 2.0 * h * intermediate_size
    router = 2.0 * h * router_width
    experts = (experts_per_token * experts_held / router_width
               * 3 * 2.0 * h * expert_size)
    fwd, routers = 2.0 * h * vocab_size, 0.0
    for i, kind in enumerate(layer_types):
        fwd += conv if kind == "conv" else attend
        fwd += dense if i < num_dense_layers else experts
        routers += 0.0 if i < num_dense_layers else router
    if not train:
        return fwd + routers
    return fwd * 3 + routers * (3 if train_router else 1)


def shortconv_kernels(*, batch: int, seq: int, channels: int, taps: int,
                      bytes_per_element: int = 2) -> dict[str, dict[str, float]]:
    """Operations and least bytes of ONE execution of each short-convolution
    kernel (``ops/short_conv.py``), by its name in the trace.

    * ``shortconv_fwd`` reads the ``[T, 3C]`` projection, eight int32 lanes of
      segment ids a position and the taps, writes ``[T, C]``: ``2 * taps + 2``
      element-wise operations an output element (the gate product, a
      multiply-add a tap, the output gate).
    * ``shortconv_bwd`` reads the same and ``dy``, writes the ``[T, 3C]``
      cotangent and the taps' gradient: the forward's work again, the
      transposed taps, the taps' gradient and three gate products.

    None of it is a matrix product; set against the chip's matrix peak the
    operations never bound, the bytes do."""
    t = float(batch * seq)
    e = bytes_per_element
    ids, w = t * 8 * 4, channels * 8 * 4.0
    return {
        "shortconv_fwd": {"ops": t * channels * (2 * taps + 2),
                          "bytes": t * channels * 4 * e + ids + w},
        "shortconv_bwd": {"ops": t * channels * (6 * taps + 6),
                          "bytes": t * channels * 8 * e + ids + 2 * w},
    }


def flash_causal_kernels(*, batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, pairs_share: float,
                         bytes_per_element: int = 2
                         ) -> dict[str, dict[str, float]]:
    """The REQUIRED work of one execution of each flash kernel in the regime
    causal + segment ids: products over the in-document causal pairs,
    ``pairs_share * seq (seq + 1) / 2`` a row and query head, whatever blocks
    the kernel walks (today it walks every block on or under the diagonal and
    masks, so its share of this reads low; a kernel that skipped the blocks no
    document spans would read higher against the SAME yardstick, never over
    100%). ``flash_fwd`` 2 products (QK^T, PV), ``flash_bwd_dq`` 3,
    ``flash_bwd_dkv`` 4; each tensor moved once (``flops.flash_kernels``'
    convention), the grouped keys and values at their own head count."""
    product = (2.0 * batch * heads * head_dim
               * pairs_share * seq * (seq + 1) / 2)
    q = float(batch * seq * heads * head_dim * bytes_per_element)
    kv = float(batch * seq * kv_heads * head_dim * bytes_per_element)
    return {
        "flash_fwd": {"ops": 2 * product, "bytes": 2 * q + 2 * kv},
        "flash_bwd_dq": {"ops": 3 * product, "bytes": 3 * q + 2 * kv},
        "flash_bwd_dkv": {"ops": 4 * product, "bytes": 2 * q + 4 * kv},
    }


if __name__ == "__main__":   # the traffic file's share, for PERF.md
    import json
    import sys

    with open(sys.argv[1]) as f:
        print(in_document_pairs_share(json.load(f)))
