"""Analytic operations and bytes of the state-space decoder
(``nemotron3_nano_30b_a3b``: blocks of ONE sublayer in a pattern of Mamba-2
layers ``M``, relu² expert layers ``E`` beside a shared expert, and attention
layers ``*`` over packed documents), from shapes and the traffic's length
distribution alone, by ``flops.py``'s two conventions: MODEL operations are
what the mathematics REQUIRES (two per multiply-accumulate of every matrix
product, forward x 3, no recomputation, element-wise work left out), with
attention over the IN-DOCUMENT causal pairs only and the scan in its
published chunked form at ``chunk_size``; STAGE costs are of one execution
of a stage."""

from __future__ import annotations


def ssd_products_per_token(*, heads: int, head_dim: int, groups: int,
                           state_size: int, chunk: int) -> float:
    """Forward operations a position of the FOUR products of the chunked
    scan (arXiv:2405.21060 section 6) at chunks of ``chunk``: ``C B^T`` a
    group (``chunk x chunk x N``), the masked product with ``dt x`` a head
    (``chunk x chunk x P``), a chunk's closing state a head (``chunk x P x
    N``) and the handed state's part of ``y`` (the same). The two
    ``chunk``-square products are counted whole, as a matrix unit runs them;
    the mask halves what of them is kept."""
    return 2.0 * (chunk * groups * state_size + chunk * heads * head_dim
                  + 2 * heads * head_dim * state_size)


def ssm_moe_lm_flops_per_token(
        *, hidden_size: int, pattern: str, mamba_heads: int,
        mamba_head_dim: int, groups: int, state_size: int, chunk: int,
        num_attention_heads: int, num_key_value_heads: int, head_dim: int,
        router_width: int, experts_per_token: int, experts_held: int,
        expert_size: int, shared_size: int, vocab_size: int, seq_len: int,
        pairs_share: float, train: bool = True,
        train_router: bool = True) -> float:
    """Per position of a packed ``seq_len`` window.

    ``M``: the input projection to ``2 inner + 2 G N + heads`` (``inner =
    heads x head_dim``), the four products of the chunked scan, the output
    projection (the convolution's taps, the gate and the norm are
    element-wise work). ``*``: q, k, v and output projections, and QK^T and
    PV for every query head over the keys of the query's own document up to
    itself: ``pairs_share * (seq_len + 1) / 2`` of them on average. ``E``:
    the router over its full width, the shared expert whole, and the routed
    experts HELD HERE in expectation under even routing (``experts_per_token
    * experts_held / router_width`` of a token's assignments), every expert
    TWO products (relu²). A router that is not trained has no backward
    products. Then the untied head over the vocabulary held (the look-up is
    no product)."""
    h = hidden_size
    inner = mamba_heads * mamba_head_dim
    mamba = (2.0 * h * (2 * inner + 2 * groups * state_size + mamba_heads)
             + ssd_products_per_token(
                 heads=mamba_heads, head_dim=mamba_head_dim, groups=groups,
                 state_size=state_size, chunk=chunk)
             + 2.0 * inner * h)
    qo, kv = num_attention_heads * head_dim, num_key_value_heads * head_dim
    attend = (2.0 * (h * qo + 2 * h * kv + qo * h)
              + pairs_share * (seq_len + 1) / 2 * 2 * 2.0 * qo)
    router = 2.0 * h * router_width
    experts = 2 * 2.0 * h * (
        shared_size
        + experts_per_token * experts_held / router_width * expert_size)
    fwd = (2.0 * h * vocab_size + pattern.count("M") * mamba
           + pattern.count("*") * attend + pattern.count("E") * experts)
    routers = pattern.count("E") * router
    if not train:
        return fwd + routers
    return fwd * 3 + routers * (3 if train_router else 1)


def ssd_scan_work(*, batch: int, seq: int, heads: int, head_dim: int,
                  groups: int, state_size: int, chunk: int,
                  bytes_per_element: int = 2) -> dict[str, dict[str, float]]:
    """The REQUIRED operations and bytes of ONE execution of the scan of one
    layer, whatever runs it (XLA today, a kernel later: the same yardstick):

    * ``forward``: the four products of the chunked form; ``x``, ``B``,
      ``C`` read and ``y`` written once in the activations' dtype, ``dt``
      read once in float32, the states (``heads x head_dim x state_size``
      float32) moved once a chunk.
    * ``backward``: twice the forward's products (every product has two
      cotangents) and the forward's again (the masks and states are made
      again, not kept); ``x``, ``dt``, ``B``, ``C`` and the cotangent of
      ``y`` read, the four cotangents written, the states and their
      cotangents once a chunk each.

    Nothing of size ``[chunks, heads, chunk, chunk]`` is counted: it need
    not leave the chip's fast memory, which is what a kernel is for."""
    t = float(batch * seq)
    e = bytes_per_element
    products = t * ssd_products_per_token(
        heads=heads, head_dim=head_dim, groups=groups,
        state_size=state_size, chunk=chunk)
    x = t * heads * head_dim * e
    bc = 2 * t * groups * state_size * e
    dt = t * heads * 4.0
    states = batch * (seq / chunk) * heads * head_dim * state_size * 4.0
    return {
        "forward": {"ops": products,
                    "bytes": 2 * x + bc + dt + states},
        "backward": {"ops": 3 * products,
                     "bytes": 3 * x + 2 * (bc + dt) + 2 * states},
    }
