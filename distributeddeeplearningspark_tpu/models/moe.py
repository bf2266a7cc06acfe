"""Routed experts: SwiGLU (or two-matrix relu²) experts behind a top-k router,
with no capacity and nothing dropped, computed for the experts a rank HOLDS.
Two routers, one layer: ``score="softmax"`` (softmax over all experts, then top-k) and
``score="sigmoid"`` (a sigmoid an expert, the top-k SELECTED on score plus a
per-expert bias and WEIGHTED by the score without it; the bias is no
parameter: it lives in the mutable collection :data:`BIAS_COLLECTION` and
the step's own load counts move it, Wang et al. arXiv:2408.15664).

One layer serves every caller: a model told which contiguous range of the
experts it holds (:mod:`.sparse_decoder`: one expert-parallel rank's share,
run without its exchange), and a model that holds them all on a mesh whose
``expert`` axis splits them (:mod:`.llama`): there the same function runs in
a ``shard_map``, every rank computes its own experts' part for the tokens it
sees (tokens are replicated over ``expert``, so no token travels), and a
``psum`` over ``expert`` adds the parts up. The all-to-all that would let a
rank see only ITS tokens' share is not built (ROADMAP queue 2, A.1).

The ``T * k`` assignments are sorted by expert (those of experts held
elsewhere last), the tokens' rows are gathered in that order, and three
(relu² experts: two) grouped matrix products (``jax.lax.ragged_dot``: on the
TPU a grouped-matmul kernel of XLA's own that walks only the tiles of rows
the group sizes cover)
run over a buffer of ``T * k`` rows, the worst case, of which
``T * k * held / num_experts`` are used on average. However unbalanced the
routing, every assignment to a held expert is computed. Router math is
float32 at ``HIGHEST`` precision whatever the activations' dtype.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from distributeddeeplearningspark_tpu.parallel.mesh import (
    AXIS_EXPERT,
    AXIS_SEQ,
    AXIS_TENSOR,
    BATCH_AXES,
)

#: the mesh axes that split TOKENS (batch rows and sequence positions)
TOKEN_AXES = (*BATCH_AXES, AXIS_SEQ)
#: the flax collection of the sigmoid router's selection bias: no gradient
#: reaches it and no optimizer sees it; a step that asks for the collection
#: as mutable gets it back moved by the step's load (``TrainState.mutable``)
BIAS_COLLECTION = "router_bias"
#: added to the sum of a token's top-k sigmoid scores before they divide
SIGMOID_NORM_EPS = 1e-6


def relu2(x):
    """``relu(x)^2``: the activation of a two-matrix expert."""
    return jnp.square(nn.relu(x))


def _zero_past(a, used):
    """Rows ``used`` and beyond of ``a`` set to zero."""
    live = jnp.arange(a.shape[0], dtype=jnp.int32)[:, None] < used
    return jnp.where(live, a, 0)


@jax.custom_vjp
def _rows_to_experts(x, order, inverse, used):
    """x [T, H] -> [T*k, H]: row a is the token of the a-th assignment in
    expert order (``order`` holds token-major assignment ids, k a token).
    Only the first ``used`` rows belong to an expert that is held; the
    grouped products leave the rest of their outputs unwritten, so the
    backward pass zeroes the cotangent there (uninitialised memory is not
    zero, and ``nan * 0`` is not either). Backward is the gather by
    ``inverse`` summed over a token's k assignments, not the scatter-add
    autodiff would write."""
    k = order.shape[0] // x.shape[0]
    return x[order // k]


def _rows_to_experts_fwd(x, order, inverse, used):
    return _rows_to_experts(x, order, inverse, used), (inverse, used,
                                                       x.shape[0])


def _rows_to_experts_bwd(res, g):
    inverse, used, tokens = res
    g = _zero_past(g, used)
    return (g[inverse].reshape(tokens, -1, g.shape[-1]).sum(axis=1)
            .astype(g.dtype), None, None, None)


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _rows_to_tokens(y, order, inverse, used):
    """The inverse permutation: y [T*k, H] in expert order -> token-major,
    with zeros for the rows past ``used`` (see :func:`_rows_to_experts`)."""
    return _zero_past(y, used)[inverse]


def _rows_to_tokens_fwd(y, order, inverse, used):
    return _rows_to_tokens(y, order, inverse, used), order


def _rows_to_tokens_bwd(order, g):
    return g[order], None, None, None


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


def _held_experts(xf, router, w_gate, w_up, w_down, first, bias=None, *,
                  k: int, norm_topk: bool, dtype, score: str = "softmax",
                  train_router: bool = True, routed_scale: float = 1.0,
                  per_rank=lambda a: a):
    """The part of the layer that the ``n`` experts ``first .. first + n``
    add (``w_*`` are their kernels: SwiGLU experts, ``down(silu(gate x) * up
    x)``, or, with ``w_gate`` ``None``, relu² experts, ``down(relu(up
    x)^2)``; ``first`` may be traced): ``xf [T, H] ->
    (y [T, H] float32, assignments of every expert [E] int32, the router's
    probabilities (or sigmoid scores) summed over the tokens [E])``.
    ``bias [E]`` (sigmoid only) is added to the scores for the SELECTION
    alone; ``routed_scale`` multiplies the weights (DeepSeek-V3's
    ``routed_scaling_factor``). Without ``train_router`` the weights of a
    token's experts carry no gradient (neither the router's kernel nor the tokens get one through
    the routing). ``per_rank`` marks the tokens' rows as differing from rank to rank
    where the ranks' work on them begins (inside a ``shard_map``; the routing
    before it is every rank's alike)."""
    tokens, h = xf.shape
    e, n = router.shape[1], w_up.shape[0]
    # float32 in earnest: on the TPU a float32 product is one bf16 pass
    # unless asked otherwise, and a router rounded to 8 bits picks other
    # experts than the model's
    logits = jnp.dot(xf.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert = jax.lax.top_k(probs, k)                     # [T, k]
        if norm_topk:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    else:
        probs = jax.nn.sigmoid(logits)
        chosen_by = probs if bias is None else (
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)))
        _, expert = jax.lax.top_k(chosen_by, k)
        gate = jnp.take_along_axis(probs, expert, axis=-1)
        if norm_topk:
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True)
                           + SIGMOID_NORM_EPS)
    if routed_scale != 1.0:
        gate = gate * routed_scale
    if not train_router:
        gate = jax.lax.stop_gradient(gate)
    local = expert - first
    is_held = (local >= 0) & (local < n)
    # sort key: the held expert's index here, experts held elsewhere last
    key = jnp.where(is_held, local, n).reshape(-1)                 # [T*k]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    counts = jnp.sum(jax.nn.one_hot(expert.reshape(-1), e, dtype=jnp.int32),
                     axis=0)
    rows = jax.lax.dynamic_slice_in_dim(counts, first, n)  # the groups' sizes
    used = jnp.sum(rows)

    xs = _rows_to_experts(per_rank(xf.astype(dtype)), order, inverse, used)
    cast = lambda w: w.astype(dtype)
    up = jax.lax.ragged_dot(xs, cast(w_up), rows)
    if w_gate is None:
        act = relu2(up)
    else:
        act = nn.silu(jax.lax.ragged_dot(xs, cast(w_gate), rows)) * up
    ys = jax.lax.ragged_dot(act, cast(w_down), rows)               # [T*k, H]
    yt = _rows_to_tokens(ys, order, inverse, used)
    y = jnp.einsum("tkh,tk->th", yt.reshape(tokens, k, h),
                   jnp.where(is_held, gate, 0.0).astype(yt.dtype),
                   preferred_element_type=jnp.float32)
    return y, counts, jnp.sum(probs, axis=0)


def _split_over_the_mesh(fn, mesh, first: int, with_bias: bool = False,
                         gated: bool = True):
    """``fn`` (:func:`_held_experts` but for ``first``) for ``x [B, S, H]``
    on a mesh: batch rows over (data, fsdp), positions over ``seq``, the
    experts' kernels over ``expert`` and their hidden width over ``tensor``
    (relu² experts, not ``gated``, come with ``None`` for ``w_gate``).
    A rank computes the part of ITS experts and columns for ITS tokens; the
    parts add up over (expert, tensor), the statistics over the tokens'
    axes."""
    def local(x, router, w_gate, w_up, w_down, *bias):
        b, s, h = x.shape
        mine = first + jax.lax.axis_index(AXIS_EXPERT) * w_up.shape[0]
        # (the transpose of "differs from rank to rank" is the sum of the
        # ranks' cotangents, which is what a token's gradient is)
        y, counts, probs = fn(
            x.reshape(-1, h), router, w_gate, w_up, w_down, mine, *bias,
            per_rank=lambda a: jax.lax.pcast(
                a, (AXIS_EXPERT, AXIS_TENSOR), to="varying"))
        return (jax.lax.psum(y, (AXIS_EXPERT, AXIS_TENSOR)).reshape(b, s, h),
                jax.lax.psum(counts, TOKEN_AXES),
                jax.lax.psum(probs, TOKEN_AXES))

    tokens = P(BATCH_AXES, AXIS_SEQ, None)
    wide = P(AXIS_EXPERT, None, AXIS_TENSOR)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(tokens, P(), wide if gated else None, wide,
                  P(AXIS_EXPERT, AXIS_TENSOR, None),
                  *([P()] if with_bias else [])),
        out_specs=(tokens, P(), P()))


class RoutedExperts(nn.Module):
    """``[B, S, H] -> ([B, S, H], stats)``.

    ``score="softmax"``: ``g = softmax(x Wr)`` over all ``num_experts`` in
    float32; a token's ``top_k`` largest, renormalised to sum 1
    (``norm_topk``). ``score="sigmoid"``: ``s = sigmoid(x Wr)``; a token's
    experts are the ``top_k`` largest of ``s + b`` and their weights ``g_e =
    s_e / (sum over the chosen of s + 1e-6)``, without ``b``
    (``select_bias``: ``b [num_experts]`` float32 in the collection
    :data:`BIAS_COLLECTION`, zeros at first; applied with that collection
    mutable, the layer moves it by ``bias_update_rate * sign(mean_e(c_e) -
    c_e)``, ``c`` being this call's assignment counts over ALL experts, and
    otherwise only reads it). Either way ``y = sum over the token's experts
    that are held of g_e * down_e(silu(gate_e x) * up_e x)`` (``expert_form
    = "relu2"``: ``g_e * down_e(relu(up_e x)^2)``, two matrices an expert and
    no ``w_gate``), with ``g``
    times ``routed_scale`` where that is not 1, plus, with ``shared_size >
    0``, a SHARED expert of the same form and of that width
    that every token passes and every rank computes whole (it belongs to no
    rank's share: the shares of all ranks sum to the whole layer with the
    shared expert counted ONCE; applied with ``"intermediates"`` mutable the
    layer sows the routed part alone there as ``routed``). ``held = (first,
    count)`` is a contiguous range of experts: an
    expert-parallel rank's share. The router keeps its full width and its k a
    token whatever is held; what the absent experts would have added is left
    out (their ranks add it, and the shares of all ranks sum to the whole
    layer: ``tests/test_sparse_decoder.py``, ``tests/test_hybrid_decoder.py``).
    ``held=None`` holds them all. On a mesh of more than one device what is
    held is split once more over the mesh's ``expert`` axis (module
    docstring).

    ``train_router=False`` stops the gradient at the weights ``g``: for a
    share that is trained WITHOUT its exchange. Such a rank sees of a token's
    output only what the experts it holds add, and that part of the gradient
    teaches a router that learns to send its tokens elsewhere (the absent
    experts add nothing, a held expert at random weights adds noise: on the
    chip the held share of the assignments fell from an eighth to under
    0.001% in 40 steps). The bias still moves.

    ``stats``: ``aux`` (Switch's balance loss over ALL experts from the top-k
    assignments, ``E * sum_e f_e * P_e`` with ``f_e`` the share of the
    ``T * k`` assignments and ``P_e`` the mean probability: 1 when uniform),
    ``load_max_over_mean`` (rows of the fullest held expert over the mean of
    the held), ``rows_held_share`` (assignments that land on held experts
    over all) and, with ``select_bias``, ``bias_abs_max`` (of the bias this
    call selected with).
    """

    hidden_size: int
    intermediate_size: int
    num_experts: int
    top_k: int
    held: tuple[int, int] | None = None
    norm_topk: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    score: str = "softmax"
    select_bias: bool = False
    bias_update_rate: float = 0.001
    train_router: bool = True
    routed_scale: float = 1.0
    shared_size: int = 0
    expert_form: str = "swiglu"

    @nn.compact
    def __call__(self, x: jax.Array) -> tuple[jax.Array, dict]:
        from distributeddeeplearningspark_tpu.ops.ring_attention import (
            resolve_mesh,
        )

        h, i, e, k = (self.hidden_size, self.intermediate_size,
                      self.num_experts, self.top_k)
        first, n = self.held or (0, e)
        if not (1 <= k <= e and 0 <= first and first + n <= e and n >= 1):
            raise ValueError(f"top_k {k}, held {self.held} of {e} experts")
        if self.score not in ("softmax", "sigmoid") or (
                self.select_bias and self.score != "sigmoid"):
            raise ValueError(f"score {self.score!r} (softmax or sigmoid), "
                             f"select_bias {self.select_bias} (sigmoid only)")
        if self.expert_form not in ("swiglu", "relu2"):
            raise ValueError(f"expert_form {self.expert_form!r}: swiglu "
                             f"(three matrices) or relu2 (two)")
        gated = self.expert_form == "swiglu"
        router = self.param("router", nn.initializers.lecun_normal(), (h, e),
                            jnp.float32)
        # lecun-normal by each expert's OWN fan-in: the leading axis counts
        # experts and is no part of any product (taken for a receptive
        # field, as the default would, it shrinks every kernel by
        # sqrt(held) and the layer's output by its third power)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", batch_axis=(0,))
        w_gate = self.param("w_gate", init, (n, h, i),
                            self.param_dtype) if gated else None
        w_up = self.param("w_up", init, (n, h, i), self.param_dtype)
        w_down = self.param("w_down", init, (n, i, h), self.param_dtype)

        bias = self.variable(
            BIAS_COLLECTION, "bias", jnp.zeros, (e,),
            jnp.float32) if self.select_bias else None
        chosen_with = () if bias is None else (bias.value,)
        fn = functools.partial(_held_experts, k=k, norm_topk=self.norm_topk,
                               dtype=self.dtype, score=self.score,
                               train_router=self.train_router,
                               routed_scale=self.routed_scale)
        kernels = (router, w_gate, w_up, w_down)
        mesh = resolve_mesh()
        if mesh is None or mesh.size == 1:
            y, counts, probs = fn(x.reshape(-1, h), *kernels, first,
                                  *chosen_with)
        else:
            shape = dict(mesh.shape)
            rows = shape[BATCH_AXES[0]] * shape[BATCH_AXES[1]]
            if (x.ndim != 3 or x.shape[0] % rows or x.shape[1] % shape[AXIS_SEQ]
                    or n % shape[AXIS_EXPERT] or i % shape[AXIS_TENSOR]):
                raise ValueError(
                    f"routed experts on mesh {shape}: x {x.shape} must be "
                    f"[B, S, H] with B dividing by data x fsdp and S by seq, "
                    f"the {n} experts held by expert, their width {i} by "
                    f"tensor")
            y, counts, probs = _split_over_the_mesh(
                fn, mesh, first, with_bias=bias is not None, gated=gated)(
                x, *kernels, *chosen_with)

        assignments = jnp.float32(x.size // h * k)
        rows_f = counts[first:first + n].astype(jnp.float32)
        stats = {
            "aux": e * jnp.sum(counts.astype(jnp.float32) / assignments
                               * probs * (k / assignments)),
            "load_max_over_mean": jnp.max(rows_f)
            / jnp.maximum(jnp.mean(rows_f), 1.0),
            "rows_held_share": jnp.sum(rows_f) / assignments,
        }
        if bias is not None:
            stats["bias_abs_max"] = jnp.max(jnp.abs(chosen_with[0]))
            if (self.is_mutable_collection(BIAS_COLLECTION)
                    and not self.is_initializing()):
                load = counts.astype(jnp.float32)
                bias.value = chosen_with[0] + self.bias_update_rate * jnp.sign(
                    jnp.mean(load) - load)
        y = y.reshape(x.shape).astype(x.dtype)
        # (written only where a caller asks for "intermediates" as mutable)
        self.sow("intermediates", "routed", y)
        if self.shared_size:
            dense = lambda feats, name: nn.Dense(
                feats, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name)
            if gated:
                act = (nn.silu(dense(self.shared_size, "shared_gate")(x))
                       * dense(self.shared_size, "shared_up")(x))
            else:
                act = relu2(dense(self.shared_size, "shared_up")(x))
            y = y + dense(h, "shared_down")(act)
        return y, stats
