"""Kernels of the main path compiled at real widths for a DESCRIBED TPU v5e
(no chip attached: the TPU's compiler is installed here). What interpret mode
cannot show: Mosaic's tiling and VMEM limits, and what the compiler refuses.
A compile that passes is not a chip run and yields no number.

All such tests live in THIS file, and the topology is described inside a
fixture: only one process may load the TPU's library, the driver runs the
suite with several workers, and a module that touched it at import time
would fail in every worker but one (on-chip-measurement guide, section 2).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _jaxpr import eqns


@pytest.fixture(scope="module")
def four_chips():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device cannot be read back from the
    persistent cache without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def routed_as_on_the_chip(monkeypatch):
    """The attention router and ``pallas_interpret`` ask the one platform
    predicate, and this process is on the CPU: answer for the chip the
    compile is for, here in the test and through no option of the program."""
    from distributeddeeplearningspark_tpu.ops import attention
    from distributeddeeplearningspark_tpu.utils import env

    monkeypatch.setattr(env, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)


@pytest.mark.parametrize("precision", [None, "highest"])
def test_indexed_attention_kernels_compile_at_published_widths(
        one_chip, no_compile_cache, precision):
    """Forward and backward of ``ops/indexed_attention.py`` at one window of
    the benchmark's third configuration: 32 / 4 heads of 128, an indexer of
    16 heads of 64, top 2,048 of 8,192. Under a caller's
    ``default_matmul_precision("highest")`` too (the kernels pin theirs;
    Mosaic refused bf16 operands at fp32 precision on the chip, PR 26)."""
    from distributeddeeplearningspark_tpu.ops import indexed_attention as ia

    b, s = 1, 8192

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((b, s, 32, 128)), sds((b, s, 4, 128)), sds((b, s, 4, 128)),
            sds((b, s, 16, 64)), sds((b, s, 64)),
            sds((b, s, 16), jnp.float32))

    def scalar(*a):
        o, kl, _ = ia.indexed_attention(*a, topk=2048, interpret=False)
        return o.astype(jnp.float32).sum() + kl.mean()

    def compile_it():
        return jax.jit(jax.grad(scalar, argnums=tuple(range(6)))).lower(
            *args).compile()

    if precision:
        with jax.default_matmul_precision(precision):
            compiled = compile_it()
    else:
        compiled = compile_it()
    text = compiled.as_text()
    for name in ("dsa_index_fwd", "dsa_index_select", "dsa_index_bwd",
                 "dsa_attend_fwd", "dsa_attend_bwd_dq", "dsa_attend_bwd_dkv",
                 "dsa_kl_target"):
        assert name in text, name
    assert "[1,32,8192,8192]" not in text      # no [B, H, S, S] array
    assert " sort(" not in text                # the selection is no sort
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * 2 ** 30, temp


def test_routed_experts_split_over_four_chips_compile(four_chips,
                                                      no_compile_cache):
    """``models/moe.py`` on a ``data=2 x expert=2`` mesh of the described
    2x2: ``ragged_dot`` inside the ``shard_map``, the ``psum`` over ``expert``
    and the backward pass, at a Llama-sized layer. GSPMD could not partition
    the grouped products; the ``shard_map`` hands each chip its own."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributeddeeplearningspark_tpu.models.moe import RoutedExperts
    from distributeddeeplearningspark_tpu.ops import ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MESH_AXES

    shape = {a: 1 for a in MESH_AXES} | {"data": 2, "expert": 2}
    mesh = Mesh(np.array(four_chips).reshape(
        [shape[a] for a in MESH_AXES]), MESH_AXES)
    layer = RoutedExperts(2048, 5632, num_experts=8, top_k=2)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    x = sds((4, 2048, 2048), jnp.bfloat16, P(("data", "fsdp"), None, None))
    params = {
        "router": sds((2048, 8), jnp.float32, P()),
        "w_gate": sds((8, 2048, 5632), jnp.float32, P("expert", None, None)),
        "w_up": sds((8, 2048, 5632), jnp.float32, P("expert", None, None)),
        "w_down": sds((8, 5632, 2048), jnp.float32, P("expert", None, None)),
    }

    def scalar(p, x):
        y, stats = layer.apply({"params": p}, x)
        return y.astype(jnp.float32).sum() + stats["aux"]

    ring_attention.set_default_mesh(mesh)
    try:
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1))).lower(
            params, x).compile()
    finally:
        ring_attention.set_default_mesh(None)
    text = compiled.as_text()
    assert "ragged-dot" in text or "ragged_dot" in text
    assert " all-reduce(" in text or " all-reduce-start(" in text


# -- the flash kernel in every regime a model uses ---------------------------

#: name -> (batch, seq, q heads, kv heads, head size (or that of q and k, and
#: v's), causal, key mask, segment ids): Llama's causal d=128, BERT-base's
#: key-padding mask d=64, grouped KV, packed documents (mask + segment ids),
#: the hybrid decoder's causal + grouped 32/8 + segment ids at d=64, latent
#: attention's 192 / 128, and causal + segment ids at d=128 with 16 query
#: heads a key-value head
FLASH_REGIMES = {
    "causal_d128": (2, 1024, 4, 4, 128, True, False, False),
    "masked_d64_bert": (2, 512, 12, 12, 64, False, True, False),
    "gqa_causal_d128": (1, 1024, 8, 2, 128, True, False, False),
    "masked_segments_d64": (2, 1024, 12, 12, 64, False, True, True),
    "gqa_causal_segments_d64": (1, 2048, 32, 8, 64, True, False, True),
    # one window of the benchmark's fourth configuration (fit_seg32k)
    "cell_seg32k": (1, 32768, 32, 8, 64, True, False, True),
    # one window of the benchmark's fifth configuration (fit_s16k): q and k
    # 192 wide (no multiple of the 128 lanes), v 128
    "cell_mla_s16k": (1, 16384, 32, 32, (192, 128), True, False, False),
    # one window of the benchmark's sixth configuration (fit_seg16k): 32
    # query heads on 2 key-value heads of 128, packed documents
    "cell_seg16k_gqa16": (1, 16384, 32, 2, 128, True, False, True),
    # one window of the benchmark's seventh configuration (fit_swa16k): its
    # sliding layers (64 query heads on 8, the band of 512: a ninth field is
    # the window) and its full layers (48 on 8)
    "cell_swa16k": (1, 16384, 64, 8, 128, True, False, True, 512),
    "cell_swa16k_full": (1, 16384, 48, 8, 128, True, False, True),
}


def _flash_regime(regime, sharding=None):
    """``(scalar function of (q, k, v, *extra), its abstract arguments)``."""
    from distributeddeeplearningspark_tpu.ops.flash_attention import (
        flash_attention)

    b, s, h, hkv, d, causal, masked, segmented, *window = FLASH_REGIMES[
        regime]
    d_qk, d_v = d if isinstance(d, tuple) else (d, d)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = [sds((b, s, h, d_qk)), sds((b, s, hkv, d_qk)), sds((b, s, hkv, d_v))]
    args += (masked + segmented) * [sds((b, s), jnp.int32)]

    def scalar(q, k, v, *extra):
        o = flash_attention(
            q, k, v, causal=causal, interpret=False,
            mask=extra[0] if masked else None,
            segment_ids=extra[-1] if segmented else None,
            window=window[0] if window else None)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    return scalar, args


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "value_and_grad"])
@pytest.mark.parametrize("regime", list(FLASH_REGIMES))
def test_flash_kernel_compiles_in_every_regime(one_chip, no_compile_cache,
                                               regime, grad):
    """Mosaic accepts ``ops/flash_attention.py`` at model widths: the forward
    kernel alone, and with both backward kernels. The numbers these kernels
    give are held against ``_xla_attention`` in interpret mode by
    ``tests/test_flash_attention.py``; here only the chip's compiler speaks."""
    scalar, args = _flash_regime(regime, one_chip)
    fn = jax.value_and_grad(scalar, argnums=(0, 1, 2)) if grad else scalar
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = (("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv") if grad
               else ("flash_fwd",))
    if len(FLASH_REGIMES[regime]) > 8:      # the band's calls
        kernels = tuple(k.replace("flash_", "flash_window_") for k in kernels)
    for name in kernels:
        assert name in text, name
    assert text.count("tpu_custom_call") >= len(kernels)


@pytest.mark.parametrize("regime", list(FLASH_REGIMES))
def test_flash_bounds_operands_follow_the_segment_ids(regime):
    """The two scalar-prefetch tables of ``segment_block_walk`` go to the
    three kernels with segment ids and ONLY then: without them (BERT's regime
    among these) the calls are built as before the kernels could skip, same
    grid, same operands, nothing prefetched. (Lowered for the described v5e,
    the regimes without segment ids give the parent's StableHLO and Mosaic
    bodies to the byte, locations stripped: PERF.md section 6, PR 31.)"""
    from distributeddeeplearningspark_tpu.ops.flash_attention import (
        DEFAULT_BLOCK)

    b, s, h, hkv, _, _, masked, segmented, *window = FLASH_REGIMES[regime]
    scalar, args = _flash_regime(regime)

    calls = {}
    for eqn in eqns(jax.make_jaxpr(jax.grad(scalar, argnums=(0, 1, 2)))(
            *args).jaxpr, "pallas_call"):
        grid_mapping = eqn.params["grid_mapping"]
        calls[eqn.params["name"]] = (
            len(eqn.invars), grid_mapping.num_index_operands,
            grid_mapping.grid)
    tables = 2 * segmented
    extra = masked + 2 * segmented + tables
    block = min(DEFAULT_BLOCK, s)
    n = s // block
    # a band's inner axis spans the blocks it can meet, ceil((w - 1) / block)
    # + 1 of them, and the calls carry the band's names
    span = -(-(window[0] - 1) // block) + 1 if window else n
    name = "flash_window_" if window else "flash_"
    assert calls == {
        name + "fwd": (3 + extra, tables, (b * h, n, span)),
        name + "bwd_dq": (6 + extra, tables, (b * h, n, span)),
        name + "bwd_dkv": (6 + extra, tables, (b * hkv, n, h // hkv * span)),
    }


def test_short_convolution_kernels_compile_at_published_widths(
        one_chip, no_compile_cache):
    """``shortconv_fwd`` and ``shortconv_bwd`` of ``ops/short_conv.py`` at one
    window of the benchmark's fourth configuration: 32,768 positions, 2,048
    channels behind a 6,144-wide projection, three taps, segment ids. The
    sublane rotation, the three column blocks of one array and the 8-row
    halos are what interpret mode cannot judge."""
    from distributeddeeplearningspark_tpu.ops import short_conv

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scalar(bcx, taps, seg):
        y = short_conv.gated_short_conv_pallas(bcx, taps, seg,
                                               interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1))).lower(
        sds((1, 32768, 6144)), sds((2048, 3), jnp.float32),
        sds((1, 32768), jnp.int32)).compile()
    text = compiled.as_text()
    for name in ("shortconv_fwd", "shortconv_bwd"):
        assert name in text, name
    assert text.count("tpu_custom_call") >= 2
    # one pass each: no [T, 6144] temporary beside the cotangent itself
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_flash_on_mesh_compiles_for_four_chips(four_chips, no_compile_cache,
                                               routed_as_on_the_chip):
    """``ops/attention._flash_on_mesh`` at BERT-base's widths over ``data=4``
    of the described 2x2: the kernel inside its ``shard_map`` (GSPMD refuses
    to partition a Mosaic call), forward and backward, and no collective,
    since attention mixes neither batch rows nor heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributeddeeplearningspark_tpu.ops import attention, ring_attention
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(data=4).build(four_chips)
    rows = NamedSharding(mesh, P(("data", "fsdp")))
    qkv = jax.ShapeDtypeStruct((128, 512, 12, 64), jnp.bfloat16,
                               sharding=rows)
    mask = jax.ShapeDtypeStruct((128, 512), jnp.int32, sharding=rows)

    def scalar(q, k, v, m):
        o = attention._flash_on_mesh(q, k, v, bias=None, mask=m, causal=False,
                                     scale=None, segment_ids=None)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    ring_attention.set_default_mesh(mesh)
    try:
        compiled = jax.jit(jax.grad(scalar, argnums=(0, 1, 2))).lower(
            qkv, qkv, qkv, mask).compile()
    finally:
        ring_attention.set_default_mesh(None)
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name
    assert "[32,12,512,64]" in text or "[384,512,64]" in text  # a chip's rows
    assert " all-reduce(" not in text and " all-gather(" not in text


def test_llama_09b_lora_step_fits_one_chip_as_budgeted(four_chips,
                                                       no_compile_cache,
                                                       routed_as_on_the_chip):
    """The 0.9b LoRA train step (hidden 2048 x 16 layers x vocabulary 32k,
    bf16 base, rank 16, four sequences of 2,048) compiled for ONE described
    chip: the compiler's live bytes against ``utils/memory.py``'s analytic
    budget, within the 10% window ``tests/test_memory.py`` states for it."""
    import optax

    from distributeddeeplearningspark_tpu.models import (
        LlamaConfig, LlamaForCausalLM, llama_rules, lora_trainable)
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.train import (
        losses, optim, step as step_lib)
    from distributeddeeplearningspark_tpu.utils.memory import (
        GiB, llama_memory_report)

    b, s = 4, 2048
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, num_layers=16, num_heads=16,
        num_kv_heads=8, intermediate_size=5632, max_position=s, lora_rank=16,
        dtype="bfloat16", param_dtype="bfloat16", remat_policy="dots")
    model = LlamaForCausalLM(cfg)
    mesh = MeshSpec(data=1).build(four_chips[:1])
    tx = optim.masked(optax.adamw(1e-4), lora_trainable)

    def init_fn(rng):
        variables = dict(model.init(
            {"params": rng, "dropout": rng},
            {"input_ids": jnp.zeros((b, s), jnp.int32)}, train=False))
        params = variables.pop("params")
        return step_lib.TrainState.create(
            params=params, opt_state=tx.init(params), mutable=variables,
            rng=rng, embed_state={})

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    shardings = step_lib.state_shardings(abstract, mesh, llama_rules(cfg))
    step = step_lib.jit_train_step(
        step_lib.make_train_step(model.apply, tx, losses.causal_lm,
                                 trainable=lora_trainable),
        mesh, shardings)
    batch = {"input_ids": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((b, s), jnp.float32)}
    compiled = step.lower(abstract, batch).compile()
    # the program the chip runs: its router sends s=2048 to the flash kernel
    assert "flash_fwd" in compiled.as_text()
    ma = compiled.memory_analysis()
    # donation aliases the state into the outputs: live bytes are the larger
    # of the two plus the temporaries, not their sum
    live = (max(ma.argument_size_in_bytes, ma.output_size_in_bytes)
            + ma.temp_size_in_bytes)
    budget = llama_memory_report(cfg, batch=b, seq=s, mesh_shape={})
    assert budget.fits(16 * GiB), budget.to_dict()
    assert abs(budget.total_bytes - live) / live < 0.10, (
        budget.to_dict(), live / GiB)


# -- what the accepted cells run lowers to the programs it lowered to ---------

def _without_locations(text: str) -> str:
    """A lowered module's StableHLO with every Mosaic kernel's body decoded
    from its bytecode to MLIR text, and every source location dropped from
    both: what is left changes only if the PROGRAM does (a docstring that
    moves a kernel's lines does not)."""
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    bodies = []

    def decoded(match):
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True   # the versioned dialect
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
        return f'\\22body\\22: \\22<body {len(bodies) - 1}>\\22'

    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', decoded, text)
    text = re.sub(r" loc\([^\n]*\)$", "", text, flags=re.M)
    text = re.sub(r"^#loc.*$", "", text, flags=re.M)
    return text + "\n".join(bodies)


def _digest(lowered) -> str:
    import hashlib

    return hashlib.sha256(
        _without_locations(lowered.as_text()).encode()).hexdigest()[:16]


#: sha256 (first 16 hex digits) of what each regime's value_and_grad lowered
#: to, for the described v5e, computed by this file's own functions: the two
#: regimes with a key mask (both BERT cells run ``masked_d64_bert``) in the
#: tree of PR 31 (commit 3c1af97), and they have not moved since; the causal
#: regimes without one in the tree of PR 33, which meant to change them (the
#: index maps clamp above the diagonal, dQ and dK/dV carry a second body).
#: A PR that changes a kernel on purpose replaces the digests of the regimes
#: it meant to change, and says so; one that did not mean to has changed a
#: program an accepted cell runs.
PARENT_FLASH_LOWERINGS = {
    "causal_d128": "3f85d2a655fc82de",
    "masked_d64_bert": "aa842b73d3a24dc8",
    "gqa_causal_d128": "74fd2eb0428c2d07",
    "masked_segments_d64": "de6843e027485bb3",
    "gqa_causal_segments_d64": "252ea21bfb3e9ecc",
    "cell_seg32k": "088695e3219ba773",
    "cell_mla_s16k": "c68d4331ee8a8f3a",
    # taken in the tree of PR 38, the parent of the band (window=None):
    # fit_seg16k's regime, and the regime fit_swa16k's full layers run
    "cell_seg16k_gqa16": "ceadac247c19d353",
    "cell_swa16k_full": "285d38884e87dfd2",
}
#: the same of ``lfm2_24b_a2b.fit_seg32k``'s whole train step (the
#: configuration's model, loss and optimizer at the cell's window), in the
#: tree of PR 33 (its kernels, and one more counter among the outputs)
PARENT_LFM2_STEP_LOWERING = "7e32b460313c7c25"


@pytest.mark.parametrize("regime", list(PARENT_FLASH_LOWERINGS))
def test_flash_regimes_of_the_accepted_cells_lower_as_in_the_parent(
        one_chip, no_compile_cache, regime):
    """Every regime lowers to the program its digest was taken from: same
    StableHLO around the three ``pallas_call``s, same Mosaic bodies,
    locations stripped. The regimes with a key mask hold the program both
    BERT cells ran before the kernels classed their steps (PR 33)."""
    scalar, args = _flash_regime(regime, one_chip)
    lowered = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2))).lower(
        *args)
    assert _digest(lowered) == PARENT_FLASH_LOWERINGS[regime]


def _lowered_step_of(name, traffic_name, devices, segment_ids):
    """The train step of a benchmark configuration at its cell's window,
    lowered for ONE described chip (state and batch abstract)."""
    import json
    import os

    from benchmark.harness import runner
    from distributeddeeplearningspark_tpu.parallel.mesh import MeshSpec
    from distributeddeeplearningspark_tpu.parallel.sharding import (
        ShardingRules)
    from distributeddeeplearningspark_tpu.train import step as step_lib

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    mod = runner.load_module(os.path.join(root, "configs", name + ".py"))
    with open(os.path.join(root, "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    built = mod.build(cfg, traffic)
    model, tx = built["model"], built["tx"]
    b, s = traffic["per_chip_batch"], traffic["seq_len"]
    batch = {"input_ids": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if segment_ids:
        batch["segment_ids"] = jax.ShapeDtypeStruct((b, s), jnp.int32)

    def init_fn(rng):
        variables = dict(model.init(
            {"params": rng}, {k: jnp.zeros(v.shape, v.dtype)
                              for k, v in batch.items()}, train=False))
        params = variables.pop("params")
        return step_lib.TrainState.create(
            params=params, opt_state=tx.init(params), mutable=variables,
            rng=rng, embed_state={})

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    mesh = MeshSpec(data=1).build(devices[:1])
    step = step_lib.jit_train_step(
        step_lib.make_train_step(model.apply, tx, built["loss"]), mesh,
        step_lib.state_shardings(abstract, mesh, ShardingRules()))
    return step.lower(abstract, batch)


def test_the_lfm2_train_step_lowers_as_in_the_parent(
        four_chips, no_compile_cache, routed_as_on_the_chip, monkeypatch):
    """The step of ``fit_seg32k`` (flash and short-convolution kernels, the
    experts, the fused head loss, AdamW) lowers to the program its digest
    was taken from: a change to ``models/hybrid_decoder.py``, ``models/moe.py``
    or an op that did not mean to reach this cell has not."""
    from distributeddeeplearningspark_tpu.ops import short_conv

    monkeypatch.setattr(short_conv, "on_tpu", lambda: True)
    lowered = _lowered_step_of("lfm2_24b_a2b", "fit_seg32k", four_chips, True)
    text = lowered.as_text()
    for name in ("flash_fwd", "flash_bwd_dkv", "shortconv_fwd",
                 "shortconv_bwd"):
        assert name in text, name
    assert _digest(lowered) == PARENT_LFM2_STEP_LOWERING


def test_the_joyai_train_step_compiles_for_one_chip_with_room_to_spare(
        four_chips, no_compile_cache, routed_as_on_the_chip):
    """``joyai_llm_flash.fit_s16k``'s whole step for ONE described chip: the
    three flash kernels at 192 / 128 in it, 491.7M parameters' state and the
    step's temporaries inside the chip's 16 GiB."""
    compiled = _lowered_step_of("joyai_llm_flash", "fit_s16k", four_chips,
                                False).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name
    assert "[1,32,16384,16384]" not in text      # no [B, H, S, S] array
    ma = compiled.memory_analysis()
    live = (max(ma.argument_size_in_bytes, ma.output_size_in_bytes)
            + ma.temp_size_in_bytes)
    assert 0.25 * 16 * 2 ** 30 < live < 15 * 2 ** 30, live / 2 ** 30


def test_the_nemotron_train_step_compiles_for_one_chip_with_room_for_the_check(
        four_chips, no_compile_cache, routed_as_on_the_chip):
    """``nemotron3_nano_30b_a3b.fit_seg16k``'s whole step for ONE described
    chip: the flash kernels causal with segment ids at 32 / 2 heads of 128
    in it, the chunked scan as three ``while`` loops a layer whose carried
    tuple begins with the state (what ``ssd_ms_per_step`` finds), no array
    of all the chunks' masks, and 528.1M parameters' state and the step's
    temporaries inside what the check after the window leaves of 16 GiB (it
    holds 24 bytes a parameter where the step's arguments are 12)."""
    compiled = _lowered_step_of("nemotron3_nano_30b_a3b", "fit_seg16k",
                                four_chips, True).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text, name
    assert "[1,32,16384,16384]" not in text      # no [B, H, S, S] array
    assert "128,8,8,128,128]" not in text        # nor every chunk's masks
    assert "f32[1,8,8,64,128]" in text           # the state a chunk hands on
    # the running sum of dt A is a product, not a reduce-window over the
    # chunk's positions with the 8 x 8 heads minor
    assert not re.search(r"f32\[1,8,128,8,8\]\S* reduce-window\(", text)
    ma = compiled.memory_analysis()
    live = (max(ma.argument_size_in_bytes, ma.output_size_in_bytes)
            + ma.temp_size_in_bytes)
    assert 0.25 * 16 * 2 ** 30 < live < 13.5 * 2 ** 30, live / 2 ** 30


def test_the_laguna_train_step_compiles_for_one_chip_with_room_for_the_check(
        four_chips, no_compile_cache, routed_as_on_the_chip):
    """``laguna_xs2.fit_swa16k``'s whole step for ONE described chip: the
    band kernels (64 / 8 heads of 128, the window of 512) beside the causal
    ones (48 / 8) with segment ids, no array of a layer's scores, and 490.3M
    parameters' state and the step's temporaries inside what the check after
    the window leaves of 16 GiB."""
    compiled = _lowered_step_of("laguna_xs2", "fit_swa16k", four_chips,
                                True).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "flash_window_fwd", "flash_window_bwd_dq",
                 "flash_window_bwd_dkv"):
        assert name in text, name
    assert ",16384,16384]" not in text           # no [.., S, S] array
    ma = compiled.memory_analysis()
    live = (max(ma.argument_size_in_bytes, ma.output_size_in_bytes)
            + ma.temp_size_in_bytes)
    # (10.5 GiB here: 5.5 of state, 5.0 of temporaries)
    assert 0.25 * 16 * 2 ** 30 < live < 12 * 2 ** 30, live / 2 ** 30
