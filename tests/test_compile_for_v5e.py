"""Kernels of the main path compiled at real widths for a DESCRIBED TPU v5e
(no chip attached: the TPU's compiler is installed here). What interpret mode
cannot show: Mosaic's tiling and VMEM limits, and what the compiler refuses.
A compile that passes is not a chip run and yields no number.

All such tests live in THIS file, and the topology is described inside a
fixture: only one process may load the TPU's library, the driver runs the
suite with several workers, and a module that touched it at import time
would fail in every worker but one (on-chip-measurement guide, section 2).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


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
