"""What the v5e's own compiler says about the kernels, asked without a chip.

The TPU compiler is installed in the sandbox and compiles for a chip that is
DESCRIBED, not attached (``v5e:2x2``). Interpret mode cannot see what this
sees: block shapes Mosaic refuses, ops it cannot lower, VMEM it does not
have. Nothing runs here — results and times come from ``chip_smoke.py`` on
the machine with the chip; a compile that passes is not a chip run.

This is the ONE file that describes a topology, and it does so inside a
module-scoped fixture (never at import, in a ``skipif`` or in ``parametrize``
arguments): only one process at a time may load the TPU library, so the
worker that is handed this file loads it and every other worker never does.
All compiles happen in the test's own process.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from distributed_llama_tpu.ops import attention as att
from distributed_llama_tpu.ops import collectives, q40


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the persistent cache but can
    # never be read back without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("tp",))


def _qm_shape(n: int, d: int, sharding) -> q40.QuantizedMatrix:
    np_, dp = q40._n_padded(n), q40._d_padded(d)
    return q40.QuantizedMatrix(
        jax.ShapeDtypeStruct((np_ // 2, dp), jnp.uint8, sharding=sharding),
        jax.ShapeDtypeStruct((np_ // 32, dp), jnp.float32, sharding=sharding),
        n, d,
    )


# Llama-2-7B's five matmuls (fused qkv, wo, fused gate|up, down, wcls) and
# Mixtral-8x7B's two expert widths
SHAPES_7B = [(4096, 12288), (4096, 4096), (4096, 22016), (11008, 4096), (4096, 32000)]
SHAPES_MIXTRAL = [(4096, 28672), (14336, 4096)]


def _compile_q40(kernel, n, d, T, one_chip):
    qm = _qm_shape(n, d, one_chip)
    x = jax.ShapeDtypeStruct((T, n), jnp.bfloat16, sharding=one_chip)
    bn, bd = q40._resolve_tiles(qm, T, q40.BLOCK_N, q40.BLOCK_D)
    compiled = kernel.lower(x, qm, block_n=bn, block_d=bd, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [1, 64])
@pytest.mark.parametrize("n,d", SHAPES_7B + SHAPES_MIXTRAL)
def test_q40_int8_kernel_compiles(one_chip, n, d, T):
    """The default q40 path (CPU tests and chip alike)."""
    assert q40.default_q40_path() == "int8"
    _compile_q40(q40._q40_matmul_int8, n, d, T, one_chip)


@pytest.mark.parametrize("n,d,T", [(4096, 32000, 256), (4096, 22016, 256), (11008, 4096, 512)])
def test_q40_default_dispatch_compiles_at_prefill_widths(one_chip, n, d, T, monkeypatch):
    """The server prefills in 256-row chunks. At these widths the decode
    tiles overflow VMEM in the int8 kernel (the chip said so: "Ran out of
    memory in memory space vmem", chip_smoke, PR 21); the dispatch must
    shrink them (``_fit_int8_tiles``) and stay on the kernel."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)  # steer the CPU branch
    qm = _qm_shape(n, d, one_chip)
    x = jax.ShapeDtypeStruct((T, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, qm: q40.q40_matmul(x, qm)).lower(x, qm).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [1, 64])
@pytest.mark.parametrize("n,d", [(4096, 12288), (11008, 4096)])
def test_q40_f32_kernel_compiles(one_chip, n, d, T):
    """The ``DLT_Q40_INT8=0`` arm."""
    _compile_q40(q40._q40_matmul_f32, n, d, T, one_chip)


def _paged_decode_args(one_chip, K: int, M: int):
    B, S, page, hd = 4, 2048, 64, 128

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kv = s((B, S, K, hd), jnp.bfloat16)
    pool = s((160, page, K, hd), jnp.bfloat16)
    return dict(
        qg=s((B, K, M, hd), jnp.float32), keys=kv, values=kv,
        pos=s((B,), jnp.int32), pool_k=pool, pool_v=pool,
        tables=s((B, S // page), jnp.int32), matched=s((B,), jnp.int32),
    )


@pytest.mark.parametrize("K,M", [(32, 1), (8, 4)])
def test_paged_decode_attention_scan_compiles(one_chip, K, M):
    """The chip's paged decode attention: the segmented XLA scan."""

    def f(qg, keys, values, pos, pool_k, pool_v, tables, matched):
        return att.batched_decode_attention(
            qg, keys, values, pos, 512, paged=(pool_k, pool_v, tables, matched)
        )

    jax.jit(f).lower(**_paged_decode_args(one_chip, K, M)).compile()


@pytest.mark.xfail(
    strict=True,
    reason="v5e Mosaic refuses the shared per-chunk einsums (batch dims B and "
    "K): \"'tpu.matmul' op Not implemented: Up to 1 batch dim supported\"",
)
@pytest.mark.parametrize("K,M", [(32, 1), (8, 4)])
def test_fused_paged_attention_kernel_compiles(one_chip, K, M):
    """``DLT_FUSED_PAGED=1``: stays behind its switch until this passes."""

    def f(qg, keys, values, pos, pool_k, pool_v, tables, matched):
        return att.fused_paged_decode_attention(
            qg, keys, values, pos, 512, (pool_k, pool_v, tables, matched),
            interpret=False,
        )

    jax.jit(f).lower(**_paged_decode_args(one_chip, K, M)).compile()


@pytest.mark.parametrize(
    "impl,marker",
    [("psum", "all-reduce"), ("ring_xla", "collective-permute"), ("ring", "tpu_custom_call")],
)
def test_all_reduce_arms_compile_tp4(mesh4, impl, marker):
    """Every arm of the seam on the described 2x2 mesh; ``marker`` is what
    the arm leaves in the compiled text (psum is the default everywhere)."""
    x = jax.ShapeDtypeStruct((8, 4096), jnp.float32, sharding=NamedSharding(mesh4, P()))
    f = jax.jit(jax.shard_map(
        lambda y: collectives.all_reduce(y, "tp", impl=impl),
        mesh=mesh4, in_specs=P(None, None), out_specs=P(None, None), check_vma=False,
    ))
    assert marker in f.lower(x).compile().as_text()


def test_fused_matmul_ring_kernel_compiles_tp4(mesh4):
    """The fused int8-matmul + ring kernel at Llama-2-7B's wo shard under
    tp=4 (n = 4096/4). Compiles; has not run on a chip (ROADMAP Speed 8)."""
    n, d = 1024, 4096
    sh = NamedSharding(mesh4, P("tp"))
    np_, dp = q40._n_padded(n), q40._d_padded(d)
    qm = q40.QuantizedMatrix(
        jax.ShapeDtypeStruct((4, np_ // 2, dp), jnp.uint8, sharding=sh),
        jax.ShapeDtypeStruct((4, np_ // 32, dp), jnp.float32, sharding=sh),
        n, d,
    )
    xs = jax.ShapeDtypeStruct((4, 1, n), jnp.bfloat16, sharding=sh)

    def f(x, qm_):
        qm0 = jax.tree.map(lambda a: a[0], qm_)
        assert collectives._fused_ring_eligible(x[0], qm0, 4)
        return collectives.fused_matmul_ring_all_reduce(x[0], qm0, "tp", 4)

    compiled = jax.jit(jax.shard_map(
        f, mesh=mesh4, in_specs=(P("tp"), P("tp")), out_specs=P(None, None),
        check_vma=False,
    )).lower(xs, qm).compile()
    assert "tpu_custom_call" in compiled.as_text()
