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

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from distributed_llama_tpu.formats.model_file import ArchType
from distributed_llama_tpu.models import llama, sampling
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.ops import attention as att
from distributed_llama_tpu.ops import collectives, decode_attention, q40


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
# Solar-Open2's widest dense launch (lin_in, 4096 -> 24896, padded to 25 x 1024 columns)
SHAPES_SOLAR = [(4096, 25600)]


def _compile_q40(kernel, n, d, T, one_chip):
    qm = _qm_shape(n, d, one_chip)
    x = jax.ShapeDtypeStruct((T, n), jnp.bfloat16, sharding=one_chip)
    bn, bd = q40._int8_tiles(qm, T, q40.BLOCK_N, q40.BLOCK_D)
    compiled = kernel.lower(x, qm, block_n=bn, block_d=bd, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [1, 16, 32, 64, 256, 640])
@pytest.mark.parametrize("n,d", SHAPES_7B + SHAPES_MIXTRAL + SHAPES_SOLAR)
def test_q40_int8_kernel_compiles(one_chip, n, d, T):
    """The one tiled q40 kernel (CPU tests and chip alike), at the row counts
    the cells decode at (1; Mixtral's 16; Solar's 32) and at the last row
    count of each further band of ``q40._BLOCK_D_BY_ROWS`` (64; the 256-row
    prefill chunk; 640, the most the compiler accepts at every width)."""
    _compile_q40(q40._q40_matmul_int8, n, d, T, one_chip)


@pytest.mark.parametrize("n,d,T", [(4096, 32000, 256), (4096, 22016, 256), (11008, 4096, 512)])
def test_q40_default_dispatch_compiles_at_prefill_widths(one_chip, n, d, T, monkeypatch):
    """The server prefills in 256-row chunks. At these widths the decode
    tiles overflow VMEM in the int8 kernel (the chip said so: "Ran out of
    memory in memory space vmem", chip_smoke, PR 21); the dispatch must
    shrink them (``_BLOCK_D_BY_ROWS``) and stay on the kernel."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)  # steer the CPU branch
    qm = _qm_shape(n, d, one_chip)
    x = jax.ShapeDtypeStruct((T, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, qm: q40.q40_matmul(x, qm)).lower(x, qm).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [1024, 2049])
def test_q40_dispatch_past_the_vmem_fit_compiles_as_plain_xla(one_chip, monkeypatch, T):
    """The int8 kernel holds all T rows in one block and the v5e compiler
    refuses it from 1024 rows whatever the tile (and refused a
    float-dequantising kernel there too, PR 30): the dispatch hands such a T
    to the XLA fallback, which compiles."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    qm = _qm_shape(4096, 12288, one_chip)
    x = jax.ShapeDtypeStruct((T, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, qm: q40.q40_matmul(x, qm)).lower(x, qm).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("T", [128, 64, 32])
@pytest.mark.parametrize("n,d", SHAPES_MIXTRAL)
def test_q40_experts_launch_compiles_at_a_buckets_rows(one_chip, monkeypatch, n, d, T):
    """One expert over its bucket of a prompt piece (``moe._moe_bucketed``):
    half the rows of a piece of 256, 128 or 64, through the default dispatch
    under the role the trace reads it by."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)  # steer the CPU branch
    qm = _qm_shape(n, d, one_chip)
    x = jax.ShapeDtypeStruct((T, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, qm: q40.q40_matmul(x, qm, role="experts")).lower(x, qm).compile()
    assert "q40_int8_experts" in compiled.as_text()


# Solar-Open2's shapes: 20 held experts of width 1280 over a hidden size of 4096 (gate|up
# 4096 -> 2560, down 1280 -> 4096), 64 linear-attention heads of 128
@pytest.mark.parametrize("rows", [32, 256])
@pytest.mark.parametrize("n,d,shared", [(4096, 2560, True), (1280, 4096, False)])
def test_q40_grouped_kernel_compiles_over_a_bank_of_held_experts(one_chip, monkeypatch, n, d, shared, rows):
    """One launch over the stacked bank, at a decode bucket and at a prefill
    chunk (whose block sums need more than the compiler's default 16 MiB of
    scoped VMEM: the launch asks for 32)."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    one = _qm_shape(n, d, one_chip)
    E = 20
    bank = q40.QuantizedMatrix(
        jax.ShapeDtypeStruct((E,) + one.qs.shape, jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((E,) + one.scales.shape, jnp.float32, sharding=one_chip), n, d)
    x = jax.ShapeDtypeStruct(((rows, n) if shared else (E, rows, n)), jnp.float32, sharding=one_chip)
    on = jax.ShapeDtypeStruct((E,), jnp.bool_, sharding=one_chip)
    compiled = q40.q40_grouped_matmul.lower(x, bank, on, role=f"held_experts_t{rows}").compile()
    assert f"q40_int8_grouped_held_experts_t{rows}" in compiled.as_text()


@pytest.mark.parametrize("rows", [32, 64, 128, 256])
def test_q40_grouped_kernel_compiles_at_1536_columns_of_a_4096_deep_bank(one_chip, monkeypatch, rows):
    """Granite-4.0-H-Small's bank of 18 held experts' gate|up, 4096 -> 1536,
    whose pack keeps its 1536 columns (``q40._d_padded``; 2048 until PR 51):
    the compiler accepts one tile of 1536 columns at the 32 rows of a decode
    bucket and of a row tile of a larger one (64, 128, 256: the launch walks
    their tiles of 32 since PR 54), inside the 32 MiB of scoped
    VMEM the launch states, and the launch's name and result are what the
    roofline's reader matches (``benchmark/layer_metrics/q40_held_experts_roofline.json``)."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    n, d, E = 4096, 1536, 18
    one = _qm_shape(n, d, one_chip)
    assert one.qs.shape == (2048, 1536) and one.scales.shape == (128, 1536)
    assert q40._int8_tiles(one, rows, q40.BLOCK_N, q40.BLOCK_D) == (1024, 1536 if rows <= 64 else 768)
    bank = q40.QuantizedMatrix(
        jax.ShapeDtypeStruct((E,) + one.qs.shape, jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((E,) + one.scales.shape, jnp.float32, sharding=one_chip), n, d)
    x = jax.ShapeDtypeStruct((E, rows, n), jnp.float32, sharding=one_chip)
    on = jax.ShapeDtypeStruct((E,), jnp.bool_, sharding=one_chip)
    role = f"held_experts_t{2 * rows}"  # a bucket is at most half its step's rows
    text = q40.q40_grouped_matmul.lower(x, bank, on, role=role).compile().as_text()
    launch = re.search(rf"%q40_int8_grouped_{role}[.\d]* = (f32\[[\d,]+\])\S* custom-call\(", text)
    assert launch, [line for line in text.splitlines() if "custom-call" in line][:4]
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
                           "q40_held_experts_roofline.json")) as f:
        pattern = json.load(f)["reader"]["ops"]
    assert launch.group(1) == f"f32[{E},{rows},1536]"
    assert re.match(pattern, f"q40_int8_grouped_{role} {launch.group(1)}")


# a 256-row piece's buckets in row tiles (PR 54): Granite-4.0-H-Small's two banks (18 held, bucket 128; a
# 65-128-row piece's 64), GLM-4.7-Flash's (64 held, buckets of 64 and of 128), and the bucket of 64 rows
# behind a first one of 32 (one tile, the launch as it was): GLM-5's banks (16 held) and Solar-Open2's (20)
@pytest.mark.parametrize("E,bucket,n,d", [
    (18, 128, 4096, 1536), (18, 128, 768, 4096), (18, 64, 4096, 1536),
    (64, 64, 2048, 3072), (64, 128, 2048, 3072), (64, 128, 1536, 2048),
    (16, 64, 6144, 4096), (16, 64, 2048, 6144), (20, 64, 4096, 2560), (20, 64, 1280, 4096)])
def test_q40_grouped_kernel_compiles_in_row_tiles_under_the_buckets_name_and_shape(one_chip, monkeypatch, E, bucket, n, d):
    """The grouped launch of buckets of more than 32 rows walks (expert, row
    tile) pairs, ``E * bucket / 32`` of them, each at the 32-row class's
    output tile (one tile of up to 4096 columns), and the v5e compiler
    accepts it inside the 32 MiB of scoped VMEM the launch states. Its
    result keeps the bucket's shape ``[experts, bucket, columns]`` under the
    launch's name, which is what the five ``q40_held_experts_roofline*``
    readers match and take ``experts`` and ``rows`` from."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    one = _qm_shape(n, d, one_chip)
    block_n, block_d = q40._int8_tiles(one, q40.GROUPED_ROW_TILE, q40.BLOCK_N, q40.BLOCK_D)
    assert q40.grouped_row_tile(bucket) == q40.GROUPED_ROW_TILE == 32
    # a row tile's output tile is the 32-row class's: no narrower than the whole bucket's was (ROADMAP Speed 3(b2))
    assert block_d == min(one.d_padded, 4096 if one.d_padded % 4096 == 0 else 3072) >= q40._int8_tiles(one, bucket, q40.BLOCK_N, q40.BLOCK_D)[1]
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bank = q40.QuantizedMatrix(s((E,) + one.qs.shape, jnp.uint8), s((E,) + one.scales.shape, jnp.float32), n, d)
    args = (s((E, bucket, n), jnp.float32), bank, s((E,), jnp.int32))
    role = "held_experts_t256"
    grid = (E * bucket // 32, one.d_padded // block_d, one.n_padded // block_n)
    assert f"grid={grid}" in str(jax.make_jaxpr(lambda *a: q40.q40_grouped_matmul(*a, role=role))(*args))
    text = q40.q40_grouped_matmul.lower(*args, role=role).compile().as_text()
    launch = re.search(rf"%q40_int8_grouped_{role}[.\d]* = (f32\[[\d,]+\])\S* custom-call\(", text)
    assert launch, [line for line in text.splitlines() if "custom-call" in line][:4]
    assert launch.group(1) == f"f32[{E},{bucket},{one.d_padded}]"
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
                           "q40_held_experts_roofline.json")) as f:  # the five entries' one reader
        assert re.match(json.load(f)["reader"]["ops"], f"q40_int8_grouped_{role} {launch.group(1)}")


@pytest.mark.parametrize("kernel,tokens", [("kda_step", 32), ("kda_chunk", 256), ("kda_chunk", 8)])
def test_the_gated_delta_rule_kernels_compile(one_chip, monkeypatch, kernel, tokens):
    """The decode step over 32 rows of a 32-row slab (the state aliased in
    place) and one row's prefill chunk, at 64 heads of 128."""
    from distributed_llama_tpu.ops import kda

    monkeypatch.setattr(kda, "_interpret_default", lambda: False)
    H, d = 64, 128
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    vec, beta = s(tokens, H, d), s(tokens, H)
    if kernel == "kda_step":
        active = jax.ShapeDtypeStruct((tokens,), jnp.bool_, sharding=one_chip)
        lowered = jax.jit(kda.kda_step, donate_argnums=(0,)).lower(
            s(tokens, H, d, d), vec, vec, vec, vec, beta, active)
    else:
        lowered = jax.jit(kda.kda_chunk).lower(s(H, d, d), vec, vec, vec, vec, beta)
    text = lowered.compile().as_text()
    assert f"%{kernel}" in text and "tpu_custom_call" in text


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
            qg, (keys, values), pos, 512, paged=(pool_k, pool_v, tables, matched)
        )

    jax.jit(f).lower(**_paged_decode_args(one_chip, K, M)).compile()


# the cells' slabs: (leaf, query heads a kv head, rows of the bucket)
DECODE_SCAN_SHAPES = {
    "long_doc": ((2, 8, 8192, 8, 128), 4, 8),
    "evabyte": ((2, 8, 3072, 32, 128), 1, 8),
    "single_stream": ((2, 16, 2048, 8, 128), 4, 1),
    "rows16": ((2, 16, 2048, 8, 128), 4, 16),
    "solar_rows32": ((2, 32, 2048, 8, 128), 8, 32),
    "k_exaone": ((2, 8, 16384, 8, 128), 8, 8),
}


@pytest.mark.parametrize("cell", sorted(DECODE_SCAN_SHAPES))
def test_row_bounded_decode_scan_compiles(one_chip, monkeypatch, cell):
    """``decode_attention.slab_decode_scan`` at the cells' slab shapes: one
    Mosaic kernel that takes the leaf as stored (no half of it, no copy of
    it: nothing of the leaf's size in the program but its parameter). A
    bucket of one row over 2048 slots is served by the XLA loop
    (``att.ONE_ROW_LOOP_SLOTS``: the chip's pair); the kernel is held to
    compiling at that shape all the same, the threshold may move."""
    monkeypatch.setattr(decode_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(att, "ONE_ROW_LOOP_SLOTS", 0)
    leaf, M, rows = DECODE_SCAN_SHAPES[cell]
    _, _, slots, K, hd = leaf

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def f(qg, cache, pos):
        if cell == "evabyte":
            return att.eva_batched_decode_attention(qg, cache, pos, 2048, 16, 512)
        return att.batched_decode_attention(qg, cache, pos, 512)

    compiled = jax.jit(f).lower(
        s((rows, K, M, hd), jnp.float32), s(leaf, jnp.bfloat16), s((rows,), jnp.int32)
    ).compile()
    hlo = compiled.as_text()
    assert "slab_decode_scan" in hlo and "tpu_custom_call" in hlo
    writes, others = _slab_sized_results(hlo, int(np.prod(leaf)) // 2)
    assert not writes and not others, writes + others


# ---------------------------------------------------------------------------
# The served batched programs at the benchmark cells' shapes (Mistral-7B
# widths, 2 of its layers, the 16-row x 2048 bf16 slab, the 384-page pool):
# between a step's cache write and its attention reads nothing of the slab's
# size may form. At PR 23 a slice of both halves and two copies of them, per
# layer per step, were two thirds of a decode step on the chip (PERF.md §5).
# ---------------------------------------------------------------------------

SERVED_LAYERS, SERVED_ROWS, SERVED_PAGES, SERVED_PAGE = 2, 16, 384, 64
# ops whose result is their operand's buffer (or no buffer at all)
_NO_BUFFER_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "while", "conditional"}
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$")


def _served_program_shapes(one_chip):
    """(cfg, params, slab, pool, s) of the Mistral cells as shapes placed on
    the described chip: what ``server.api`` builds for ``--dtype q40
    --parallel 16 --max-seq-len 2048 --kv-pages 384``, two layers deep."""
    cfg = LlamaConfig(
        arch=ArchType.LLAMA, dim=4096, hidden_dim=14336, n_layers=SERVED_LAYERS,
        n_heads=32, n_kv_heads=8, vocab_size=32000, seq_len=2048, head_size=128,
        kv_dim=1024, rope_theta=1e6,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def placed(tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    layer = dict(
        qkv=_qm_shape(4096, 6144, one_chip), wo=_qm_shape(4096, 4096, one_chip),
        gate_up=_qm_shape(4096, 28672, one_chip), down=_qm_shape(14336, 4096, one_chip),
        rms_att=s((4096,), jnp.float32), rms_ffn=s((4096,), jnp.float32),
    )
    params = dict(
        embedding=s((32000, 4096), jnp.float32), layers=[layer] * SERVED_LAYERS,
        rms_final=s((4096,), jnp.float32), rope_table=s((2048, 64, 2), jnp.float32),
        wcls=_qm_shape(4096, 32000, one_chip),
    )
    slab = placed(jax.eval_shape(
        lambda: llama.init_batch_cache(cfg, SERVED_ROWS, dtype=jnp.bfloat16)))
    pool = placed(jax.eval_shape(
        lambda: llama.init_page_pool(cfg, SERVED_PAGES, SERVED_PAGE, dtype=jnp.bfloat16)))
    return cfg, params, slab, pool, s


def _computations(hlo: str) -> dict:
    """``{computation name: its instruction lines}`` of a compiled program's text."""
    computations, name = {}, None
    for line in hlo.splitlines():
        m = None if line.startswith(" ") else _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            computations[name] = []
        elif name is not None:
            computations[name].append(line)
    return computations


def _slab_sized_results(hlo: str, half_elements: int, dtype: str = "bf16"):
    """Read a compiled program's text: ``(in_place_writes, others)``, the
    instructions outside fusions whose result holds a ``dtype`` array of at
    least half a slab leaf. A write is a dynamic-update-slice or scatter into
    the leaf (alone or as a fusion's body), or a kernel whose result aliases
    its operand: it updates its operand's buffer. ``others`` are buffers of
    their own in the chip's main memory, each one pass over the slab. Not
    among them: what the compiler's memory-space assignment stages in the
    chip's VMEM (``S(1)`` in a result's layout; a v5e has 128 MiB of it, and a
    leaf of 64 MiB may be prefetched there ahead of its kernel and written
    back behind it: the same one read and one write of main memory, moved in
    time), and the ``copy-done`` that writes such a result back."""
    computations = _computations(hlo)

    def big(result_type: str) -> bool:
        return any(
            np.prod([int(d) for d in dims.split(",")]) >= half_elements
            for dims in re.findall(dtype + r"\[([\d,]+)\]", result_type)
        )

    def writes_in_place(op: str, line: str) -> bool:
        if op in ("dynamic-update-slice", "scatter"):
            return True
        if op == "custom-call" and "output_to_operand_aliasing" in line:
            return True
        called = re.search(r"calls=%?([\w.\-]+)", line)
        return op == "fusion" and called is not None and any(
            (m := _INSTRUCTION.match(inner)) and big(m.group(2))
            and m.group(3) in ("dynamic-update-slice", "scatter")
            for inner in computations.get(called.group(1), ())
        )

    fused = {
        m.group(1) for lines in computations.values() for line in lines
        if " fusion(" in line and (m := re.search(r"calls=%?([\w.\-]+)", line))
    }
    def in_vmem(result_type: str) -> bool:
        """Whether every leaf-sized array of the result lives in VMEM."""
        layouts = [
            layout for dims, layout in re.findall(dtype + r"\[([\d,]+)\](\{[^}]*\})?", result_type)
            if np.prod([int(d) for d in dims.split(",")]) >= half_elements
        ]
        return bool(layouts) and all("S(1)" in layout for layout in layouts)

    # an asynchronous move's start holds its operand and its destination, no buffer of its own;
    # one that names VMEM on either side is the staging above, and so is its done
    moves = {
        m.group(1): "S(1)" in m.group(2) for lines in computations.values() for line in lines
        if (m := _INSTRUCTION.match(line)) and m.group(3) in ("copy-start", "slice-start")
    }
    writes, others = [], []
    for comp, lines in computations.items():
        if comp in fused:
            continue
        for line in lines:
            m = _INSTRUCTION.match(line)
            if not m or m.group(3) in _NO_BUFFER_OPS or m.group(1) in moves or not big(m.group(2)):
                continue
            done = re.search(r"(?:copy|slice)-done\(%?([\w.\-]+)", line)
            if in_vmem(m.group(2)) or (done and moves.get(done.group(1))):
                continue
            found = f"{comp}: %{m.group(1)} = {m.group(2)[:120]} {m.group(3)}"
            (writes if writes_in_place(m.group(3), line) else others).append(found)
    return writes, others


def _assert_no_slab_sized_temporaries(compiled, slab):
    leaf = jax.tree.leaves(slab)[0]
    writes, others = _slab_sized_results(compiled.as_text(), leaf.size // 2)
    # one in-place write a layer, or the reader above no longer sees the program
    assert len(writes) == SERVED_LAYERS, writes
    assert not others, "slab-sized buffers besides the cache write:\n" + "\n".join(others)


def _aliased_outputs(hlo: str) -> dict:
    """``{output index: parameter number}`` of a compiled module's
    ``input_output_alias`` header: the results that live in a donated
    argument's buffer."""
    header = hlo[hlo.index("input_output_alias={"):].split("\n", 1)[0]
    return {
        int(out): int(param)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }


_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}"
)


def _conditionals_over(hlo: str, wanted) -> dict:
    """Read a compiled program's text: for every instruction whose line
    ``wanted`` accepts, the ``conditional`` instructions that stand between
    it and the entry computation, outermost last. ``{"<computation>:
    %<instruction>": [conditional names]}``; an empty list is an instruction
    that runs whatever any predicate says."""
    computations = _computations(hlo)
    # callee -> (caller computation, the calling instruction if it is a conditional)
    callers = {}
    for comp, lines in computations.items():
        for line in lines:
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            for single, several in _CALLED.findall(line):
                for callee in [single] if single else re.findall(r"%?([\w.\-]+)", several):
                    callers.setdefault(callee, []).append(
                        (comp, m.group(1) if m.group(3) == "conditional" else None))

    def guards(comp, seen=()):
        found = []
        for caller, conditional in callers.get(comp, ()):
            if caller not in seen:
                found += ([conditional] if conditional else []) + guards(caller, seen + (comp,))
        return found

    return {
        f"{comp}: %{m.group(1)}": guards(comp)
        for comp, lines in computations.items() for line in lines
        if (m := _INSTRUCTION.match(line)) and wanted(m.group(3), line)
    }


def _is_top_k(op: str, line: str) -> bool:
    """The sampler's candidate selection as the v5e compiler leaves it: the
    ``TopK`` custom call (rows of 8 and more) or the sorts it is rewritten
    to (one row); a full-vocabulary sort of the pick's fallbacks too."""
    return op == "sort" or (op == "custom-call" and 'custom_call_target="TopK"' in line)


@pytest.mark.parametrize("cell", ["mistral-one-row", "granite-32-rows", "tp4-head"])
def test_served_decode_chunk_selects_candidates_only_behind_the_samplers_condition(
        one_chip, mesh4, monkeypatch, cell):
    """ISSUE 46: in the decode chunk the cells dispatch (Mistral's one-row
    bucket, two layers; Granite's 32 rows, one period of ten layers) every
    top-k and sort belongs to a computation that a ``conditional`` calls, and
    ONE conditional a step stands over all of them (the pick's own conditions
    lie inside it): a step whose rows all take the argmax launches none. The
    tp arm (a vocab-sharded head over the described 2x2 mesh, 16 rows of
    Mistral's 32000 logits): the candidates' composition is called inside the
    arm, and its all-gather compiles behind the same conditional."""
    from distributed_llama_tpu.ops import ssd

    monkeypatch.setattr(q40, "_interpret_default", lambda: False)  # steer the CPU branch
    monkeypatch.setattr(ssd, "_interpret_default", lambda: False)
    monkeypatch.setattr(decode_attention, "_interpret_default", lambda: False)
    if cell == "tp4-head":
        rows, vocab = SERVED_ROWS, 32000

        def step(local, seeds, pos, temperature, topp, topk):
            cand = lambda: sampling.sharded_topk_indices(local, "tp", sampling.TOPP_FAST_K)
            logits = jax.lax.all_gather(local, "tp", axis=1, tiled=True)
            return sampling.fused_sample_batched(logits, seeds, pos, temperature, topp, topk, cand=cand)

        s = lambda shape, dt, spec=P(): jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh4, spec))
        row = (rows,)
        text = jax.jit(jax.shard_map(
            step, mesh=mesh4, in_specs=(P(None, "tp"),) + (P(),) * 5, out_specs=P(), check_vma=False,
        )).lower(
            s((rows, vocab), jnp.float32, P(None, "tp")), s(row, jnp.uint32), s(row, jnp.int32),
            s(row, jnp.float32), s(row, jnp.float32), s(row, jnp.int32),
        ).compile().as_text()
        gathers = _conditionals_over(text, lambda op, line: op in ("all-gather", "all-gather-start"))
        # the logits' gather runs every step (the argmax and the fingerprint
        # read it); the candidates' two ride the arm
        assert sorted(len(g) > 0 for g in gathers.values()) == [False, True, True], gathers
    else:
        if cell == "mistral-one-row":
            rows, carry = 1, SERVED_ROWS
            cfg, params, slab, _, s = _served_program_shapes(one_chip)
        else:
            rows = carry = 32
            cfg, params, slab, _, s = _granite_program_shapes(one_chip, 1, rows)
        text = sampling.decode_chunk_batched.lower(
            cfg, params, s((carry,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
            32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
            s((rows,), jnp.uint32)).compile().as_text()
    selections = _conditionals_over(text, _is_top_k)
    assert selections, "the reader above no longer sees the sampler's top-k"
    assert all(selections.values()), selections
    assert len({guards[-1] for guards in selections.values()}) == 1, selections


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "unpaged"])
@pytest.mark.parametrize("rows", [1, SERVED_ROWS])
def test_served_decode_chunk_forms_nothing_of_slab_size(one_chip, monkeypatch, rows, paged):
    """``sampling.decode_chunk_batched`` as the cells dispatch it (bucket 1:
    ``single_stream``; bucket 16: ``chat_shared``, ``batch_decode``; since
    PR 44 a one-chip scheduler hands its chunks no pages, hits being copied
    into their rows) and its ``_paged`` twin (rows that alias the pool):
    in the whole program, the scan's body included, only the per-layer cache
    write has a result of half a slab leaf or more. The scheduler's carry of
    first tokens (one entry a slab row, whatever the bucket) adds none, and
    comes back in the buffer it was donated in."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)  # steer the CPU branch
    monkeypatch.setattr(decode_attention, "_interpret_default", lambda: False)
    cfg, params, slab, pool, s = _served_program_shapes(one_chip)
    head = (cfg, params, s((SERVED_ROWS,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_))
    sampler = (32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
               s((rows,), jnp.uint32))
    if paged:
        lowered = sampling.decode_chunk_batched_paged.lower(
            *head, pool, *sampler, s((rows, 2048 // SERVED_PAGE), jnp.int32), s((rows,), jnp.int32))
    else:
        lowered = sampling.decode_chunk_batched.lower(*head, *sampler)
    compiled = lowered.compile()
    _assert_no_slab_sized_temporaries(compiled, slab)
    # a fused slab read without pages takes the row-bounded kernel, as stored;
    # a bucket of one row over this short a slab keeps the loop
    assert ("slab_decode_scan" in compiled.as_text()) == (not paged and rows > 1)
    # results: the bundle, the slab's leaves, the carry; arguments: the
    # weights' leaves, then the carry
    carry_out, carry_in = 1 + len(jax.tree.leaves(slab)), len(jax.tree.leaves(params))
    assert _aliased_outputs(compiled.as_text())[carry_out] == carry_in


def test_served_mixtral_piece_holds_both_arms_of_its_expert_layers(one_chip, monkeypatch):
    """A 256-row prompt piece of ``mixtral8x7b.batch_decode`` (Mixtral's
    widths, 2 of its layers, the pool-enabled program the cell dispatches):
    ONE program holds, for each expert layer, the bucketed arm (8 experts
    over 128 rows each) and the every-row arm (8 over 256), so an overflow
    builds nothing inside a window; it returns the layers' counts."""
    from distributed_llama_tpu.engine import batch

    monkeypatch.setattr(q40, "_interpret_default", lambda: False)  # steer the CPU branch
    cfg, params, slab, pool, s = _served_program_shapes(one_chip)
    cfg = dataclasses.replace(cfg, arch=ArchType.MIXTRAL, n_experts=8, n_active_experts=2)
    layer = {k: v for k, v in params["layers"][0].items() if k not in ("gate_up", "down")}
    expert = dict(gate_up=_qm_shape(4096, 28672, one_chip), down=_qm_shape(14336, 4096, one_chip))
    layer.update(router=s((4096, 8), jnp.bfloat16), experts=[expert] * 8)
    params = dict(params, layers=[layer] * SERVED_LAYERS)
    compiled = batch._slab_prefill_single_paged.lower(
        cfg, params, s((256,), jnp.int32), slab, pool, s((), jnp.int32), s((), jnp.int32),
        s((), jnp.int32), s((2048 // SERVED_PAGE,), jnp.int32), s((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == SERVED_LAYERS
    for rows in (128, 256):  # both arms' launches, under the role the trace reads them by
        for width in (28672, 4096):
            assert re.search(rf"f32\[{rows},{width}\]\S* custom-call\(.*q40_int8_experts", text), (rows, width)
    moe_counts = compiled.out_info[2]
    assert (moe_counts.shape, moe_counts.dtype) == ((3,), jnp.int32)


def test_served_eva_decode_chunk_forms_nothing_of_slab_size(one_chip, monkeypatch):
    """The decode chunk of ``evabyte.doc_sessions`` (EvaByte's widths, 2 of
    its layers, 8 rows of 2048 window slots and 1024 summaries): a step
    writes a layer's leaf twice in place (the row's key and value, then the
    summary of the chunk that position may end) and its one loop reads the
    leaf a chunk at a time; nothing else of the leaf's size forms."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    monkeypatch.setattr(decode_attention, "_interpret_default", lambda: False)
    rows, layers = 8, 2
    cfg = LlamaConfig(
        arch=ArchType.EVABYTE, dim=4096, hidden_dim=11008, n_layers=layers, n_heads=32,
        n_kv_heads=32, vocab_size=320, seq_len=16384, head_size=128, kv_dim=4096,
        rope_theta=1e5, window=2048, eva_chunk=16,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layer = dict(
        qkv=_qm_shape(4096, 12288, one_chip), wo=_qm_shape(4096, 4096, one_chip),
        gate_up=_qm_shape(4096, 22016, one_chip), down=_qm_shape(11008, 4096, one_chip),
        rms_att=s((4096,), jnp.float32), rms_ffn=s((4096,), jnp.float32),
        eva_phi=s((32, 128), jnp.float32), eva_mu=s((32, 128), jnp.float32),
    )
    params = dict(
        embedding=s((320, 4096), jnp.float32), layers=[layer] * layers,
        rms_final=s((4096,), jnp.float32), rope_table=s((16384, 64, 2), jnp.float32),
        wcls=_qm_shape(4096, 320, one_chip),
    )
    slab = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
        lambda: llama.init_batch_cache(cfg, rows, dtype=jnp.bfloat16)))
    assert [leaf.shape for leaf in slab] == [(2, rows, 3072, 32, 128)] * layers
    compiled = sampling.decode_chunk_batched.lower(
        cfg, params, s((rows,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
        32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
        s((rows,), jnp.uint32)).compile()
    writes, others = _slab_sized_results(compiled.as_text(), slab[0].size // 2)
    assert len(writes) == 2 * layers, writes
    assert not others, "slab-sized buffers besides the cache writes:\n" + "\n".join(others)
    assert "slab_decode_scan" in compiled.as_text()


def test_served_latent_decode_chunk_forms_nothing_of_slab_size(one_chip, monkeypatch):
    """The decode chunk of ``glm-4.7-flash.doc_sessions`` (GLM-4.7-Flash's
    attention widths, 2 layers with a dense feed-forward, 8 rows of 16384
    latent rows of 576 values, positions minor): a step writes a layer's leaf
    in place, a lane tile around each row's new position, and the latent scan
    reads it a chunk at a time, as keys and as values; nothing else of the
    leaf's size forms. Stored [rows, positions, 576], or written by a scatter
    or a column at a time, the same program copied every layer's whole leaf
    into the positions-minor layout and back, in every step (576 is no
    multiple of a tile's 128 lanes, and the compiler reads it positions-minor
    whatever it is given)."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    rows, layers = 8, 2
    cfg = LlamaConfig(
        arch=ArchType.GLM4_MOE_LITE, dim=2048, hidden_dim=10240, n_layers=layers, n_heads=20,
        n_kv_heads=20, vocab_size=154880, seq_len=16384, head_size=256, kv_dim=5120,
        rope_theta=1e6, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layer = dict(
        qkv_a=_qm_shape(2048, 1344, one_chip), q_b=_qm_shape(768, 5120, one_chip),
        q_a_norm=s((768,), jnp.float32), kv_a_norm=s((512,), jnp.float32),
        w_uk=s((20, 192, 512), jnp.bfloat16), w_uv=s((20, 512, 256), jnp.bfloat16),
        wo=_qm_shape(5120, 2048, one_chip),
        gate_up=_qm_shape(2048, 20480, one_chip), down=_qm_shape(10240, 2048, one_chip),
        rms_att=s((2048,), jnp.float32), rms_ffn=s((2048,), jnp.float32),
    )
    params = dict(
        embedding=s((154880, 2048), jnp.float32), layers=[layer] * layers,
        rms_final=s((2048,), jnp.float32), rope_table=s((16384, 32, 2), jnp.float32),
        wcls=_qm_shape(2048, 154880, one_chip),
    )
    slab = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
        lambda: llama.init_batch_cache(cfg, rows, dtype=jnp.bfloat16)))
    assert [leaf["latent"].shape for leaf in slab] == [(rows, 576, 16384)] * layers
    compiled = sampling.decode_chunk_batched.lower(
        cfg, params, s((rows,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
        32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
        s((rows,), jnp.uint32)).compile()
    writes, others = _slab_sized_results(compiled.as_text(), slab[0]["latent"].size // 2)
    assert len(writes) == rows * layers, writes
    assert not others, "slab-sized buffers besides the cache writes:\n" + "\n".join(others)


def _dsa_program_shapes(one_chip, rows: int = 8, layers: int = 2):
    """(cfg, params, slab, pool, s) of ``glm-5.doc_sessions`` as shapes on the
    described chip: GLM-5's attention and indexer widths (64 heads, a query
    latent of 2048, 32 index heads of 128, the 2048 best positions), ``layers``
    layers with a dense feed-forward, ``rows`` rows of 16384 positions: a
    latent row of 576 values and an index key of 128 a position, bf16,
    positions minor; a pool of 64 pages of 64 positions."""
    cfg = LlamaConfig(
        arch=ArchType.GLM4_MOE_LITE, dim=6144, hidden_dim=12288, n_layers=layers, n_heads=64,
        n_kv_heads=64, vocab_size=19360, seq_len=16384, head_size=256, kv_dim=16384,
        rope_theta=1e6, q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32, index_head_dim=128, index_topk=2048,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layer = dict(
        qkv_a=_qm_shape(6144, 2048 + 576 + 128 + 32, one_chip), q_b=_qm_shape(2048, 16384 + 4096, one_chip),
        q_a_norm=s((2048,), jnp.float32), kv_a_norm=s((512,), jnp.float32),
        index_k_norm=s((2, 128), jnp.float32),
        w_uk=s((64, 192, 512), jnp.bfloat16), w_uv=s((64, 512, 256), jnp.bfloat16),
        wo=_qm_shape(16384, 6144, one_chip),
        gate_up=_qm_shape(6144, 24576, one_chip), down=_qm_shape(12288, 6144, one_chip),
        rms_att=s((6144,), jnp.float32), rms_ffn=s((6144,), jnp.float32),
    )
    params = dict(
        embedding=s((19360, 6144), jnp.float32), layers=[layer] * layers,
        rms_final=s((6144,), jnp.float32), rope_table=s((16384, 32, 2), jnp.float32),
        wcls=_qm_shape(6144, 19360, one_chip),
    )
    placed = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    slab = placed(jax.eval_shape(lambda: llama.init_batch_cache(cfg, rows, dtype=jnp.bfloat16)))
    pool = placed(jax.eval_shape(lambda: llama.init_page_pool(cfg, 64, 64, dtype=jnp.bfloat16)))
    assert [(leaf["latent"].shape, leaf["index"].shape) for leaf in slab] == [
        ((rows, 576, 16384), (rows, 128, 16384))] * layers
    assert [tuple(h.shape for h in halves) for halves in pool] == [((64, 64 * 576), (64, 64 * 128))] * layers
    return cfg, params, slab, pool, s


def test_served_dsa_decode_chunk_forms_nothing_of_a_leafs_size(one_chip, monkeypatch):
    """The decode chunk of ``glm-5.doc_sessions``: a step writes both arrays
    of a layer's leaf in place (a lane tile around each row's new position:
    the latent row, the index key), the indexer reads the index keys a chunk
    at a time, the selection works on a row's scores (16384 floats) and the
    masked latent scan reads the latents a chunk at a time; nothing of the
    size of the index array (the smaller of the two: rows x 128 x 16384)
    forms besides the writes, no leaf is copied."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    rows, layers = 8, 2
    cfg, params, slab, _, s = _dsa_program_shapes(one_chip, rows, layers)
    compiled = sampling.decode_chunk_batched.lower(
        cfg, params, s((rows,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
        32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
        s((rows,), jnp.uint32)).compile()
    writes, others = _slab_sized_results(compiled.as_text(), slab[0]["index"].size // 2)
    assert len(writes) == 2 * rows * layers, writes
    assert not others, "leaf-sized buffers besides the cache writes:\n" + "\n".join(others)


def test_served_dsa_prompt_piece_forms_nothing_of_a_leafs_size(one_chip, monkeypatch):
    """A 256-row piece of the same cell: the row's two arrays are taken out of
    the slab and put back, written once each, and the indexer's scores and the
    selection of 256 tokens over 16384 positions (a float32 and a mask of
    [256, 16384]) are no array of the index leaf's size. What does form,
    once a layer: ONE row's latents transposed ([1, 16384, 576], 19 MB, an
    eighth of the latent leaf; the piece of a file without an indexer forms
    none: the mask over the scan makes the compiler hoist the mix's operand
    out of the loop; PERF.md section 7, PR 53). No leaf is copied."""
    from distributed_llama_tpu.engine import batch

    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    rows, layers = 8, 2
    cfg, params, slab, pool, s = _dsa_program_shapes(one_chip, rows, layers)
    compiled = batch._slab_prefill_single_paged.lower(
        cfg, params, s((256,), jnp.int32), slab, pool, s((), jnp.int32), s((), jnp.int32),
        s((), jnp.int32), s((16384 // 64,), jnp.int32), s((), jnp.int32),
    ).compile()
    writes, others = _slab_sized_results(compiled.as_text(), slab[0]["index"].size // 2)
    one_row = [line for line in others if "bf16[1,16384,576]" in line]
    assert len(one_row) <= layers and len(one_row) == len(others), (
        "leaf-sized buffers besides the cache writes:\n" + "\n".join(others))
    assert writes


def _granite_program_shapes(one_chip, periods: int, rows: int):
    """(cfg, params, slab, pool, s) of ``granite-4.0-h-micro.batch_prompted``
    as shapes on the described chip: Granite-4.0-H-Micro's published widths,
    ``periods`` periods of ten layers (state-space but for index 5), ``rows``
    rows of 2048 positions, bf16 keys and values, float32 state."""
    layers = 10 * periods
    cfg = LlamaConfig(
        arch=ArchType.GRANITE_HYBRID, dim=2048, hidden_dim=8192, n_layers=layers, n_heads=32,
        n_kv_heads=8, vocab_size=100352, seq_len=2048, head_size=64, kv_dim=512, attn_period=10,
        attn_offset=5, lin_conv=4, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
        embed_scale=12.0, residual_scale=0.22, attn_scale=0.015625, logits_divisor=8.0,
        kv_head_pack=2,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f32 = lambda *shape: s(shape, jnp.float32)
    dense = dict(gate_up=_qm_shape(2048, 16384, one_chip), down=_qm_shape(8192, 2048, one_chip),
                 rms_att=f32(2048), rms_ffn=f32(2048))
    ssm = dict(ssm_in=_qm_shape(2048, 8512, one_chip), conv=f32(4352, 4), conv_bias=f32(4352),
               dt_bias=f32(64), a_log=f32(64), ssm_d=f32(64), ssm_norm=f32(4096),
               wo=_qm_shape(4096, 2048, one_chip), **dense)
    softmax = dict(qkv=_qm_shape(2048, 3072, one_chip), wo=_qm_shape(2048, 2048, one_chip), **dense)
    params = dict(
        embedding=f32(100352, 2048),
        layers=[softmax if cfg.layer_kind(l)[0] == "full" else ssm for l in range(layers)],
        rms_final=f32(2048), rope_table=f32(2048, 32, 2), wcls=_qm_shape(2048, 100352, one_chip),
    )
    placed = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    slab = placed(jax.eval_shape(lambda: llama.init_batch_cache(cfg, rows, dtype=jnp.bfloat16)))
    pool = placed(jax.eval_shape(
        lambda: llama.init_page_pool(cfg, SERVED_PAGES, SERVED_PAGE, dtype=jnp.bfloat16)))
    return cfg, params, slab, pool, s


@pytest.mark.parametrize("kernel,tokens", [("ssd_step", 32), ("ssd_chunk", 256), ("ssd_chunk", 8)])
def test_the_state_space_kernels_compile(one_chip, monkeypatch, kernel, tokens):
    """The decode step over 32 rows of a 32-row slab (the state aliased in
    place) and one row's prefill piece, at 64 heads of 64 with 128 state
    values: two heads a row of lanes, ``[32, 128, 128]`` a row's state."""
    from distributed_llama_tpu.ops import ssd

    monkeypatch.setattr(ssd, "_interpret_default", lambda: False)
    H, P, N = 64, 64, 128
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    state = ssd.state_shape(H, P, N)
    assert state == (32, 128, 128)
    tokenwise = (s(tokens, H, P), s(tokens, N), s(tokens, N), s(tokens, H), s(H))
    if kernel == "ssd_step":
        active = jax.ShapeDtypeStruct((tokens,), jnp.bool_, sharding=one_chip)
        lowered = jax.jit(ssd.ssd_step, donate_argnums=(0,)).lower(s(tokens, *state), *tokenwise, active)
    else:
        lowered = jax.jit(ssd.ssd_chunk).lower(
            s(*state), *tokenwise, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    text = lowered.compile().as_text()
    assert f"%{kernel}" in text and "tpu_custom_call" in text


def test_served_state_space_decode_chunk_forms_nothing_of_a_leafs_size(one_chip, monkeypatch):
    """The decode chunk of ``granite-4.0-h-micro.batch_prompted`` (one period
    of ten layers, 32 rows): a step updates each state-space layer's state in
    place through ``ssd_step`` (16.8 M float32 values a layer: copied once,
    it would be as many bytes again as the step reads) and writes the softmax
    layer's keys and values in place. Its kv heads of 64 share cache rows of
    128 two by two (``cfg.kv_head_pack``): stored [.., 8, 64] the same program
    copied the whole leaf into a positions-minor layout and back in EVERY
    step (the compiler's own choice for a minor axis of half a lane tile), as
    GLM's [.., positions, 576] leaf was; stored [.., 4, 128] the row-bounded
    kernel reads it as it lies. Nothing else of either leaf's size forms,
    the convolution tails (13056 values a row) are far below it."""
    from distributed_llama_tpu.ops import ssd

    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    monkeypatch.setattr(ssd, "_interpret_default", lambda: False)
    monkeypatch.setattr(decode_attention, "_interpret_default", lambda: False)
    rows = 32
    cfg, params, slab, _, s = _granite_program_shapes(one_chip, 1, rows)
    assert slab[0]["S"].shape == (rows, 32, 128, 128) and slab[5].shape == (2, rows, 2048, 4, 128)
    compiled = sampling.decode_chunk_batched.lower(
        cfg, params, s((rows,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
        32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
        s((rows,), jnp.uint32)).compile()
    text = compiled.as_text()
    writes, others = _slab_sized_results(text, slab[0]["S"].size // 2, "f32")
    # nine steps, each aliasing its state; the compiler may stage one of them through VMEM
    steps = set(re.findall(r"%(ssd_step[\w.]*) = [^\n]*output_to_operand_aliasing", text))
    assert len(steps) == 9 and 7 <= len(writes) <= 9 and all("%ssd_step" in w for w in writes), writes
    assert not others, "state-sized buffers besides the in-place step:\n" + "\n".join(others)
    writes, others = _slab_sized_results(text, slab[5].size // 2)
    assert len(writes) == 1, writes
    assert not others, "slab-sized buffers besides the cache write:\n" + "\n".join(others)
    assert "ssd_step" in text and "slab_decode_scan" in text


def _granite_moe_program_shapes(one_chip, rows: int):
    """(cfg, params, slab, pool, s) of ``granite-4.0-h-small.batch_prompted``
    as shapes on the described chip: Granite-4.0-H-Small's published widths,
    its leading period of ten layers (state-space but for index 5), 18 of 72
    experts held in every layer beside a shared one, a quarter of the
    vocabulary, ``rows`` rows of 2048 positions, bf16 keys and values,
    float32 state."""
    layers, held = 10, 18
    cfg = LlamaConfig(
        arch=ArchType.GRANITE_HYBRID, dim=4096, hidden_dim=1536, n_layers=layers, n_heads=32,
        n_kv_heads=8, vocab_size=25088, seq_len=2048, head_size=128, kv_dim=1024, attn_period=10,
        attn_offset=5, lin_conv=4, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
        n_experts=held, n_active_experts=10, moe_hidden_dim=768, n_shared_experts=2,
        n_routed_experts=72, first_expert=0,
        embed_scale=12.0, residual_scale=0.22, attn_scale=0.0078125, logits_divisor=16.0,
    )

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def bank(n, d):
        one = _qm_shape(n, d, one_chip)
        return q40.QuantizedMatrix(s((held,) + one.qs.shape, jnp.uint8),
                                   s((held,) + one.scales.shape, jnp.float32), n, d)

    f32 = lambda *shape: s(shape, jnp.float32)
    tail = dict(router=f32(4096, 72), experts_gate_up=bank(4096, 1536), experts_down=bank(768, 4096),
                shared_gate_up=_qm_shape(4096, 3072, one_chip), shared_down=_qm_shape(1536, 4096, one_chip),
                rms_att=f32(4096), rms_ffn=f32(4096))
    ssm = dict(ssm_in=_qm_shape(4096, 16768, one_chip), conv=f32(8448, 4), conv_bias=f32(8448),
               dt_bias=f32(128), a_log=f32(128), ssm_d=f32(128), ssm_norm=f32(8192),
               wo=_qm_shape(8192, 4096, one_chip), **tail)
    softmax = dict(qkv=_qm_shape(4096, 6144, one_chip), wo=_qm_shape(4096, 4096, one_chip), **tail)
    params = dict(
        embedding=f32(25088, 4096),
        layers=[softmax if cfg.layer_kind(l)[0] == "full" else ssm for l in range(layers)],
        rms_final=f32(4096), rope_table=f32(2048, 64, 2), wcls=_qm_shape(4096, 25088, one_chip),
    )
    placed = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    slab = placed(jax.eval_shape(lambda: llama.init_batch_cache(cfg, rows, dtype=jnp.bfloat16)))
    pool = placed(jax.eval_shape(
        lambda: llama.init_page_pool(cfg, SERVED_PAGES, SERVED_PAGE, dtype=jnp.bfloat16)))
    return cfg, params, slab, pool, s


def _kernel_paths():
    """{(kernel, path): dispatch decisions counted so far} of ``dllama_kernel_path_total``."""
    from distributed_llama_tpu import telemetry

    counter = telemetry.REGISTRY.get("dllama_kernel_path_total")
    return {key: child.value for key, child in counter._children.items()} if counter else {}


@pytest.mark.parametrize("program", ["the 32-row decode chunk", "a 256-row piece"])
def test_served_granite_experts_programs_form_nothing_of_a_leafs_size(one_chip, monkeypatch, program):
    """The two big programs of ``granite-4.0-h-small.batch_prompted`` at the
    published widths (the leading period, 18 of 72 experts held): the state
    ``[64, 128, 128]`` a row and layer (twice H-Micro's) is stepped in place
    and a piece takes ONE row's out and puts it back; the softmax layer's keys
    and values, heads of 128, are written in place; nothing else of either
    leaf's size forms. The 768-wide ``down`` bank goes through the grouped int8
    kernel (its contraction padded to the 1024 of one input tile, which the
    gauge ``dllama_q40_padded_weight_bytes`` counts), asserted by the
    dispatch counter and by the launch's name, not left to a silent
    fallback. The programs' temporaries are stated (the cell's memory:
    PERF.md section 4)."""
    from distributed_llama_tpu import telemetry
    from distributed_llama_tpu.engine import batch
    from distributed_llama_tpu.models import moe
    from distributed_llama_tpu.ops import ssd

    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    monkeypatch.setattr(ssd, "_interpret_default", lambda: False)
    monkeypatch.setattr(decode_attention, "_interpret_default", lambda: False)
    rows = 32
    cfg, params, slab, pool, s = _granite_moe_program_shapes(one_chip, rows)
    assert slab[0]["S"].shape == (rows, 64, 128, 128) and slab[5].shape == (2, rows, 2048, 8, 128)
    assert cfg.kv_head_pack == 1 and cfg.softmax_scale == 1 / 128
    assert (moe.held_bucket_rows(cfg, 32), moe.held_bucket_rows(cfg, 256)) == (16, 128)
    was_on = telemetry.is_enabled()
    telemetry.enable()
    try:
        before = _kernel_paths()
        if program == "the 32-row decode chunk":
            compiled = sampling.decode_chunk_batched.lower(
                cfg, params, s((rows,), jnp.int32), slab, s((rows,), jnp.int32), s((rows,), jnp.bool_),
                32, s((rows,), jnp.float32), s((rows,), jnp.float32), s((rows,), jnp.int32),
                s((rows,), jnp.uint32)).compile()
        else:
            compiled = batch._slab_prefill_single_paged.lower(
                cfg, params, s((256,), jnp.int32), slab, pool, s((), jnp.int32), s((), jnp.int32),
                s((), jnp.int32), s((2048 // SERVED_PAGE,), jnp.int32), s((), jnp.int32),
            ).compile()
        after = _kernel_paths()
    finally:
        if not was_on:
            telemetry.disable()
    moved = {key: after[key] - before.get(key, 0) for key in after if after[key] != before.get(key, 0)}
    # the grouped launches took the int8 kernel: one decision for each bank's shape at this
    # program's rows (the launch is jitted: the layers after the first reuse its trace), the
    # 768-wide down bank's among them
    assert moved.get(("q40_grouped_matmul", "mxu_int8"), 0) >= 2, moved
    assert ("q40_grouped_matmul", "xla_fallback") not in moved, moved
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"[granite-4.0-h-small] {program}: temporaries {temp / 1e6:.1f} MB")
    if program == "the 32-row decode chunk":
        # a bucket of 16 rows an expert, twice the even share of the step's own 32 rows, and the
        # every-row arm behind it (until PR 52 the every-row arm for certain): [18, rows,
        # columns], the gate|up bank's 1536 columns as its pack holds them (2048 until PR 51)
        for arm_rows in (16, 32):
            for width in (1536, 4096):
                assert re.search(rf"f32\[18,{arm_rows},{width}\]\S* custom-call\(.*q40_int8_grouped_held_experts_t32",
                                 text), (arm_rows, width)
        writes, others = _slab_sized_results(text, slab[0]["S"].size // 2, "f32")
        steps = set(re.findall(r"%(ssd_step[\w.]*) = [^\n]*output_to_operand_aliasing", text))
        assert len(steps) == 9 and 7 <= len(writes) <= 9 and all("%ssd_step" in w for w in writes), writes
        assert not others, "state-sized buffers besides the in-place step:\n" + "\n".join(others)
        writes, others = _slab_sized_results(text, slab[5].size // 2)
        assert len(writes) == 1, writes
        assert not others, "slab-sized buffers besides the cache write:\n" + "\n".join(others)
        assert "slab_decode_scan" in text and temp < 1.5e9
    else:
        # ONE conditional a layer: the bucket of 128 rows, the every-row arm behind it
        assert len(re.findall(r" conditional\(", text)) == cfg.n_layers
        for arm_rows in (128, 256):
            for width in (1536, 4096):
                assert re.search(rf"f32\[18,{arm_rows},{width}\]\S* custom-call\(.*q40_int8_grouped_held_experts_t256",
                                 text), (arm_rows, width)
        assert "%ssd_chunk" in text
        # a row's leaf is taken out and put back: nothing of the WHOLE leaf's size but the
        # in-place writes
        # (the softmax layer's scores, 256 queries x 32 heads x 2048 positions, are float32 of
        # half a state leaf's size and no copy of one)
        writes, others = _slab_sized_results(text, slab[0]["S"].size // 2, "f32")
        others = [o for o in others if "64,128,128]" in o]
        assert not others, "state-sized buffers in a piece:\n" + "\n".join(others)
        writes, others = _slab_sized_results(text, slab[5].size // 2)
        assert not others, "slab-sized buffers in a piece:\n" + "\n".join(others)
        # the bucket's launches, in row tiles since PR 54, are the ones the roofline's reader finds
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
                               "q40_held_experts_roofline.json")) as f:
            pattern = json.load(f)["reader"]["ops"]
        assert re.match(pattern, "q40_int8_grouped_held_experts_t256 f32[18,128,1536]")
        # held choices, layers by arm, and the rows the launches multiplied
        moe_counts = compiled.out_info[2]
        assert (moe_counts.shape, moe_counts.dtype) == ((4,), jnp.int32) and temp < 2.5e9


def test_served_verify_chunk_forms_nothing_of_slab_size(one_chip, monkeypatch):
    """``sampling.spec_verify_chunk_batched_paged`` (``--spec-draft 4``, 16
    rows): one forward per dispatch, so the whole program is the step."""
    monkeypatch.setattr(q40, "_interpret_default", lambda: False)
    cfg, params, slab, pool, s = _served_program_shapes(one_chip)
    rows, window = SERVED_ROWS, 5
    compiled = sampling.spec_verify_chunk_batched_paged.lower(
        cfg, params, s((rows, window), jnp.int32), slab, s((rows,), jnp.int32),
        s((rows,), jnp.bool_), pool, s((rows,), jnp.int32), s((rows,), jnp.float32),
        s((rows,), jnp.float32), s((rows,), jnp.int32), s((rows,), jnp.uint32),
        s((rows, 2048 // SERVED_PAGE), jnp.int32), s((rows,), jnp.int32),
    ).compile()
    _assert_no_slab_sized_temporaries(compiled, slab)


@pytest.mark.parametrize("run", [1, 64, 128])
def test_prefix_restore_writes_the_slab_in_place(one_chip, run):
    """``engine.batch._restore_pages`` at ``mistral7b.long_doc_qa``'s shapes
    (8 rows x 8192 positions, 1024 pages of 64, bf16; 2 of its layers), for a
    hit of one page, of 64–127 and of a whole row: every leaf comes back in
    the buffer it was donated in, written by two contiguous in-place updates,
    and nothing else of half a leaf's size forms (a copy of the slab a hit
    would cost more than the decode steps it saves)."""
    from distributed_llama_tpu.engine import batch

    rows, seq, pages = 8, 8192, 1024
    cfg = LlamaConfig(
        arch=ArchType.LLAMA, dim=4096, hidden_dim=14336, n_layers=SERVED_LAYERS,
        n_heads=32, n_kv_heads=8, vocab_size=32000, seq_len=seq, head_size=128,
        kv_dim=1024, rope_theta=1e6,
    )

    def placed(make):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), jax.eval_shape(make))

    slab = placed(lambda: llama.init_batch_cache(cfg, rows, dtype=jnp.bfloat16))
    pool = placed(lambda: llama.init_page_pool(cfg, pages, SERVED_PAGE, dtype=jnp.bfloat16))
    assert [leaf.shape for leaf in slab] == [(2, rows, seq, 8, 128)] * SERVED_LAYERS
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((2, run), jnp.int32, sharding=one_chip)
    compiled = batch._restore_pages.lower(slab, pool, ids, scalar, scalar).compile()
    writes, others = _slab_sized_results(compiled.as_text(), slab[0].size // 2)
    assert len(writes) == 2 * SERVED_LAYERS, writes
    assert not others, "slab-sized buffers besides the two writes a leaf:\n" + "\n".join(others)
    assert _aliased_outputs(compiled.as_text()) == {l: l for l in range(SERVED_LAYERS)}
    assert compiled.memory_analysis().temp_size_in_bytes < slab[0].size // 64


# the cells whose lines carried the publish's whole-half slice (ledger, PR 46), two layers of
# each: (slab leaves [2, rows, slots, K, hd], pool page's slots, pool entries, BlockSlots keywords)
_EVA_LEAF, _DOC_LEAF = (2, 8, 3072, 32, 128), (2, 8, 8192, 8, 128)
_EXAONE_LEAVES = ((2, 8, 1024, 8, 128), (2, 8, 16384, 8, 128))  # a window layer's ring, a full layer
_PUBLISHES = {
    "evabyte-summaries": ("_publish_pages", (_EVA_LEAF,) * 2, 4, (True, True), {"base": 2048}),
    "evabyte-window": ("_publish_window_pages", (_EVA_LEAF,) * 2, 64, (True, True), {"ring": 2048}),
    "long-documents": ("_publish_pages", (_DOC_LEAF,) * 2, 64, (True, True), {}),
    "k-exaone-full": ("_publish_pages", _EXAONE_LEAVES, 64, (False, True), {}),
    "k-exaone-rings": ("_publish_window_pages", _EXAONE_LEAVES, 64, (True, False), {"ring": 1024}),
}


@pytest.mark.parametrize("case", sorted(_PUBLISHES))
def test_prefix_publish_forms_nothing_of_a_rows_size(one_chip, case):
    """``engine.batch._publish_pages`` and ``_publish_window_pages`` at the
    shapes of the three cells that publish most (EvaByte's summaries behind a
    base and its window store as a ring, long documents, K-EXAONE's full
    layers and its rings; bf16, 8 ids): every pool half comes back in the
    buffer it was donated in, written in place, and the read forms nothing as
    large as ONE ROW of one half of the leaf it reads. A ``leaf[0]`` handed on
    as a half is no view to this compiler: it materialised the whole half, 201
    MB in EvaByte's cell, twice a layer and a publish."""
    from distributed_llama_tpu.engine import batch

    program, leaves, block, pooled, slots = _PUBLISHES[case]
    s = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    slab = [s(leaf) for leaf in leaves]
    pages = 512
    pool = [(s((pages, block) + leaf[3:]),) * 2 if held else None
            for leaf, held in zip(leaves, pooled)]
    ids = s((8,), jnp.int32)
    compiled = getattr(batch, program).lower(block, slab, pool, ids, ids, s((), jnp.int32), **slots).compile()
    read = [leaf for leaf, held in zip(leaves, pooled) if held]
    halves, text = 2 * len(read), compiled.as_text()
    # one in-place scatter a pool half, or the reader no longer sees the program
    writes, _ = _slab_sized_results(text, pages * block * int(np.prod(read[0][3:])))
    assert len(writes) == halves, writes
    _, others = _slab_sized_results(text, min(int(np.prod(leaf[2:])) for leaf in read))
    assert not others, "results as large as a row of a half:\n" + "\n".join(others)
    # the parameters are the leaves the program reads (a layer of the other kind is no
    # argument of the compiled program), then the pool's halves
    assert _aliased_outputs(text) == {o: len(read) + o for o in range(halves)}
    leaf_bytes = 2 * min(int(np.prod(leaf)) for leaf in read)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes // 64


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
