"""Tensor parallelism: one SPMD program over a ``tp`` mesh axis.

Layout parity with the reference's slicing math (reference:
src/commands.cpp:11-108):

  * q/k/v, w1(gate)/w3(up) — output-dim sharded  (RowMatmulSlice, :11-43)
  * wo, w2(down)           — input-dim sharded   (ColMatmulSlice, :45-73)
  * attention heads        — ``n_heads/tp`` per shard (MultiHeadAttSlice, :104-108)
  * KV cache               — sharded on the KV-head axis (KvCacheSlice, :97-102)
  * MoE experts            — every shard holds a 1/tp hidden-slice of all
                             experts (transformer.cpp:335-353)
  * wcls                   — output(vocab)-dim sharded + all-gather (the
                             reference keeps logits root-only instead)

What the reference does with 4 TCP hops per layer (broadcast xb, gather xbv,
broadcast xb, gather xbv — README.md:135-147) is here exactly 2 all-reduces
per layer (after wo and after w2) riding ICI, with the activation broadcast
replaced by replicated-by-construction compute. The all-reduces route
through the seam in ``ops.collectives``: ``lax.psum`` by default, with
the bidirectional ``make_async_remote_copy`` ring kernel (the reduce
overlaps the matmul epilogue instead of serializing after it) behind
``DLT_ALLREDUCE=ring`` until the chip smoke validates its Mosaic build.

The divisibility constraint mirrors ``nSlices <= nKvHeads``
(reference: src/transformer.cpp:108-111): tp must divide n_kv_heads (and
n_heads, hidden_dim).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_llama_tpu import lockcheck
from distributed_llama_tpu.models import llama
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.parallel import sharding


def validate_tp(cfg: LlamaConfig, tp: int, quantized: bool = False) -> None:
    """The sharding-divisibility constraint, enforced like the reference's
    nSlices checks (reference: src/transformer.cpp:105-111)."""
    if tp & (tp - 1):
        raise ValueError(f"tp must be a power of two, got {tp}")
    for name, value in (
        ("n_heads", cfg.n_heads),
        ("n_kv_heads", cfg.n_kv_heads),
        ("hidden_dim", cfg.hidden_dim),
    ):
        if value % tp != 0:
            raise ValueError(f"tp={tp} must divide {name}={value}")
    if quantized:
        # input-dim shards must land on 32-wide quant-block boundaries,
        # like ColMatmulSlice's n % (nSlices*blockSize) requirement
        # (reference: src/commands.cpp:49-56)
        from distributed_llama_tpu.quants import QK

        for name, value in (("dim", cfg.dim), ("hidden_dim", cfg.hidden_dim)):
            if value % (tp * QK) != 0:
                raise ValueError(
                    f"q40 tp={tp} needs {name}={value} divisible by {tp * QK}"
                )


def layer_param_specs(cfg: LlamaConfig, axis: str = "tp") -> dict[str, P]:
    """PartitionSpecs for the stacked per-layer tree (leading axis = layer).
    A rule-table lookup (parallel/sharding.py — the one sharding
    authority); kept as the historical call surface."""
    return sharding.param_specs(
        cfg, "stacked", shard_vocab=False, axes={"model": axis}
    )["layers"]


def param_specs(cfg: LlamaConfig, shard_vocab: bool, axis: str = "tp") -> dict[str, Any]:
    return sharding.param_specs(cfg, "stacked", shard_vocab, {"model": axis})


def param_specs_layered(
    cfg: LlamaConfig, n_layers: int, shard_vocab: bool, axis: str = "tp"
) -> dict[str, Any]:
    """Specs for the per-layer-list params layout (engine.weights.load_params):
    a rule-table lookup over the layered skeleton."""
    return sharding.param_specs(
        cfg, "layered", shard_vocab, {"model": axis}, n_layers=n_layers
    )


def q40_layer_specs(cfg: LlamaConfig, axis: str = "tp") -> dict[str, P]:
    """PartitionSpecs for ONE layer of the q40 per-layer-list layout
    (fused qkv/gate_up, QuantizedMatrix leaves — a spec here is a pytree
    prefix covering both the qs and scales arrays, which shard alike)."""
    return sharding.param_specs(
        cfg, "q40", shard_vocab=False, axes={"model": axis}, n_layers=1
    )["layers"][0]


def q40_param_specs(
    cfg: LlamaConfig, n_layers: int, shard_vocab: bool, axis: str = "tp"
) -> dict[str, Any]:
    return sharding.param_specs(
        cfg, "q40", shard_vocab, {"model": axis}, n_layers=n_layers
    )


# Resolved cache layouts for the classic 1-D ``tp`` mesh (the table lives
# in parallel/sharding.py CACHE_AXES; backends on other meshes resolve
# with their own axis mapping)
_TP_AXES = {"model": "tp"}
CACHE_SPEC = sharding.cache_spec("stacked", _TP_AXES)  # [L, 2, S, K, hd]
CACHE_SPEC_LAYER = sharding.cache_spec("stream", _TP_AXES)  # per-layer [S, K, hd]
# batched slab cache (engine.batch): per-layer (keys, values) tuples of
# [B, S, K, hd] — batch and sequence replicated, KV heads sharded
BATCH_CACHE_SPEC_LAYER = sharding.cache_spec("slab", _TP_AXES)
# prefix-cache page pool (engine.prefix_cache): per-layer (keys, values)
# halves of [P, page, K, hd] — pages and positions replicated, KV heads
# sharded exactly like the slab, so each shard's paged attention reads ITS
# OWN pool half through the (replicated) page tables with the same local
# program as the single-chip path
POOL_SPEC_LAYER = sharding.cache_spec("pool", _TP_AXES)


def place_params(host_params, specs, mesh) -> Any:
    """device_put a params tree against a matching PartitionSpec tree.

    Explicit recursion: PartitionSpec is a tuple subclass (and
    QuantizedMatrix a custom node), so tree.map over the spec tree would
    descend into the specs themselves. A single PartitionSpec acts as a
    prefix covering the whole tree (the replicated case)."""
    from jax.sharding import PartitionSpec as _P

    from distributed_llama_tpu.ops.q40 import QuantizedMatrix

    def rec(p, s):
        if isinstance(s, _P):
            if isinstance(p, dict):
                return {k: rec(p[k], s) for k in p}
            if isinstance(p, list):
                return [rec(pi, s) for pi in p]
        elif isinstance(p, dict):
            return {k: rec(p[k], s[k]) for k in p}
        elif isinstance(p, list):
            return [rec(pi, si) for pi, si in zip(p, s)]
        if isinstance(p, QuantizedMatrix):
            # one spec covers both leaves: qs and scales shard along the
            # same axis index
            ns = NamedSharding(mesh, s)
            return QuantizedMatrix(
                jax.device_put(p.qs, ns),
                jax.device_put(p.scales, ns),
                p.n_logical,
                p.d_logical,
            )
        return jax.device_put(p, NamedSharding(mesh, s))

    return rec(host_params, specs)


class TransferProbeMixin:
    """Shared timing harness over a backend's :meth:`transfer_probe`: all
    parallel backends measure their collective ("transfer") cost the same
    way, so the methodology lives once. Each measurement also feeds the
    telemetry registry (all-reduce latency histogram + estimated payload
    bytes) when telemetry is enabled."""

    def _collective_tel(self):
        tel = getattr(self, "_collective_tel_bundle", None)
        if tel is None:
            from distributed_llama_tpu import telemetry as _telemetry

            tel = _telemetry.CollectiveInstruments()
            self._collective_tel_bundle = tel
        return tel

    def _faults_plan(self):
        """Bind-once fault-injection plan (engine/faults.py): the no-op
        NULL_PLAN unless a chaos plan was installed before construction."""
        plan = getattr(self, "_faults_plan_bound", None)
        if plan is None:
            from distributed_llama_tpu.engine import faults as _faults

            plan = _faults.active_plan()
            self._faults_plan_bound = plan
        return plan

    def _enqueue(self, jitted, *args):
        """Dispatch a jitted multi-partition program with the backend's
        enqueue order serialized (when the backend defines a dispatch
        lock). Concurrent callers sharing one backend — the pod's slice
        schedulers — would otherwise interleave their per-device enqueues
        inconsistently, and two in-flight programs spanning overlapping
        device sets deadlock at their first collectives (observed as a
        hung serving window; the same race corrupts the CPU client's heap
        under concurrent python-thread dispatch). The lock covers ONLY
        the asynchronous enqueue, never a fetch — execution still
        overlaps."""
        lock = getattr(self, "_dispatch_lock", None)
        if lock is None:
            return jitted(*args)
        with lock:
            return jitted(*args)

    def transfer_bytes_per_token(self) -> int:
        """Estimated LOGICAL payload bytes the probed collective sequence
        moves per token (f32 activations; backends override with their own
        per-layer collective shapes). 0 when a backend declines to estimate."""
        return 0

    def measure_transfer_ms(self, n_tokens: int = 32) -> float:
        """Per-token collective cost on the real mesh, replayed
        back-to-back (upper bound: XLA may overlap collectives with compute
        in the real program). The engine re-runs this periodically at
        quiescent points, so the printed T follows actual interconnect load
        over a session — the TPU analogue of the reference's
        TASK_TYPE_TRANSFER wall-time accounting (src/utils.cpp:216-218)."""
        from distributed_llama_tpu.telemetry import Stopwatch

        # transfer-error injection site (chaos tests): a raise here models a
        # flaky interconnect — the engine keeps its previous estimate instead
        # of failing the request that triggered the probe (engine.py)
        self._faults_plan().fire("tp.transfer")
        tel = self._collective_tel()
        jitted, args = self._transfer_probe_cached(n_tokens)
        with tel.span("transfer_probe", tokens=n_tokens):
            sw = Stopwatch()
            # the fetch is the fence: the probe's result must reach the host
            np.asarray(self._enqueue(jitted, *args)[0])
            per_token_ms = sw.elapsed_ms() / n_tokens
        if tel.enabled:
            tel.probe_runs.inc()
            tel.allreduce_latency.observe(per_token_ms / 1000.0)
            tel.allreduce_bytes.inc(self.transfer_bytes_per_token() * n_tokens)
        return per_token_ms

    def _transfer_probe_cached(self, n_tokens: int):
        key = ("probe", n_tokens)
        cached = self._decode_cache.get(key)
        if cached is None:
            jitted, args = self.transfer_probe(n_tokens)
            np.asarray(self._enqueue(jitted, *args)[0])  # compile + warm outside the window
            cached = (jitted, args)
            self._decode_cache[key] = cached
        return cached


class TensorParallelForward(TransferProbeMixin):
    """Jitted shard_map'd forward over a 1-D ``tp`` mesh.

    ``quantized=True`` switches the param layout to the q40 per-layer list
    (fused qkv/gate_up QuantizedMatrix leaves, built in sharded layout by
    ``engine.weights.load_params(tp=...)``).
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        tp: int,
        devices=None,
        quantized: bool = False,
        layered: bool | None = None,
        axis: str = "tp",
        mesh: Mesh | None = None,
    ):
        """``axis``/``mesh`` let a subclass run the same program family on
        a larger named mesh (the one-process pod backend rides a
        ('data', 'model') mesh with ``axis='model'``; every spec below
        resolves through the rule table with that mapping, replicating
        over any axis the mapping never names)."""
        validate_tp(cfg, tp, quantized=quantized)
        self.cfg = cfg
        self.tp = tp
        self.axis = axis
        self.quantized = quantized
        # layered = per-layer-list params + cache (the engine's production
        # layout for every dtype); stacked remains for synthetic-params
        # callers (tests, the driver dryrun)
        self.layered = quantized if layered is None else layered
        if mesh is not None:
            if axis not in mesh.axis_names or mesh.shape[axis] != tp:
                raise ValueError(
                    f"mesh axis {axis!r} of size {tp} required, got "
                    f"{dict(mesh.shape)}"
                )
            self.mesh = mesh
        else:
            if devices is None:
                devices = jax.devices()[:tp]
            if len(devices) < tp:
                raise ValueError(f"need {tp} devices, have {len(devices)}")
            self.mesh = Mesh(
                mesh_utils.create_device_mesh((tp,), devices=devices), (axis,)
            )
        self.shard_vocab = cfg.vocab_size % tp == 0
        self._decode_cache: dict = {}
        self._chunk_cache: dict = {}
        # serializes program ENQUEUE order across callers sharing this
        # backend (the pod's slice schedulers); see TransferProbeMixin._enqueue
        self._dispatch_lock = lockcheck.make_lock("TransferProbeMixin._dispatch_lock")
        axes = {"model": axis}
        if quantized:
            self._specs = q40_param_specs(
                cfg, cfg.n_layers, self.shard_vocab, axis=axis
            )
        elif self.layered:
            self._specs = param_specs_layered(
                cfg, cfg.n_layers, self.shard_vocab, axis=axis
            )
        else:
            self._specs = param_specs(cfg, self.shard_vocab, axis=axis)
        # cache/slab/pool layouts from the same rule table (sharding.py)
        self._stream_cache_spec = sharding.cache_spec("stream", axes)
        self._slab_spec = sharding.cache_spec("slab", axes)
        self._pool_spec_layer = sharding.cache_spec("pool", axes)
        # batched-dispatch vector layouts: per-row scalars ([B] first/pos/
        # active/sampler/seeds), per-row page tables ([B, n_table]) and the
        # packed token bundle ([chunk+2, B]). Replicated on the 1-D mesh;
        # the pod backend re-points them at its 'data' axis when the slab's
        # batch axis is data-sharded (parallel/pod.py)
        self._vec_spec = P()
        self._table_spec = P()
        self._tok_out_spec = P()
        if self.layered:
            # layered cache (list of per-layer arrays): the unrolled forward
            # needs per-leaf in-place aliasing (see llama.init_cache)
            self._cache_spec: Any = [self._stream_cache_spec] * cfg.n_layers
        else:
            self._cache_spec = sharding.cache_spec("stacked", axes)

        fn = functools.partial(self._step, cfg, self.axis)
        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, P(), self._cache_spec, P(), P()),
            out_specs=(P(), self._cache_spec),
            check_vma=False,
        )
        self._jitted = jax.jit(mapped, donate_argnums=(2,))

    # the forward accepts the bucket-padded prompt's real-token count (the
    # capacity-bucketed MoE prefill masks pad rows out of its buckets)
    accepts_n_real = True

    @staticmethod
    def _step(cfg, axis, params, tokens, cache, pos, n_real):
        logits, new_cache = llama.forward_tokens(
            cfg, params, tokens, cache, pos, axis_name=axis, n_real=n_real
        )
        if logits.shape[-1] != cfg.vocab_size:
            # wcls was vocab-sharded: reassemble full logits on every shard
            logits = jax.lax.all_gather(logits, axis, axis=1, tiled=True)
        return logits, new_cache

    # ------------------------------------------------------------------

    def shard_params(self, host_params) -> Any:
        return place_params(host_params, self._specs, self.mesh)

    def _decode_jitted(self, n_steps: int, temperature: float, topp: float, topk: int):
        # per-instance cache (an lru_cache on the method would pin self and
        # its compiled executables in a class-level cache for process life)
        key = (n_steps, temperature, topp, topk)
        cached = self._decode_cache.get(key)
        if cached is not None:
            return cached
        from distributed_llama_tpu.models import sampling

        cfg = self.cfg

        axis = self.axis

        def fn(params, first_token, cache, pos, seed):
            return sampling.decode_scan(
                cfg, params, first_token, cache, pos, seed, n_steps,
                temperature, topp, topk, axis_name=axis,
            )

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, P(), self._cache_spec, P(), P()),
            out_specs=(P(), self._cache_spec),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(2,))
        self._decode_cache[key] = jitted
        return jitted

    def decode_loop(
        self, params, first_token, cache, pos, n_steps, temperature, topp,
        seed: int = 0, topk: int = 0,
    ):
        """On-device autoregressive decode under TP: ONE dispatch for
        ``n_steps`` tokens, collectives riding the mesh every step. Sampling
        runs replicated on counter coins (same (seed, position) → same token
        on every shard)."""
        from distributed_llama_tpu import prng

        jitted = self._decode_jitted(
            int(n_steps), float(temperature), float(topp), int(topk)
        )
        tokens, cache = self._enqueue(
            jitted,
            params, jnp.asarray(first_token), cache, jnp.asarray(pos),
            jnp.uint32(prng.fold_seed(seed)),
        )
        return tokens, cache

    def _chunk_jitted(self, n_steps: int):
        cached = self._chunk_cache.get(n_steps)
        if cached is not None:
            return cached
        from distributed_llama_tpu.models import sampling

        cfg = self.cfg

        axis = self.axis

        def fn(params, first_token, cache, pos, temperature, topp, topk, seed):
            return sampling.decode_scan(
                cfg, params, first_token, cache, pos, seed, n_steps,
                temperature, topp, topk, axis_name=axis,
            )

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, P(), self._cache_spec, P(), P(), P(), P(), P()),
            out_specs=(P(), self._cache_spec),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(2,))
        self._chunk_cache[n_steps] = jitted
        return jitted

    def decode_chunk(
        self, params, first_token, cache, pos, n_steps, temperature, topp,
        topk, seed32,
    ):
        """Chunked streaming decode under TP: temperature/topp/topk are
        traced (one compiled program per chunk size, no per-request
        recompiles); coins re-key per position from the folded request
        seed, so no sampler state returns."""
        jitted = self._chunk_jitted(int(n_steps))
        return self._enqueue(
            jitted,
            params, jnp.asarray(first_token), cache, jnp.asarray(pos),
            jnp.float32(temperature), jnp.float32(topp), jnp.int32(topk),
            jnp.asarray(seed32, jnp.uint32),
        )

    def transfer_probe(self, n_tokens: int = 32):
        """(jitted_fn, example_args) replaying one decode step's collective
        sequence per iteration — 2 psums of a [1, dim] f32 activation per
        layer (after wo and after down, the reference's two gather+merge
        hops per layer, src/llama2-tasks.cpp:115-131/196-212) plus the vocab
        all-gather when wcls is sharded — scanned ``n_tokens`` times in one
        dispatch. Exposed separately from :meth:`measure_transfer_ms` so
        tests can compile it and assert the collectives survive XLA DCE
        (the keep-alive arithmetic is what this probe's timing validity
        rests on)."""
        cfg = self.cfg
        shard_vocab = self.shard_vocab
        axis = self.axis
        vshard = cfg.vocab_size // self.tp if shard_vocab else cfg.vocab_size

        def token_step(carry, _):
            x, lg = carry

            def layer_step(c, _):
                # two all-reduces per layer, as in the forward program —
                # through the SAME seam the forward uses (ops.collectives),
                # so the probe times whichever implementation (psum / ring)
                # production decode actually rides
                from distributed_llama_tpu.ops import collectives

                c = collectives.all_reduce(c, axis) * 0.5
                c = collectives.all_reduce(c, axis) * 0.5
                return c, None

            x, _ = jax.lax.scan(layer_step, x, None, length=cfg.n_layers)
            if shard_vocab:
                g = jax.lax.all_gather(lg, axis, axis=1, tiled=True)
                lg = lg + jnp.sum(g) * 1e-9  # keep the gather live
            return (x, lg), None

        def fn(x, lg):
            (x, lg), _ = jax.lax.scan(token_step, (x, lg), None, length=n_tokens)
            return x, lg

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(P(), P(None, axis) if shard_vocab else P()),
            out_specs=(P(), P(None, axis) if shard_vocab else P()),
            check_vma=False,
        )
        x = jnp.ones((1, cfg.dim), jnp.float32)
        lg = jnp.ones((1, vshard * self.tp if shard_vocab else cfg.vocab_size), jnp.float32)
        return jax.jit(mapped), (x, lg)

    def transfer_bytes_per_token(self) -> int:
        """2 psums of a [1, dim] f32 activation per layer (after wo and
        after down) plus the vocab all-gather when wcls is sharded — the
        exact sequence :meth:`transfer_probe` replays."""
        n = 2 * self.cfg.n_layers * self.cfg.dim * 4
        if self.shard_vocab:
            n += self.cfg.vocab_size * 4
        return n

    def init_cache(self, dtype=jnp.float32):
        from distributed_llama_tpu.ops import kv_cache as kvc

        kv_shape = (self.cfg.seq_len, self.cfg.n_kv_heads, self.cfg.head_size)
        if self.layered:  # per-layer (keys, values) tuples (see _cache_spec)
            sharding = NamedSharding(self.mesh, self._stream_cache_spec)

            def zeros(shape, dt):
                # shape is GLOBAL; build the local kv-head shard (the spec
                # prefix covers QuantizedKV's rank-3 scales leaf too)
                local = np.zeros((shape[0], shape[1] // self.tp) + shape[2:], dt)
                return jax.make_array_from_callback(shape, sharding, lambda idx: local)

            return [
                (kvc.init_half(kv_shape, dtype, zeros=zeros),
                 kvc.init_half(kv_shape, dtype, zeros=zeros))
                for _ in range(self.cfg.n_layers)
            ]
        if kvc.is_quantized_cache_dtype(dtype):
            raise ValueError("the i8 KV cache requires the layered cache layout")
        shape = (self.cfg.n_layers, 2) + kv_shape
        sharding = NamedSharding(self.mesh, self._cache_spec)
        per_shard = shape[:3] + (shape[3] // self.tp,) + shape[4:]
        zeros = np.zeros(per_shard, dtype)
        return jax.make_array_from_callback(shape, sharding, lambda idx: zeros)

    def forward(self, params, tokens, cache, pos, n_real=None):
        tokens = jnp.asarray(tokens)
        if n_real is None:
            n_real = tokens.shape[0]
        return self._enqueue(
            self._jitted, params, tokens, cache, jnp.asarray(pos),
            jnp.int32(n_real),
        )

    # ------------------------------------------------------------------
    # Batched multi-stream decode (engine.batch.BatchScheduler): the slab
    # cache shards its KV-head axis over tp exactly like the per-stream
    # caches, so the batched step is the same SPMD program family with a
    # leading batch axis. Requires the layered params/cache layout (the
    # engine's production layout for every dtype).
    # ------------------------------------------------------------------

    # -- slab row seam: the pod backend overrides these three to gather/
    # -- scatter one row across its data-sharded batch axis; here they are
    # -- the plain local ops (all run INSIDE the shard_map'd bodies)

    def _local_slab_shape(self, gshape: tuple) -> tuple:
        """One device's shard of a GLOBAL slab-half shape [B, S, K, hd]
        (or its rank-4 scales twin): KV heads divide by the model degree;
        the pod backend additionally divides the batch axis when its slab
        is data-sharded."""
        return gshape[:2] + (gshape[2] // self.tp,) + gshape[3:]

    def _slab_row_take(self, half, row):
        from distributed_llama_tpu.ops import kv_cache as kvc

        return kvc.slab_take_row(half, row)

    def _slab_row_put(self, half, new_row, row):
        from distributed_llama_tpu.ops import kv_cache as kvc

        return kvc.slab_put_row(half, new_row, row)

    def _slab_publish(self, pool_half, slab_half, row, src_page, page_ids):
        from distributed_llama_tpu.ops import kv_cache as kvc

        return kvc.publish_row_pages(
            pool_half, slab_half, row, src_page, page_ids, pool_half.shape[1]
        )

    def init_batch_cache(self, b_max: int, dtype=jnp.float32):
        from distributed_llama_tpu.ops import kv_cache as kvc

        if not self.layered:
            raise ValueError("the batched slab cache requires the layered layout")
        cfg = self.cfg
        shape = (b_max, cfg.seq_len, cfg.n_kv_heads, cfg.head_size)
        sharding = NamedSharding(self.mesh, self._slab_spec)

        def zeros(gshape, dt):
            local = np.zeros(self._local_slab_shape(gshape), dt)
            return jax.make_array_from_callback(gshape, sharding, lambda idx: local)

        return [
            (kvc.init_half(shape, dtype, zeros=zeros),
             kvc.init_half(shape, dtype, zeros=zeros))
            for _ in range(cfg.n_layers)
        ]

    def _batched_chunk_jitted(self, n_steps: int):
        key = ("batched_chunk", n_steps)
        cached = self._chunk_cache.get(key)
        if cached is not None:
            return cached
        from distributed_llama_tpu.models import sampling

        cfg = self.cfg
        axis = self.axis
        batch_cache_spec = [self._slab_spec] * cfg.n_layers

        def fn(params, carry, cache, pos, active, temperature, topp,
               topk, seeds):
            # the fingerprint folds the all-gathered full-vocab logits, so
            # every shard packs the same replicated bundle (integrity.py);
            # the sampler's candidate top-k composes over the sharded vocab
            # BEFORE that gather (sampling.sharded_topk_indices)
            return sampling.batched_chunk_from_carry(
                cfg, params, carry, cache, pos, active, seeds, n_steps,
                temperature, topp, topk, axis_name=axis,
            )

        V = self._vec_spec
        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, V, batch_cache_spec, V, V, V, V,
                      V, V),
            out_specs=(self._tok_out_spec, batch_cache_spec, V),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(1, 2))
        self._chunk_cache[key] = jitted
        return jitted

    def batched_decode_chunk(
        self, params, carry, cache, pos, active, n_steps, temperature,
        topp, topk, seeds,
    ):
        """One chunk of the batched multi-stream decode under TP: B
        sequences step together with per-row positions/seeds/sampler
        settings, collectives riding the mesh each step. One compiled
        program per (bucket, chunk) shape; no sampler state returns.
        ``carry`` (the scheduler's first-token vector, donated) feeds the
        first step and returns advanced, as in
        ``sampling.decode_chunk_batched``."""
        jitted = self._batched_chunk_jitted(int(n_steps))
        return self._enqueue(
            jitted,
            params, jnp.asarray(carry), cache, jnp.asarray(pos),
            jnp.asarray(active), jnp.asarray(temperature), jnp.asarray(topp),
            jnp.asarray(topk), jnp.asarray(seeds),
        )

    def _slab_forward_jitted(self):
        key = ("slab_forward",)
        cached = self._chunk_cache.get(key)
        if cached is not None:
            return cached
        from distributed_llama_tpu.ops import kv_cache as kvc

        cfg = self.cfg
        axis = self.axis
        batch_cache_spec = [self._slab_spec] * cfg.n_layers

        def fn(params, tokens, slab, row, pos, n_real):
            row_cache = [
                (self._slab_row_take(k, row), self._slab_row_take(v, row))
                for k, v in slab
            ]
            logits, new_rows = llama.forward_tokens(
                cfg, params, tokens, row_cache, pos, axis_name=axis,
                n_real=n_real,
            )
            if logits.shape[-1] != cfg.vocab_size:
                logits = jax.lax.all_gather(logits, axis, axis=1, tiled=True)
            new_slab = [
                (self._slab_row_put(k, nk, row), self._slab_row_put(v, nv, row))
                for (k, v), (nk, nv) in zip(slab, new_rows)
            ]
            return logits, new_slab

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, P(), batch_cache_spec, P(), P(), P()),
            out_specs=(P(), batch_cache_spec),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(2,))
        self._chunk_cache[key] = jitted
        return jitted

    def slab_forward(self, params, tokens, slab, row: int, pos: int, n_real: int):
        """Prefill ``tokens`` into slab row ``row`` under TP (the
        per-request prefill of the batched serving path): the row runs the
        ordinary sharded forward and is written back in place."""
        jitted = self._slab_forward_jitted()
        return self._enqueue(
            jitted,
            params, jnp.asarray(tokens), slab, jnp.int32(row), jnp.int32(pos),
            jnp.int32(n_real),
        )

    # ------------------------------------------------------------------
    # Sharded prefix-cache page pool (engine.prefix_cache, zero-copy paged
    # attention): per-shard [P, page, K/tp, hd] pool halves mirror the slab
    # sharding, page tables/matched lengths are replicated host indices, and
    # every paged read/publish runs the same local program family as the
    # single-chip backend inside shard_map. PR 4 deferred this — the copy
    # design needed per-shard gather programs; zero-copy needs none.
    # ------------------------------------------------------------------

    def init_page_pool(self, n_pages: int, page: int, dtype=jnp.float32):
        from distributed_llama_tpu.ops import kv_cache as kvc

        if not self.layered:
            raise ValueError("the sharded page pool requires the layered layout")
        cfg = self.cfg
        shape = (n_pages, page, cfg.n_kv_heads, cfg.head_size)
        sharding = NamedSharding(self.mesh, self._pool_spec_layer)

        def zeros(gshape, dt):
            local = np.zeros(gshape[:2] + (gshape[2] // self.tp,) + gshape[3:], dt)
            return jax.make_array_from_callback(gshape, sharding, lambda idx: local)

        return [
            (kvc.init_half(shape, dtype, zeros=zeros),
             kvc.init_half(shape, dtype, zeros=zeros))
            for _ in range(cfg.n_layers)
        ]

    def _pool_spec(self):
        return [(self._pool_spec_layer, self._pool_spec_layer)] * self.cfg.n_layers

    def _publish_pages_jitted(self):
        key = ("publish_pages",)
        cached = self._chunk_cache.get(key)
        if cached is not None:
            return cached
        from distributed_llama_tpu.ops import kv_cache as kvc

        batch_cache_spec = [self._slab_spec] * self.cfg.n_layers

        def fn(slab, pool, page_ids, src_page, row):
            # per-shard publish of the local KV-head slice: the page size is
            # static from the local pool half's shape
            return [
                (
                    self._slab_publish(pk, k, row, src_page, page_ids),
                    self._slab_publish(pv, v, row, src_page, page_ids),
                )
                for (k, v), (pk, pv) in zip(slab, pool)
            ]

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(batch_cache_spec, self._pool_spec(), P(), P(), P()),
            out_specs=self._pool_spec(),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(1,))
        self._chunk_cache[key] = jitted
        return jitted

    def publish_pages(self, slab, pool, page_ids, src_page, row):
        """Copy slab row ``row``'s completed prefill pages into pool pages
        ``page_ids`` on every shard (each shard moves its own KV-head
        slice). The donated pool aliases in place; the slab is read-only."""
        jitted = self._publish_pages_jitted()
        return self._enqueue(
            jitted,
            slab, pool, jnp.asarray(page_ids), jnp.asarray(src_page),
            jnp.int32(row),
        )

    def _batched_chunk_paged_jitted(self, n_steps: int):
        key = ("batched_chunk_paged", n_steps)
        cached = self._chunk_cache.get(key)
        if cached is not None:
            return cached
        from distributed_llama_tpu.models import sampling

        cfg = self.cfg
        axis = self.axis
        batch_cache_spec = [self._slab_spec] * cfg.n_layers

        def fn(params, carry, cache, pool, pos, active, temperature,
               topp, topk, seeds, tables, matched):
            return sampling.batched_chunk_from_carry(
                cfg, params, carry, cache, pos, active, seeds, n_steps,
                temperature, topp, topk, axis_name=axis,
                paged=(pool, tables, matched),
            )

        V = self._vec_spec
        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, V, batch_cache_spec, self._pool_spec(),
                      V, V, V, V, V, V, self._table_spec, V),
            out_specs=(self._tok_out_spec, batch_cache_spec, V),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(1, 2))
        self._chunk_cache[key] = jitted
        return jitted

    def batched_decode_chunk_paged(
        self, params, carry, cache, pool, pos, active, n_steps,
        temperature, topp, topk, seeds, tables, matched,
    ):
        """One batched decode chunk with zero-copy prefix aliasing under
        TP: each shard's attention reads its pool half through the
        replicated page tables for positions below ``matched`` and its slab
        rows beyond — the sharded form of
        ``sampling.decode_chunk_batched_paged``."""
        jitted = self._batched_chunk_paged_jitted(int(n_steps))
        return self._enqueue(
            jitted,
            params, jnp.asarray(carry), cache, pool, jnp.asarray(pos),
            jnp.asarray(active), jnp.asarray(temperature), jnp.asarray(topp),
            jnp.asarray(topk), jnp.asarray(seeds), jnp.asarray(tables),
            jnp.asarray(matched),
        )

    def _slab_forward_paged_jitted(self):
        key = ("slab_forward_paged",)
        cached = self._chunk_cache.get(key)
        if cached is not None:
            return cached
        from distributed_llama_tpu.ops import kv_cache as kvc

        cfg = self.cfg
        axis = self.axis
        batch_cache_spec = [self._slab_spec] * cfg.n_layers

        def fn(params, tokens, slab, pool, row, pos, n_real, table, matched):
            row_cache = [
                (self._slab_row_take(k, row), self._slab_row_take(v, row))
                for k, v in slab
            ]
            logits, new_rows = llama.forward_tokens(
                cfg, params, tokens, row_cache, pos, axis_name=axis,
                n_real=n_real, paged=(pool, table, matched),
            )
            if logits.shape[-1] != cfg.vocab_size:
                logits = jax.lax.all_gather(logits, axis, axis=1, tiled=True)
            new_slab = [
                (self._slab_row_put(k, nk, row), self._slab_row_put(v, nv, row))
                for (k, v), (nk, nv) in zip(slab, new_rows)
            ]
            return logits, new_slab

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(self._specs, P(), batch_cache_spec, self._pool_spec(),
                      P(), P(), P(), P(), P()),
            out_specs=(P(), batch_cache_spec),
            check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(2,))
        self._chunk_cache[key] = jitted
        return jitted

    def slab_forward_paged(
        self, params, tokens, slab, pool, row: int, pos: int, n_real: int,
        table, matched,
    ):
        """:meth:`slab_forward` with zero-copy prefix aliasing: the row's
        suffix prefill attends over pool pages for positions below
        ``matched`` (each shard reading its own half) and the slab row
        beyond."""
        jitted = self._slab_forward_paged_jitted()
        return self._enqueue(
            jitted,
            params, jnp.asarray(tokens), slab, pool, jnp.int32(row),
            jnp.int32(pos), jnp.int32(n_real), jnp.asarray(table),
            jnp.int32(matched),
        )
