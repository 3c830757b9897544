"""Expert parallelism: experts partitioned over an ``ep`` mesh axis with
``lax.all_to_all`` token routing — a first-class engine backend.

The production default MoE path TP-slices experts exactly like the reference
(every shard holds a 1/tp hidden-slice of ALL experts,
reference: src/transformer.cpp:335-353) — the right layout when E is small
and tokens are few (decode). TRUE expert parallelism is the named extension
beyond the reference (SURVEY.md §2 parallelism table): device d owns E/ep
WHOLE experts and tokens travel to their experts over ICI — the
dispatch/compute/combine exchange the reference's TCP star cannot express
(its MoE broadcasts every token to every node, src/grok1-tasks.cpp:121-202).

Two compute paths, chosen per batch shape inside one jitted program family:

* **Dispatch (prefill, T % ep == 0)** — the switch-transformer exchange with
  SORT-COMPACTED per-expert capacity buckets: each shard takes its T/ep
  token slice, ranks every (token, choice) pair within its target expert
  (a cumsum over the one-hot expert assignment), scatters rows into a
  ``[E, Ce, D]`` send buffer, and two ``all_to_all``s move rows to expert
  owners and outputs back. Each local expert computes ONE dense
  [ep·Ce, D] matmul — no masking in the hot compute, no Tl·k sparse slots
  (the round-4 prototype's layout). ``Ce`` is the shard's own rows, the
  drop-free worst case (a row chooses a given expert at most once), so the
  outputs are EXACT.
* **Dense-local (decode / tiny batches)** — every shard runs its El local
  experts on the (replicated) tokens, weights them with its slice of the
  router matrix, and a psum over ``ep`` combines. For T=1 this costs El
  expert-FFNs per shard in parallel — already ≤ the TP-sliced path's k
  sequential expert kernels when ep ≥ E/k — with zero all_to_alls on the
  decode critical path.

``ExpertParallelForward`` is the engine backend on a ``(tp, ep)`` mesh:
attention/dense weights shard over ``tp`` (replicated over ``ep``), expert
banks shard over BOTH (experts over ``ep``, hidden over ``tp``), the KV
cache shards over ``tp`` heads. Q40 expert banks stay 4-bit: per-expert
QuantizedMatrix leaves are stacked on a leading expert axis sharded over
``ep`` (note: on real TPU, slicing Pallas operands out of a stacked array
can make XLA hoist per-expert copies — acceptable here because EP>1 is a
multi-chip capability validated on the CPU mesh; single-chip serving uses
the TP-sliced path).

Validated against the dense MoE path on the virtual CPU mesh
(tests/test_expert_parallel.py), which also micro-benchmarks the exchange
against TP-sliced experts.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.parallel.tensor_parallel import TransferProbeMixin

def local_expert_weights(lp, e: int):
    """Weights of LOCAL expert ``e`` from EP layer params: stacked q40
    leaves (``experts_gate_up``/``experts_down`` QuantizedMatrix with a
    leading local-expert axis) or stacked bf16 banks."""
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix

    if "experts_gate_up" in lp:
        gu, dn = lp["experts_gate_up"], lp["experts_down"]
        return {
            "gate_up": QuantizedMatrix(
                gu.qs[e], gu.scales[e], gu.n_logical, gu.d_logical
            ),
            "down": QuantizedMatrix(
                dn.qs[e], dn.scales[e], dn.n_logical, dn.d_logical
            ),
        }
    return {"gate": lp["moe_gate"][e], "up": lp["moe_up"][e], "down": lp["moe_down"][e]}


def _n_local_experts(cfg: LlamaConfig, lp) -> int:
    if "experts_gate_up" in lp:
        return lp["experts_gate_up"].qs.shape[0]
    return lp["moe_gate"].shape[0]


def ep_moe_ffn(
    cfg: LlamaConfig,
    xn: jax.Array,  # [T, D] normed tokens, REPLICATED across ep
    lp,
    ep_axis: str,
) -> jax.Array:
    """Expert-parallel MoE FFN inside shard_map: expert banks in ``lp`` hold
    only this shard's E/ep experts. Returns [T, D] f32, complete over the
    expert partition (all ep collectives happen here); still a hidden-slice
    partial under TP — the caller's psum over the tp axis applies on top."""
    T = xn.shape[0]
    ep = jax.lax.psum(1, ep_axis)
    if T % ep == 0 and T >= ep and T > 1:
        return _ep_dispatch(cfg, xn, lp, ep_axis, ep)
    return _ep_dense_local(cfg, xn, lp, ep_axis, ep)


def _ep_dense_local(cfg, xn, lp, ep_axis: str, ep: int) -> jax.Array:
    """Decode/tiny-batch path: each shard computes its El local experts on
    the replicated tokens, weighted by its slice of the [T, E] router
    weights; psum over ep combines the expert partition."""
    from distributed_llama_tpu.models.moe import _expert_ffn, router_weights

    El = _n_local_experts(cfg, lp)
    idx = jax.lax.axis_index(ep_axis)
    weights = router_weights(cfg, xn, lp["router"])  # [T, E] replicated
    w_local = jax.lax.dynamic_slice(
        weights, (0, idx * El), (xn.shape[0], El)
    )  # [T, El]
    out = jnp.zeros(xn.shape, jnp.float32)
    for e in range(El):
        out = out + w_local[:, e : e + 1] * _expert_ffn(
            cfg, xn, local_expert_weights(lp, e)
        )
    return jax.lax.psum(out, ep_axis)


def _ep_dispatch(cfg, xn, lp, ep_axis: str, ep: int) -> jax.Array:
    """Prefill path: sort-compacted capacity buckets + two all_to_alls
    (dispatch/combine) + one all_gather (token re-replication). Bucket
    algebra shared with the dense bucketed prefill (models.moe)."""
    from distributed_llama_tpu.models.moe import (
        _expert_ffn,
        bucket_combine,
        bucket_rank,
        bucket_scatter,
        router_topk,
    )

    T, D = xn.shape
    E = cfg.n_experts
    El = _n_local_experts(cfg, lp)
    Tl = T // ep
    idx = jax.lax.axis_index(ep_axis)
    # a row chooses a given expert at most once, so a bucket of the shard's
    # own rows can never overflow: no row is dropped
    Ce = Tl

    x_local = jax.lax.dynamic_slice(xn, (idx * Tl, 0), (Tl, D))
    top_vals, top_idx = router_topk(cfg, x_local, lp["router"])  # [Tl, k]

    flat_e, rank, t_ids = bucket_rank(top_idx, E)
    send = bucket_scatter(x_local, flat_e, rank, t_ids, E, Ce)

    # all_to_all #1: rows travel to their expert's owner shard.
    # send viewed as [ep owners, El, Ce, D]; recv[s] = what shard s sent
    # for MY El experts
    recv = jax.lax.all_to_all(
        send.reshape(ep, El, Ce, D), ep_axis, split_axis=0, concat_axis=0
    )  # [ep, El, Ce, D]

    # local expert compute: ONE dense FFN per local expert over its
    # [ep*Ce, D] bucket — no masking, no one-hot in the hot loop
    outs = []
    for e in range(El):
        rows = recv[:, e].reshape(ep * Ce, D)
        outs.append(_expert_ffn(cfg, rows, local_expert_weights(lp, e)))  # f32
    out_banks = jnp.stack(outs)  # [El, ep*Ce, D]

    # all_to_all #2: outputs return to the rows' home shards in slot order
    back = jax.lax.all_to_all(
        out_banks.reshape(El, ep, Ce, D).transpose(1, 0, 2, 3),
        ep_axis, split_axis=0, concat_axis=0,
    )  # [ep, El, Ce, D] -> global expert order is (owner, local) = e_global
    back = back.reshape(E, Ce, D)

    # combine on the home shard
    out_local = bucket_combine(back, top_idx, rank, top_vals, Ce)  # [Tl, D] f32

    # re-replicate the token axis for the (replicated) rest of the network
    return jax.lax.all_gather(out_local, ep_axis, axis=0, tiled=True)  # [T, D]


class ExpertParallelMoE:
    """A single expert-parallel MoE FFN layer over a 1-D ``ep`` mesh: the
    test/micro-benchmark harness around :func:`ep_moe_ffn` (the engine path
    is :class:`ExpertParallelForward`). Expert banks shard over the expert
    axis; tokens dispatch with the capacity-bucket all_to_all exchange
    (T % ep == 0) or fall back to dense-local compute."""

    def __init__(self, cfg: LlamaConfig, ep: int, devices=None):
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, PartitionSpec as P


        if cfg.n_experts % ep:
            raise ValueError(f"ep={ep} must divide n_experts={cfg.n_experts}")
        if devices is None:
            devices = jax.devices()[:ep]
        self.cfg = cfg
        self.ep = ep
        self.mesh = Mesh(
            mesh_utils.create_device_mesh((ep,), devices=devices), ("ep",)
        )

        def body(xn, lp):
            return ep_moe_ffn(cfg, xn, lp, "ep")

        lp_specs = {
            "router": P(),
            "moe_gate": P("ep", None, None),
            "moe_up": P("ep", None, None),
            "moe_down": P("ep", None, None),
        }
        mapped = jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(), lp_specs), out_specs=P(),
            check_vma=False,
        )
        self._jitted = jax.jit(mapped)

    def __call__(self, xn, router, gate, up, down):
        """xn: [T, D]; banks: [E, D, H] / [E, H, D]. Returns [T, D] f32."""
        lp = {
            "router": jnp.asarray(router),
            "moe_gate": jnp.asarray(gate),
            "moe_up": jnp.asarray(up),
            "moe_down": jnp.asarray(down),
        }
        return self._jitted(jnp.asarray(xn), lp)


# ---------------------------------------------------------------------------
# Expert-parallel engine backend
# ---------------------------------------------------------------------------


def ep_param_specs(cfg: LlamaConfig, quantized: bool, shard_vocab: bool):
    """PartitionSpecs of the EP params layout on the ("tp", "ep") mesh:
    attention/dense weights follow the TP layout (replicated over ep),
    expert banks shard experts over ep AND hidden over tp. A rule-table
    lookup (parallel/sharding.py — one spec is a pytree prefix over a
    stacked QuantizedMatrix: qs [E, n2, d] + scales [E, ns, d] shard
    alike)."""
    from distributed_llama_tpu.parallel import sharding

    return sharding.param_specs(
        cfg,
        "ep_q40" if quantized else "ep",
        shard_vocab,
        {"model": "tp", "expert": "ep"},
    )


def stack_expert_leaves(host_params) -> Any:
    """Convert load_params' per-expert q40 list layout (``experts``:
    [{gate_up, down}, ...]) into the EP stacked layout
    (``experts_gate_up``/``experts_down`` QuantizedMatrix with a leading
    expert axis) — the form whose leading axis a PartitionSpec can shard
    over ``ep``. bf16 banks (moe_gate/up/down) are already stacked."""
    from distributed_llama_tpu.ops.q40 import QuantizedMatrix

    def stack(mats: list) -> QuantizedMatrix:
        return QuantizedMatrix(
            np.stack([np.asarray(m.qs) for m in mats]),
            np.stack([np.asarray(m.scales) for m in mats]),
            mats[0].n_logical,
            mats[0].d_logical,
        )

    out = dict(host_params)
    out["layers"] = []
    for lp in host_params["layers"]:
        lp = dict(lp)
        if "experts" in lp:
            experts = lp.pop("experts")
            lp["experts_gate_up"] = stack([e["gate_up"] for e in experts])
            lp["experts_down"] = stack([e["down"] for e in experts])
        out["layers"].append(lp)
    return out


class ExpertParallelForward(TransferProbeMixin):
    """Engine backend: expert parallelism over a ("tp", "ep") mesh.

    Duck-typed like TensorParallelForward/SequenceParallelForward (the
    engine's ``_tp_engine`` slot): shard_params / init_cache / forward /
    decode_loop / decode_chunk / measure_transfer_ms. Attention and dense
    weights shard over ``tp`` only; expert banks shard experts over ``ep``
    and hidden over ``tp``; the KV cache shards over ``tp`` heads and is
    replicated over ``ep`` (every shard runs the same attention — EP's
    memory win is the expert banks, which dominate a MoE model's bytes:
    Mixtral 8x7B is ~45/47 GB experts)."""

    def __init__(self, cfg: LlamaConfig, ep: int, tp: int = 1,
                 quantized: bool = False, devices=None):
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from distributed_llama_tpu.parallel.tensor_parallel import (
            validate_tp,
        )

        if not cfg.is_moe:
            raise ValueError("--ep requires a mixture-of-experts model")
        if cfg.n_experts % ep:
            raise ValueError(f"ep={ep} must divide n_experts={cfg.n_experts}")
        if tp > 1:
            validate_tp(cfg, tp, quantized=quantized)
        self.cfg = cfg
        self.ep = ep
        self.tp = tp
        self.quantized = quantized
        n_dev = tp * ep
        if devices is None:
            devices = jax.devices()[:n_dev]
        if len(devices) < n_dev:
            raise ValueError(f"need {n_dev} devices (tp*ep), have {len(devices)}")
        self.mesh = Mesh(
            mesh_utils.create_device_mesh((tp, ep), devices=devices[:n_dev]),
            ("tp", "ep"),
        )
        self._P = P
        self._NamedSharding = NamedSharding
        self.shard_vocab = tp > 1 and cfg.vocab_size % tp == 0
        self._tp_axis = "tp" if tp > 1 else None
        self._specs = ep_param_specs(cfg, quantized, self.shard_vocab)
        cache_ax = P(None, "tp", None) if tp > 1 else P(None, None, None)
        self._cache_spec = [cache_ax] * cfg.n_layers
        self._decode_cache: dict = {}

        step = jax.shard_map(
            functools.partial(_ep_forward, cfg, self._tp_axis),
            mesh=self.mesh,
            in_specs=(self._specs, P(), self._cache_spec, P()),
            out_specs=(P(), self._cache_spec),
            check_vma=False,
        )
        self._jitted = jax.jit(step, donate_argnums=(2,))

    # -- engine interface ---------------------------------------------------

    def shard_params(self, host_params):
        from distributed_llama_tpu.parallel.tensor_parallel import place_params

        if self.quantized:
            host_params = stack_expert_leaves(host_params)
        return place_params(host_params, self._specs, self.mesh)

    def init_cache(self, dtype=jnp.float32):
        from distributed_llama_tpu.ops import kv_cache as kvc

        cfg = self.cfg
        shape = (cfg.seq_len, cfg.n_kv_heads, cfg.head_size)
        sharding = self._NamedSharding(self.mesh, self._cache_spec[0])

        def zeros(gshape, dt):
            local = np.zeros((gshape[0], gshape[1] // self.tp) + gshape[2:], dt)
            return jax.make_array_from_callback(gshape, sharding, lambda idx: local)

        return [
            (kvc.init_half(shape, dtype, zeros=zeros),
             kvc.init_half(shape, dtype, zeros=zeros))
            for _ in range(cfg.n_layers)
        ]

    def forward(self, params, tokens, cache, pos):
        return self._jitted(params, jnp.asarray(tokens), cache, jnp.asarray(pos))

    def decode_loop(
        self, params, first_token, cache, pos, n_steps, temperature, topp,
        seed: int = 0, topk: int = 0,
    ):
        from distributed_llama_tpu import prng

        tokens, cache = self._decode_scan(
            int(n_steps), float(temperature), float(topp), int(topk)
        )(
            params, jnp.asarray(first_token), cache, jnp.asarray(pos),
            jnp.uint32(prng.fold_seed(seed)),
        )
        return tokens, cache

    def decode_chunk(
        self, params, first_token, cache, pos, n_steps, temperature, topp,
        topk, seed32,
    ):
        jitted = self._decode_scan(int(n_steps), None, None, None)
        return jitted(
            params, jnp.asarray(first_token), cache, jnp.asarray(pos),
            jnp.float32(temperature), jnp.float32(topp), jnp.int32(topk),
            jnp.asarray(seed32, jnp.uint32),
        )

    def _decode_scan(self, n_steps: int, temperature, topp, topk):
        from distributed_llama_tpu.models import sampling

        P = self._P
        key_ = (n_steps, temperature, topp, topk)
        cached = self._decode_cache.get(key_)
        if cached is not None:
            return cached
        cfg = self.cfg
        tp_axis = self._tp_axis

        def scan_body(params, first_token, cache, pos, seed, t, p, k_top):
            def step(carry, _):
                token, cache_c, pp = carry
                logits, cache_c = _ep_forward(cfg, tp_axis, params, token[None], cache_c, pp)
                nxt = sampling.sample_token(logits[0], seed, pp, t, p, k_top)
                return (nxt, cache_c, pp + 1), nxt

            (_, cache, _), tokens = jax.lax.scan(
                step, (first_token.astype(jnp.int32), cache, pos.astype(jnp.int32)),
                None, length=n_steps,
            )
            return tokens, cache

        if temperature is None:

            def fn(params, first_token, cache, pos, t_in, p_in, k_in, seed):
                return scan_body(
                    params, first_token, cache, pos, seed, t_in, p_in, k_in
                )

            in_specs = (self._specs, P(), self._cache_spec, P(), P(), P(), P(), P())
        else:

            def fn(params, first_token, cache, pos, seed):
                return scan_body(
                    params, first_token, cache, pos, seed, temperature, topp,
                    topk,
                )

            in_specs = (self._specs, P(), self._cache_spec, P(), P())
        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=(P(), self._cache_spec), check_vma=False,
        )
        jitted = jax.jit(mapped, donate_argnums=(2,))
        self._decode_cache[key_] = jitted
        return jitted

    def transfer_probe(self, n_tokens: int = 32):
        """Replay of the EP decode's per-layer collective sequence: one
        ep-psum of the [1, dim] expert-partition partial (plus the two tp
        all-reduces and the vocab all-gather when composed with TP).
        Keep-alive arithmetic prevents XLA DCE (see TransferProbeMixin)."""
        cfg = self.cfg
        tp_axis = self._tp_axis
        P = self._P

        def token_step(carry, _):
            x, z = carry

            def layer(c, _):
                xx, zz = c
                xx = jax.lax.psum(xx, "ep") * 0.5
                if tp_axis is not None:
                    zz = jax.lax.psum(zz, tp_axis) * 0.5
                    zz = jax.lax.psum(zz, tp_axis) * 0.5
                return (xx, zz), None

            (x, z), _ = jax.lax.scan(layer, (x, z), None, length=cfg.n_layers)
            return (x, z), None

        def fn(x, z):
            (x, z), _ = jax.lax.scan(token_step, (x, z), None, length=n_tokens)
            return x, z

        mapped = jax.shard_map(
            fn, mesh=self.mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
        x = jnp.ones((1, cfg.dim), jnp.float32)
        z = jnp.ones((1, cfg.dim), jnp.float32)
        return jax.jit(mapped), (x, z)

    def transfer_bytes_per_token(self) -> int:
        """The probed EP decode sequence per layer: one ep-psum of the
        [1, dim] expert-partition partial, plus the two [1, dim] tp
        all-reduces when composed with TP (see :meth:`transfer_probe`)."""
        per_layer = self.cfg.dim * 4
        if self._tp_axis is not None:
            per_layer += 2 * self.cfg.dim * 4
        return self.cfg.n_layers * per_layer


def _ep_forward(cfg, tp_axis, params, tokens, cache, pos):
    """Per-shard forward body on the (tp, ep) mesh: the shared llama wiring
    with ep_axis="ep" threading expert banks through the EP exchange."""
    from distributed_llama_tpu.models import llama

    logits, new_cache = llama.forward_tokens(
        cfg, params, tokens, cache, pos, axis_name=tp_axis, ep_axis="ep"
    )
    if tp_axis is not None and logits.shape[-1] != cfg.vocab_size:
        logits = jax.lax.all_gather(logits, tp_axis, axis=1, tiled=True)
    return logits, new_cache
