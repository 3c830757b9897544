"""One-process pod serving: replicas as slices of a ('data', 'model') mesh.

The reference scales by running 2^n OS processes that each hold a full
1/n weight slice and talk over TCP; our ReplicaPool (PRs 9-11)
reproduced that shape as N independent engines — N full weight copies in
HBM, batch scaling capped at process boundaries. This module is ROADMAP
item 3's alternative shape: ONE process, ONE named mesh

    ('data', 'model')  =  (replica slices, tensor-parallel shards)

with tensor parallelism riding the ``'model'`` axis inside every slice,
and the weights placed ONCE — resolved through the declarative rule
table (parallel/sharding.py) with the ``'data'`` axis never appearing in
a weight rule, so a pod serves N replicas from one params tree instead
of materializing N copies. Scale batch by widening ``'data'``, scale
model size by widening ``'model'``.

What stays exactly the same is the serving contract on top: each data
slice IS a replica — a :class:`~distributed_llama_tpu.engine.batch.
BatchScheduler` + serving lanes behind the ReplicaPool front door, with
the PR 9/10 health ladder, placement, failover-replay and
restart-supervision semantics untouched. A mesh-slice failure is a
replica loss: its in-flight requests requeue through fair admission and
replay bit-identically on surviving slices, and the supervisor rebuilds
the slice — WITHOUT reloading weights, because the pod's params tree is
shared (a rebuild is a new scheduler + lanes over the same arrays, and
the PR 10 rebuild checksum gate verifies the same bytes trivially).

Compute model: every slice's programs are the proven TP program family
(TensorParallelForward), shard_map'd over the FULL pod mesh with the
``'model'`` axis doing the work and ``'data'`` as a replication axis —
slices share ONE compiled batched-decode program (the jit caches live on
the shared backend), and greedy streams are bit-identical to the
N-independent-engines pool at the same model degree (the per-shard
programs and collective groups are the same). The honest cost under CPU
mesh mocks: a slice's dispatch occupies all data rows (replicated
compute); the N-process pool stacked all replicas on the same devices
too (the aggregate against that pool: not measured on the chip) — on
real hardware the follow-up is data-sharded slabs per dispatch.

Everything runs under ``JAX_PLATFORMS=cpu`` +
``--xla_force_host_platform_device_count`` mesh mocks, the way PR 7's TP
pool does.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.parallel import sharding
from distributed_llama_tpu.parallel.tensor_parallel import TensorParallelForward

DATA_AXIS = "data"
MODEL_AXIS = "model"

def parse_pod(spec: str) -> tuple[int, int]:
    """``--pod DATAxMODEL`` (e.g. ``2x2``) -> (data, model)."""
    m = re.fullmatch(r"(\d+)\s*[xX*]\s*(\d+)", str(spec).strip())
    if not m:
        raise ValueError(
            f"--pod wants DATAxMODEL (e.g. 2x2), got {spec!r}"
        )
    data, model = int(m.group(1)), int(m.group(2))
    if data < 1 or model < 1:
        raise ValueError(f"--pod axes must be >= 1, got {data}x{model}")
    return data, model


def pod_mesh(data: int, model: int, devices=None) -> Mesh:
    """The single named pod mesh. Slices are its rows: replica i owns
    ``mesh.devices[i, :]`` conceptually — programs are SPMD over the
    whole mesh with weights/compute invariant along ``'data'``."""
    n = data * model
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"pod {data}x{model} needs {n} devices, have {len(devices)} "
            "(CPU mocks: set --xla_force_host_platform_device_count)"
        )
    grid = mesh_utils.create_device_mesh((data, model), devices=devices[:n])
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


class PodForward(TensorParallelForward):
    """The TP program family on the pod mesh: tensor parallelism over
    ``'model'``, every spec resolved through the rule table with
    ``{"model": "model"}`` — the ``'data'`` axis never appears in a
    weight or cache rule, so arrays replicate over it and one instance
    (shared by every slice's engine) serves the whole pod with one
    compiled program per shape."""

    def __init__(
        self,
        cfg: LlamaConfig,
        data: int,
        model: int,
        devices=None,
        quantized: bool = False,
    ):
        self.data = data
        # flips on at init_batch_cache when the lane count divides 'data':
        # the slab's BATCH axis then shards across data rows, so one
        # slice's chunk dispatch does B rows of work total on the whole
        # mesh (matched with the N-engine baseline) instead of B rows
        # replicated per data row (data x the FLOPs)
        self._slab_data_sharded = False
        self._slab_rows: int | None = None
        super().__init__(
            cfg,
            model,
            quantized=quantized,
            layered=True,
            axis=MODEL_AXIS,
            mesh=pod_mesh(data, model, devices=devices),
        )

    # ------------------------------------------------------------------
    # Data-sharded slab: the batched-decode hot path parallelizes its
    # rows over 'data'; single-row ops (prefill take/put, page publish)
    # gather/scatter the owning shard's row with exact masked psums
    # (zeros elsewhere — bit-identical to the local op).
    # ------------------------------------------------------------------

    def init_batch_cache(self, b_max: int, dtype=jnp.float32):
        from jax.sharding import PartitionSpec as P

        sharded = self.data > 1 and b_max % self.data == 0
        if self._slab_rows is not None and (
            b_max != self._slab_rows or sharded != self._slab_data_sharded
        ):
            # every slice scheduler shares this backend's compiled
            # programs; a second slab layout would silently recompile
            # against the wrong specs
            raise ValueError(
                f"pod slab layout is fixed at first use: {self._slab_rows} "
                f"rows (data-sharded={self._slab_data_sharded}), got {b_max}"
            )
        if self._slab_rows is None:
            self._slab_rows = b_max
            if sharded:
                self._slab_data_sharded = True
                self._slab_spec = P(DATA_AXIS, None, MODEL_AXIS, None)
                self._vec_spec = P(DATA_AXIS)
                self._table_spec = P(DATA_AXIS, None)
                self._tok_out_spec = P(None, DATA_AXIS)
                # sub-buckets would straddle shards: dispatch the whole slab
                self.decode_bucket_floor = b_max
            elif self.data > 1:
                print(
                    f"⚠️ pod slab stays data-replicated: {b_max} lanes per "
                    f"slice do not divide data={self.data} (decode costs "
                    f"{self.data}x the FLOPs; pick --parallel divisible by "
                    "the data extent)"
                )
        return super().init_batch_cache(b_max, dtype)

    def _local_slab_shape(self, gshape: tuple) -> tuple:
        out = super()._local_slab_shape(gshape)
        if self._slab_data_sharded:
            out = (out[0] // self.data,) + out[1:]
        return out

    def _slab_row_take(self, half, row):
        """Global slab row -> a REPLICATED single-row cache half: the
        owning data shard contributes its row, everyone else exact zeros,
        one psum broadcasts it (int8 rides an int32 psum)."""
        if not self._slab_data_sharded:
            return super()._slab_row_take(half, row)
        from distributed_llama_tpu.ops import kv_cache as kvc

        Bl = half.shape[0]  # local batch rows inside shard_map
        idx = jax.lax.axis_index(DATA_AXIS)
        local = row - idx * Bl
        owned = (local >= 0) & (local < Bl)
        piece = kvc.slab_take_row(half, jnp.clip(local, 0, Bl - 1))
        if isinstance(piece, kvc.QuantizedKV):
            di = jnp.where(owned, piece.data.astype(jnp.int32), 0)
            sc = jnp.where(owned, piece.scales, jnp.zeros_like(piece.scales))
            return kvc.QuantizedKV(
                jax.lax.psum(di, DATA_AXIS).astype(piece.data.dtype),
                jax.lax.psum(sc, DATA_AXIS),
            )
        z = jnp.where(owned, piece, jnp.zeros_like(piece))
        return jax.lax.psum(z, DATA_AXIS)

    def _slab_row_put(self, half, new_row, row):
        """Write a (replicated) row half back: only the owning data shard
        keeps the update; the rest keep their rows byte-identical."""
        if not self._slab_data_sharded:
            return super()._slab_row_put(half, new_row, row)
        from distributed_llama_tpu.ops import kv_cache as kvc

        Bl = half.shape[0]
        idx = jax.lax.axis_index(DATA_AXIS)
        local = row - idx * Bl
        owned = (local >= 0) & (local < Bl)
        upd = kvc.slab_put_row(half, new_row, jnp.clip(local, 0, Bl - 1))
        if isinstance(half, kvc.QuantizedKV):
            return kvc.QuantizedKV(
                jnp.where(owned, upd.data, half.data),
                jnp.where(owned, upd.scales, half.scales),
            )
        return jnp.where(owned, upd, half)

    def _slab_publish(self, pool_half, slab_half, row, src_page, page_ids):
        """Publish a data-sharded slab row's pages into the (replicated)
        pool: gather the row once, then the ordinary local publish."""
        if not self._slab_data_sharded:
            return super()._slab_publish(
                pool_half, slab_half, row, src_page, page_ids
            )
        from distributed_llama_tpu.ops import kv_cache as kvc

        row_half = self._slab_row_take(slab_half, row)
        if isinstance(row_half, kvc.QuantizedKV):
            one = kvc.QuantizedKV(row_half.data[None], row_half.scales[None])
        else:
            one = row_half[None]
        return kvc.publish_row_pages(
            pool_half, one, 0, src_page, page_ids, pool_half.shape[1]
        )


def max_device_weight_bytes(params_trees) -> int:
    """MEASURED weight bytes on the most-loaded device across one or
    more placed params trees: walks every leaf's addressable shards and
    sums per device. This is the number the bench's memory gate reads —
    for the N-engine pool it shows N stacked copies on the shared model
    group's devices; for the pod, one model-sharded copy per data row —
    so a broken rule table (silent replication) shows up as REAL bytes,
    not as an attribution formula."""
    per_device: dict = {}
    for params in params_trees:
        for _, leaf in sharding.leaf_paths(params):
            arrays = (
                (leaf.qs, leaf.scales) if hasattr(leaf, "qs") else (leaf,)
            )
            for arr in arrays:
                shards = getattr(arr, "addressable_shards", None)
                if not shards:
                    continue
                for sh in shards:
                    d = sh.device
                    per_device[d] = per_device.get(d, 0) + int(sh.data.nbytes)
    return max(per_device.values(), default=0)


def tree_weight_bytes(params) -> int:
    """Logical resident bytes of a params tree (QuantizedMatrix counts
    its packed qs + scales). For a pod tree this is the bytes of the ONE
    shared copy; an N-engine pool holds N trees of this size."""
    total = 0
    for _, leaf in sharding.leaf_paths(params):
        qs = getattr(leaf, "qs", None)
        if qs is not None:
            total += int(qs.nbytes) + int(leaf.scales.nbytes)
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


class PodGroup:
    """One pod's shared substrate: the mesh, the backend, and the ONE
    placed params tree — plus the engine factory the serving layer's
    replica builds (and REBUILDS, after a slice death) draw slices from.

    Every engine this hands out shares ``backend`` (so compiled programs
    are built once for the whole pod) and ``params`` (so weights are
    resident once per model group). Per-slice state — slab, page pool,
    KV caches, scheduler, lanes — stays per engine, which is exactly the
    failure domain the ReplicaPool supervises."""

    def __init__(
        self,
        cfg: LlamaConfig,
        backend: PodForward,
        params: Any,
        cache_dtype=jnp.bfloat16,
        spec=None,
        dtype=jnp.bfloat16,
    ):
        self.cfg = cfg
        self.backend = backend
        self.params = params
        self.cache_dtype = cache_dtype
        self.spec = spec
        self.dtype = dtype  # the load dtype, so sibling() loads alike
        self.data = backend.data
        self.model = backend.tp
        self.weight_bytes = tree_weight_bytes(params)
        self._note_telemetry()

    # ------------------------------------------------------------------

    @classmethod
    def build(
        cls,
        model_path: str,
        data: int,
        model: int,
        dtype=jnp.bfloat16,
        max_seq_len: int | None = None,
        cache_dtype=None,
        devices=None,
        **cfg_overrides,
    ) -> "PodGroup":
        """Load the model ONCE and place it on the pod mesh through the
        rule table. The file is read per-shard exactly like the classic
        tp load (O(model/tp) matrix traffic), then placed by
        ``backend.shard_params`` — one tree for every slice, vs the
        N-engine pool's N loads + N trees."""
        from distributed_llama_tpu.engine import weights as weights_lib
        from distributed_llama_tpu.formats.model_file import ModelFileReader
        from distributed_llama_tpu.models.config import config_from_spec

        quantized = dtype == weights_lib.QUANTIZED_DTYPE
        reader = ModelFileReader(model_path)
        spec = reader.spec.clamp_seq_len(max_seq_len)
        cfg = config_from_spec(spec, **cfg_overrides)
        if cache_dtype is None:
            cache_dtype = jnp.bfloat16 if quantized else dtype
        backend = PodForward(cfg, data, model, devices=devices, quantized=quantized)
        host_params = weights_lib.load_params(
            reader, cfg, dtype=dtype, tp=model, mesh=None
        )
        reader.close()
        params = backend.shard_params(host_params)
        return cls(
            cfg, backend, params, cache_dtype=cache_dtype, spec=spec,
            dtype=dtype,
        )

    def sibling(self, model_path: str) -> "PodGroup":
        """A SECOND PodGroup over the SAME mesh/backend with a different
        weight file placed as a second params tree — the pod's blue-green
        rollout shape (ISSUE 18): slice engines cut over tree-by-tree via
        :meth:`slice_engine` on the sibling, compiled programs are reused
        (same backend, same shapes), and the OLD tree is released by
        dropping the old group when the last slice moves (the serving
        layer pops the old version's factory; JAX frees the placed
        arrays with it). The new file must match the serving config —
        same architecture, new weights."""
        from distributed_llama_tpu.engine import weights as weights_lib
        from distributed_llama_tpu.formats.model_file import ModelFileReader

        reader = ModelFileReader(model_path)
        host_params = weights_lib.load_params(
            reader, self.cfg, dtype=self.dtype, tp=self.model, mesh=None
        )
        reader.close()
        params = self.backend.shard_params(host_params)
        return PodGroup(
            self.cfg, self.backend, params,
            cache_dtype=self.cache_dtype, spec=self.spec,
            dtype=self.dtype,
        )

    def slice_engine(self):
        """A fresh slice engine over the shared backend + params: what a
        ReplicaPool replica build (or post-failure REBUILD) costs under
        the pod — scheduler + lanes + caches, never a weight reload."""
        from distributed_llama_tpu.engine.engine import InferenceEngine

        return InferenceEngine.from_shared(
            self.cfg,
            self.backend,
            self.params,
            cache_dtype=self.cache_dtype,
            spec=self.spec,
        )

    # engine_factory surface for ApiState (a zero-arg callable)
    def __call__(self):
        return self.slice_engine()

    # ------------------------------------------------------------------

    def resident_weight_bytes_per_replica(self) -> int:
        """The pod's headline memory accounting: the one shared tree's
        bytes attributed across its ``data`` slices. The N-engine pool's
        equivalent figure is the full tree PER replica (docs/PERF.md
        "One-process pod serving: weight memory")."""
        return self.weight_bytes // max(1, self.data)

    def _note_telemetry(self) -> None:
        from distributed_llama_tpu import telemetry

        tel = telemetry.MeshInstruments()
        if tel.enabled:
            tel.mesh_devices.labels(axis=DATA_AXIS).set(self.data)
            tel.mesh_devices.labels(axis=MODEL_AXIS).set(self.model)
            tel.resident_weight_bytes.labels(group="pod").set(self.weight_bytes)
            tel.resident_weight_bytes.labels(group="per_replica").set(
                self.resident_weight_bytes_per_replica()
            )
