"""Expert parallelism on the virtual CPU mesh: the dispatch/combine
exchange against the dense (single-device) MoE path (the drop-free worst
case, every row of a shard on one expert, among them), the full engine
backend (--ep) against the dense engine, and a micro-benchmark against the
TP-sliced expert layout."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.config import config_from_spec
from distributed_llama_tpu.parallel.expert_parallel import ExpertParallelMoE
from tests.model_utils import random_tensors, tiny_spec, write_model_file


def _moe_setup(E=4, k=2, T=8, D=32, H=64, seed=0):
    from distributed_llama_tpu.formats.model_file import ArchType

    spec = tiny_spec(
        arch_type=ArchType.MIXTRAL, dim=D, hidden_dim=H, n_experts=E,
        n_active_experts=k, vocab_size=64, seq_len=32,
    )
    cfg = config_from_spec(spec)
    rng = np.random.RandomState(seed)
    xn = rng.randn(T, D).astype(np.float32)
    router = rng.randn(D, E).astype(np.float32) / np.sqrt(D)
    gate = rng.randn(E, D, H).astype(np.float32) / np.sqrt(D)
    up = rng.randn(E, D, H).astype(np.float32) / np.sqrt(D)
    down = rng.randn(E, H, D).astype(np.float32) / np.sqrt(H)
    return cfg, xn, router, gate, up, down


def _dense_reference(cfg, xn, router, gate, up, down):
    """The production dense MoE path (models/moe) on one device."""
    from distributed_llama_tpu.models.moe import _moe_dense

    lp = {
        "router": jnp.asarray(router),
        "moe_gate": jnp.asarray(gate),
        "moe_up": jnp.asarray(up),
        "moe_down": jnp.asarray(down),
    }
    return np.asarray(_moe_dense(cfg, jnp.asarray(xn), lp))


class TestExpertParallel:
    @pytest.mark.parametrize("ep", [2, 4])
    def test_matches_dense_moe(self, ep):
        cfg, xn, router, gate, up, down = _moe_setup()
        want = _dense_reference(cfg, xn, router, gate, up, down)
        epm = ExpertParallelMoE(cfg, ep)
        got = np.asarray(epm(xn, router, gate, up, down))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_single_device_degenerates(self):
        cfg, xn, router, gate, up, down = _moe_setup(T=4)
        want = _dense_reference(cfg, xn, router, gate, up, down)
        epm = ExpertParallelMoE(cfg, 1)
        got = np.asarray(epm(xn, router, gate, up, down))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_uneven_tokens_fall_back_to_dense_local(self):
        """T not divisible by ep cannot shard the token axis; the dense-local
        path (every shard runs its experts on all tokens + psum) must still
        produce the exact MoE output."""
        cfg, xn, router, gate, up, down = _moe_setup(T=6)
        want = _dense_reference(cfg, xn, router, gate, up, down)
        epm = ExpertParallelMoE(cfg, 4)
        got = np.asarray(epm(xn, router, gate, up, down))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_larger_expert_count(self):
        cfg, xn, router, gate, up, down = _moe_setup(E=8, k=2, T=8, seed=3)
        want = _dense_reference(cfg, xn, router, gate, up, down)
        epm = ExpertParallelMoE(cfg, 4)
        got = np.asarray(epm(xn, router, gate, up, down))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("ep", [2, 4])
    @pytest.mark.parametrize("rows_a_shard", [8, 32, 64])
    def test_a_shard_whose_rows_all_choose_one_expert_loses_none(self, rows_a_shard, ep):
        """The worst case the dispatch's buckets are sized for (``Ce = Tl``):
        a router rigged so that every row of shard ``s`` puts expert ``s`` first
        and ``s + 1`` second fills those two buckets to the last row, and
        the exchange still gives what the dense path gives."""
        from distributed_llama_tpu.models.moe import router_topk

        E = 4
        cfg, xn, router, gate, up, down = _moe_setup(E=E, T=rows_a_shard * ep, seed=11)
        shard = np.arange(xn.shape[0]) // rows_a_shard
        xn[:, :ep] = 0.0
        xn[np.arange(xn.shape[0]), shard] = 8.0
        for s in range(ep):
            router[s, s % E], router[s, (s + 1) % E] = 4.0, 2.0
        _, top_idx = router_topk(cfg, jnp.asarray(xn), jnp.asarray(router))
        np.testing.assert_array_equal(
            np.asarray(top_idx), np.stack([shard % E, (shard + 1) % E], axis=1)
        )

        want = _dense_reference(cfg, xn, router, gate, up, down)
        got = np.asarray(ExpertParallelMoE(cfg, ep)(xn, router, gate, up, down))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_benchmark_vs_tp_sliced(self, capsys):
        """Informational micro-benchmark (no assertion on timings — CPU-mesh
        wall clocks are not the TPU story): EP all-to-all routing vs the
        TP-sliced expert layout on the same 4-device mesh."""
        from jax.sharding import PartitionSpec as P
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        from distributed_llama_tpu.models.moe import moe_ffn
        
        cfg, xn, router, gate, up, down = _moe_setup(E=8, k=2, T=32, D=64, H=128)
        epm = ExpertParallelMoE(cfg, 4)

        mesh = Mesh(
            mesh_utils.create_device_mesh((4,), devices=jax.devices()[:4]), ("tp",)
        )

        def tp_body(xn_, lp_):
            return moe_ffn(cfg, xn_, lp_, "tp")

        lp_spec = {
            "router": P(), "moe_gate": P(None, None, "tp"),
            "moe_up": P(None, None, "tp"), "moe_down": P(None, "tp", None),
        }
        tp_fn = jax.jit(jax.shard_map(
            tp_body, mesh=mesh, in_specs=(P(), lp_spec), out_specs=P(),
            check_vma=False,
        ))
        lp = {
            "router": jnp.asarray(router), "moe_gate": jnp.asarray(gate),
            "moe_up": jnp.asarray(up), "moe_down": jnp.asarray(down),
        }

        np.asarray(epm(xn, router, gate, up, down))  # compile
        np.asarray(tp_fn(jnp.asarray(xn), lp))
        t0 = time.perf_counter()
        for _ in range(10):
            np.asarray(epm(xn, router, gate, up, down))
        ep_ms = (time.perf_counter() - t0) * 100
        t0 = time.perf_counter()
        for _ in range(10):
            np.asarray(tp_fn(jnp.asarray(xn), lp))
        tp_ms = (time.perf_counter() - t0) * 100
        print(f"\nEP all-to-all: {ep_ms:.2f} ms/call; TP-sliced: {tp_ms:.2f} ms/call "
              f"(4-device CPU mesh, E=8 k=2 T=32)")
        # both must at least produce the same math
        want = _dense_reference(cfg, xn, router, gate, up, down)
        np.testing.assert_allclose(
            np.asarray(epm(xn, router, gate, up, down)), want, rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(tp_fn(jnp.asarray(xn), lp)), want, rtol=2e-4, atol=2e-4
        )


def _mixtral_file(tmp_path, **over):
    from distributed_llama_tpu.formats.model_file import ArchType, HiddenAct

    spec = tiny_spec(
        arch_type=ArchType.MIXTRAL, n_experts=4, n_active_experts=2,
        hidden_act=HiddenAct.SILU, **over,
    )
    tensors = random_tensors(spec, seed=0)
    path = str(tmp_path / "mixtral.m")
    write_model_file(path, spec, tensors)
    return path


class TestExpertParallelEngine:
    """--ep as a full engine backend: prefill + decode through
    InferenceEngine on the CPU mesh must match the dense (ep=1) engine."""

    def _run(self, path, dtype, tol, **engine_kw):
        from distributed_llama_tpu.engine import InferenceEngine

        prompt = [1, 5, 9, 13, 2, 7, 30, 63]
        dense = InferenceEngine(path, dtype=dtype)
        want_prefill = dense.prefill(prompt)
        want_step = dense.decode_step(3)

        ep_engine = InferenceEngine(path, dtype=dtype, **engine_kw)
        got_prefill = ep_engine.prefill(prompt)
        got_step = ep_engine.decode_step(3)
        np.testing.assert_allclose(got_prefill, want_prefill, rtol=tol, atol=tol)
        np.testing.assert_allclose(got_step, want_step, rtol=tol, atol=tol)
        return ep_engine

    def test_engine_ep2_matches_dense(self, tmp_path):
        path = _mixtral_file(tmp_path)
        self._run(path, jnp.float32, 2e-4, ep=2)

    def test_engine_ep2_a_prompt_of_64_rows_matches_dense(self, tmp_path):
        """32 rows a shard through the engine's own dispatch: every row's
        experts answer, as in the dense engine."""
        from distributed_llama_tpu.engine import InferenceEngine

        path = _mixtral_file(tmp_path, seq_len=96)
        prompt = list(np.random.RandomState(3).randint(1, 64, 64))
        want = InferenceEngine(path, dtype=jnp.float32).forward(prompt)
        got = InferenceEngine(path, dtype=jnp.float32, ep=2).forward(prompt)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_engine_ep2_tp2_matches_dense(self, tmp_path):
        path = _mixtral_file(tmp_path)
        self._run(path, jnp.float32, 2e-4, ep=2, tp=2)

    def test_engine_ep2_q40(self, tmp_path):
        """Q40 expert banks under EP: stacked QuantizedMatrix leaves sharded
        by expert must match the q40 dense engine."""
        path = _mixtral_file(tmp_path)
        self._run(path, "q40", 5e-2, ep=2)

    def test_engine_ep_decode_chunks(self, tmp_path):
        """The jitted EP decode chunk (the serving fast path) agrees with
        the dense engine's greedy stream."""
        from distributed_llama_tpu.engine import InferenceEngine

        path = _mixtral_file(tmp_path)
        prompt = [1, 5, 9, 13]
        dense = InferenceEngine(path, dtype=jnp.float32)
        dense.prefill(prompt)
        want = list(dense.generate_chunks(7, temperature=0.0, chunk=4, limit=12))

        ep_engine = InferenceEngine(path, dtype=jnp.float32, ep=2)
        ep_engine.prefill(prompt)
        got = list(ep_engine.generate_chunks(7, temperature=0.0, chunk=4, limit=12))
        assert got == want

    def test_engine_ep_requires_moe(self, tmp_path):
        from distributed_llama_tpu.engine import InferenceEngine

        spec = tiny_spec()
        tensors = random_tensors(spec, seed=0)
        path = str(tmp_path / "llama.m")
        write_model_file(path, spec, tensors)
        with pytest.raises(ValueError, match="mixture-of-experts"):
            InferenceEngine(path, dtype=jnp.float32, ep=2)

    def test_engine_ep_sp_exclusive(self, tmp_path):
        from distributed_llama_tpu.engine import InferenceEngine

        path = _mixtral_file(tmp_path)
        with pytest.raises(ValueError, match="do not compose"):
            InferenceEngine(path, dtype=jnp.float32, ep=2, sp=2)

    def test_engine_ep_i8_cache(self, tmp_path):
        """EP composes with the quantized KV cache (QuantizedKV halves
        replicated-over-ep, tp-sharded when composed): parity within i8
        quantization noise of the dense f32-cache engine."""
        from distributed_llama_tpu.engine import InferenceEngine

        path = _mixtral_file(tmp_path)
        prompt = [1, 5, 9, 13, 2, 7]
        dense = InferenceEngine(path, dtype=jnp.float32)
        want = dense.prefill(prompt)
        ep_engine = InferenceEngine(path, dtype=jnp.float32, ep=2, cache_dtype="i8")
        got = ep_engine.prefill(prompt)
        assert ep_engine.cache[0][0].data.dtype == jnp.int8
        scale = np.abs(want).max()
        assert np.abs(got - want).max() / scale < 0.05  # i8 cache noise bound
