"""One-process pod serving on the ('data','model') mesh (ISSUE 15).

* Greedy streams from the pod are bit-identical to N independent
  engines at the same model degree (the N-process ReplicaPool shape) —
  and to the real ``--tp`` backend the pool would run.
* One params tree: every slice engine shares the SAME placed arrays
  (the N x weight-copy tax is gone), the rebuild path never reloads
  weights, and the resident-bytes accounting divides by the slice count.
* Mesh-slice death IS a replica loss: a chaos-killed slice's victims
  replay bit-identically on surviving slices through the untouched
  PR 9/10 ladder, and the supervisor rebuilds the slice from the shared
  substrate.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu import retry, telemetry
from distributed_llama_tpu.engine import InferenceEngine, faults
from distributed_llama_tpu.parallel import pod as pod_lib
from distributed_llama_tpu.parallel.pod import PodGroup, parse_pod, tree_weight_bytes
from distributed_llama_tpu.server.api import ApiState

from tests.model_utils import random_tensors, tiny_spec, write_model_file
from tests.test_faults import post_raw, serve_state
from tests.test_fair_sched import SseStream


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("pod")
    spec = tiny_spec(seq_len=96)
    path = str(tmp / "m.m")
    write_model_file(path, spec, random_tensors(spec, seed=7))
    return path


class TestPodMechanics:
    def test_parse_pod(self):
        assert parse_pod("2x2") == (2, 2)
        assert parse_pod("4X1") == (4, 1)
        assert parse_pod("1*8") == (1, 8)
        with pytest.raises(ValueError):
            parse_pod("2x")
        with pytest.raises(ValueError):
            parse_pod("0x2")

    def test_pod_needs_enough_devices(self, model_path):
        with pytest.raises(ValueError, match="devices"):
            PodGroup.build(model_path, 8, 4, dtype=jnp.float32)

    def test_pod_rejects_composition_with_tp(self, model_path):
        from distributed_llama_tpu.apps.cli import make_pod_group

        args = types.SimpleNamespace(
            pod="2x2", tp=2, sp=1, ep=1, model=model_path, tokenizer="x",
            dtype="f32", cache_dtype="auto", max_seq_len=None,
            temperature=0.0, topp=0.9, topk=0, seed=1,
        )
        with pytest.raises(SystemExit):
            make_pod_group(args)


class TestPodSharedSubstrate:
    def test_one_params_tree_across_slices_and_rebuilds(self, model_path):
        group = PodGroup.build(model_path, 2, 2, dtype=jnp.float32)
        e1, e2 = group.slice_engine(), group()
        # the tentpole memory property: the SAME arrays, not N copies
        assert e1.params is group.params and e2.params is group.params
        assert e1._tp_engine is e2._tp_engine is group.backend
        # the PR 10 rebuild checksum gate holds trivially: same bytes
        assert e1.weights_checksum() == e2.weights_checksum()
        # accounting: one tree attributed across the data slices
        assert group.weight_bytes == tree_weight_bytes(group.params) > 0
        assert group.resident_weight_bytes_per_replica() == group.weight_bytes // 2

    def test_slices_share_compiled_programs(self, model_path):
        group = PodGroup.build(model_path, 2, 2, dtype=jnp.float32)
        e1, e2 = group.slice_engine(), group.slice_engine()
        s1, s2 = e1.default_stream, e2.new_stream()
        s1.prefill([1, 2, 3])
        t1 = s1.generate_on_device(4, 6, temperature=0.0)
        compiled_after_first = dict(group.backend._decode_cache)
        s2.prefill([1, 2, 3])
        t2 = s2.generate_on_device(4, 6, temperature=0.0)
        # the second slice reused the pod's jitted program (no new keys)
        assert dict(group.backend._decode_cache) == compiled_after_first
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))

    def test_mesh_telemetry_gauges(self, model_path):
        telemetry.reset()
        telemetry.enable()
        try:
            group = PodGroup.build(model_path, 2, 2, dtype=jnp.float32)
            group.slice_engine()
            text = telemetry.prometheus_text()
            assert 'dllama_mesh_devices{axis="data"} 2' in text
            assert 'dllama_mesh_devices{axis="model"} 2' in text
            assert 'dllama_resident_weight_bytes{group="pod"}' in text
            assert (
                f'dllama_resident_weight_bytes{{group="per_replica"}} '
                f"{group.resident_weight_bytes_per_replica()}" in text
            )
        finally:
            telemetry.disable()
            telemetry.reset()


class TestPodParity:
    """Greedy bit-parity: the acceptance criterion's decode-equivalence
    half (the serving/failover half is TestPodSliceFailover)."""

    PROMPT = [1, 2, 3, 4, 5]

    def _greedy(self, engine, steps=16):
        s = engine.default_stream
        s.prefill(self.PROMPT)
        return np.asarray(s.engine.generate_on_device(6, steps, temperature=0.0))

    def test_pod_matches_n_independent_engines(self, model_path):
        """data=2 x model=2 pod vs TWO independent engines each holding
        their own full (model=2-sharded) weight copy — the one-process
        mesh vs the N-engines ReplicaPool shape, bit-identical."""
        group = PodGroup.build(model_path, 2, 2, dtype=jnp.float32)
        want = self._greedy(group.slice_engine())
        # N independent single-row groups = N engines with OWN params
        lone = [PodGroup.build(model_path, 1, 2, dtype=jnp.float32)
                for _ in range(2)]
        assert lone[0].params is not lone[1].params
        for g in lone:
            np.testing.assert_array_equal(self._greedy(g.slice_engine()), want)

    def test_pod_chunked_decode_matches_loop(self, model_path):
        group = PodGroup.build(model_path, 2, 2, dtype=jnp.float32)
        e = group.slice_engine()
        want = self._greedy(group.slice_engine())
        s = e.default_stream
        s.prefill(self.PROMPT)
        got = list(s.generate_chunks(6, temperature=0.0, chunk=5, limit=s.pos + 16))
        np.testing.assert_array_equal(np.asarray(got[:16]), want)

    def test_pod_matches_tp_replica_pool_backend(self, model_path):
        """Pod slices vs the REAL --tp backend the N-process ReplicaPool
        runs (tp=2 == model=2): bit-identical greedy streams."""
        etp = InferenceEngine(model_path, dtype=jnp.float32, tp=2)
        want = self._greedy(etp)
        group = PodGroup.build(model_path, 2, 2, dtype=jnp.float32)
        np.testing.assert_array_equal(self._greedy(group.slice_engine()), want)


# ----------------------------------------------------------------------
# Serving-level: mesh-slice death IS a replica loss (the PR 9 contract
# on the pod substrate), over real HTTP
# ----------------------------------------------------------------------


def make_pod_state(tmp_path, name, *, data=2, model=2, parallel=2,
                   max_seq=192, **extra):
    """A pod-backed ApiState: replicas are slices of ONE ('data','model')
    mesh sharing one params tree; the group is the engine factory, so a
    post-failover rebuild hands out a fresh slice over the same weights."""
    from distributed_llama_tpu.formats.tokenizer_file import (
        TokenizerData,
        write_tokenizer_file,
    )
    from distributed_llama_tpu.tokenizer import Sampler, Tokenizer

    from tests.test_tokenizer import make_sentencepiece_like_tokenizer

    base = make_sentencepiece_like_tokenizer()
    spec = tiny_spec(seq_len=max_seq, vocab_size=base.vocab_size)
    model_file = str(tmp_path / f"{name}.m")
    write_model_file(model_file, spec, random_tensors(spec, seed=0))
    data_t = TokenizerData(
        vocab=base.vocab, scores=base.scores, bos_id=1, eos_id=2,
        chat_eos_id=2,
        chat_template="{{bos_token}}{% for m in messages %}<|im_start|>...{% endfor %}",
    )
    tok_path = str(tmp_path / f"{name}.t")
    with open(tok_path, "wb") as f:
        write_tokenizer_file(f, data_t)
    group = PodGroup.build(model_file, data, model, dtype=jnp.float32)
    tokenizer = Tokenizer.from_file(tok_path)
    sampler = Sampler(
        vocab_size=spec.vocab_size, temperature=0.0, topp=0.9, seed=1
    )
    args = types.SimpleNamespace(
        temperature=0.0, topp=0.9, seed=1, chat_template=None,
        parallel=parallel, replicas=data, batch_decode=True,
        decode="device", decode_chunk=4, replica_restart_backoff_s=0.05,
        **extra,
    )
    state = ApiState(
        group.slice_engine(), tokenizer, sampler, args, engine_factory=group
    )
    state.pool.restart_policy = retry.BackoffPolicy(
        attempts=retry.UNBOUNDED, base_s=0.05
    )
    return state, group


def _one_long_prompt(url, min_tokens=24):
    for cand in (
        "tell me a very long story",
        "alpha bravo charlie delta echo",
        "hello world hello world",
        "the quick brown fox jumps",
        "one two three four five six",
    ):
        status, _, body = post_raw(
            url, {"messages": [{"role": "user", "content": cand}],
                  "max_tokens": 96},
        )
        assert status == 200
        if body["usage"]["completion_tokens"] >= min_tokens:
            return cand, body["choices"][0]["message"]["content"]
    raise AssertionError("no candidate prompt streams long enough")


_SLOW = "batch.fetch:kind=delay,delay_ms=25,count=-1"


@pytest.mark.chaos
class TestPodSliceFailover:
    def test_slice_kill_mid_decode_replays_bit_identical_and_rebuilds(
        self, tmp_path
    ):
        """The pod acceptance test: 4 streams across 2 mesh slices, slice
        0 chaos-killed mid-decode — victims replay byte-identically on
        the surviving slice, the supervisor rebuilds the dead slice FROM
        THE SHARED SUBSTRATE (no weight reload: the rebuilt engine holds
        the same params object), and the rebuilt slice serves again."""
        clean, _ = make_pod_state(tmp_path, "clean")
        assert len(clean.pool.replicas) == 2
        url, server = serve_state(clean)
        try:
            prompt, baseline = _one_long_prompt(url)
            _, _, b8 = post_raw(
                url, {"messages": [{"role": "user", "content": prompt}],
                      "max_tokens": 8},
            )
            baseline8 = b8["choices"][0]["message"]["content"]
        finally:
            server.shutdown()
            clean.pool.close()

        faults.install(faults.parse(
            f"replica.crash:kind=raise,row=0,after=16,count=1;{_SLOW}"
        ))
        state, group = make_pod_state(tmp_path, "chaos")
        url, server = serve_state(state)
        try:
            body = {"messages": [{"role": "user", "content": prompt}],
                    "max_tokens": 96}
            streams = [SseStream(url, dict(body)) for _ in range(4)]
            texts = [s.read_first_delta() + s.read_rest() for s in streams]
            assert all(s.error_type is None for s in streams), [
                s.error_type for s in streams
            ]
            # every stream — survivors AND replayed victims — matches the
            # uncontended baseline byte for byte
            assert texts == [baseline] * 4
            pool = state.pool
            assert pool.failovers_total == 1
            assert pool.last_failover_victims == 2
            assert pool.replayed_total == pool.last_failover_victims
            # the slice comes back...
            from distributed_llama_tpu.server.replicas import HEALTHY

            assert pool.wait_state(0, HEALTHY, timeout_s=60)
            assert pool.restarts_total == 1
            # ...WITHOUT reloading weights: the rebuilt engine shares the
            # pod's one params tree (the tentpole property, preserved
            # through the failure path)
            assert pool.replicas[0].engine.params is group.params
            # ...and serves again
            for s in pool.replicas[1].slots:
                s.busy = True
            try:
                status, _, body2 = post_raw(
                    url, {"messages": [{"role": "user", "content": prompt}],
                          "max_tokens": 8},
                )
                assert status == 200
                assert body2["choices"][0]["message"]["content"] == baseline8
            finally:
                for s in pool.replicas[1].slots:
                    s.busy = False
        finally:
            server.shutdown()
            state.pool.close()
