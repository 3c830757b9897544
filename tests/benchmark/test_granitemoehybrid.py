"""Granite-4.0-H at a toy size on the CPU: the program's engine against the
family's plain reference (a token-by-token recurrence, no cache) through every
path a served row takes (prefill, prefill in pieces, decode through the slab,
rows of different lengths in one bucket, a bucket with a masked row, a prefix
hit that resumes from a state snapshot), what refuses by name, and that each
multiplier and each piece of the state-space mathematics is load-bearing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_tiny
import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.harness.cell import load_check
from benchmark.reference.qfile import QFile
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.models import llama
from distributed_llama_tpu.ops import kda, ssd

CONFIG = granite_tiny.CONFIG
PAGE = 8
SEED = 2**31 + 3
# float32 against float32, the chunked form against the recurrence: what is left is the order
# of float32 sums (measured 1e-6 to 2e-6 of max|logit|)
TOL = 2e-5
RNG = np.random.default_rng(11)
PROMPT = RNG.integers(300, 16000, 45).tolist()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    return modelfile.write_artifacts(CONFIG, SEED, str(tmp_path_factory.mktemp("granite")), 512)[0]


@pytest.fixture(scope="module")
def reference(model):
    ref = families.load(CONFIG, "reference")
    qf = QFile(model, ref)
    return lambda tokens: ref.forward(qf, np.asarray([tokens], np.int32), np.arange(len(tokens)))[0]


@pytest.fixture(scope="module")
def engine(model):
    return InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32)


def scheduler(engine, rows=2, prefill_chunk=0, kv_pages=32, **kw):
    return BatchScheduler(engine, n_rows=rows, chunk=4, prefix_cache=True, kv_pages=kv_pages,
                          page_size=PAGE, prefill_chunk=prefill_chunk, **kw)


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def decode(stream, logits_row, n):
    """Greedy decode of ``n`` tokens after a prefill that returned ``logits_row``."""
    toks = []

    def on_token(prev, tok):
        toks.append(tok)
        return len(toks) < n

    stream.stream_decode(int(np.argmax(logits_row)), on_token, 0.0, 0.9, seed=1,
                         limit=stream.pos + n,
                         first_prev=int(stream._history[-1]) if stream._history else 0)
    return toks


def deficits(reference, prompt, answer):
    """Teacher-forced, as the benchmark's check does it: how far each served
    token lies below the reference's best for the same context, as a share
    of max|logit|."""
    rows = reference(prompt + answer)[len(prompt) - 1:-1]
    return [float(r.max() - r[t]) / float(np.abs(r).max()) for r, t in zip(rows, answer)]


def test_the_file_says_what_the_configuration_says(model, engine):
    from distributed_llama_tpu.formats.model_file import ArchType, read_spec

    spec, cfg = read_spec(model), engine.cfg
    assert spec.arch_type == ArchType.GRANITE_HYBRID
    assert [cfg.layer_kind(l) for l in range(10)] == \
        [("ssm", "dense")] * 5 + [("full", "dense")] + [("ssm", "dense")] * 4
    assert (cfg.embed_scale, cfg.residual_scale, cfg.softmax_scale, cfg.logits_divisor) == (12.0, 0.22, 0.125, 8.0)
    assert cfg.softmax_scale != cfg.head_size ** -0.5 and not cfg.use_rope
    assert cfg.is_recurrent and cfg.state_mixer == "ssm" and cfg.kv_read_kinds == ("full",)
    # nine layers of state [2 groups, 16, 4 heads x 32] and a tail of 3 x (256 + 32) a row
    assert llama.recurrent_state_bytes(cfg, 3) == 3 * 9 * 4 * (8 * 32 * 16 + 3 * 288)
    assert llama.init_batch_cache(cfg, 3)[0]["S"].shape == (3, 2, 16, 128)
    # the head is the embedding's matrix (Q40 of it), but for the rows an answer never holds
    reader = families.load(CONFIG, "reference")
    qf = QFile(model, reader)
    from benchmark.reference.ops import dequant
    from benchmark.harness.traffic import FIRST_FILLER_ID

    head, emb = np.asarray(dequant(qf.raw("wcls"))), qf.f32("embedding")
    assert not head[:FIRST_FILLER_ID].any() and emb[:FIRST_FILLER_ID].any()
    # Q40 keeps a block's values to an eighth of its largest: the same matrix, to that rounding
    assert np.abs(head[FIRST_FILLER_ID:] - emb[FIRST_FILLER_ID:]).max() <= np.abs(emb).max() / 8


@pytest.mark.parametrize("case", ["prefill alone", "prefill in two pieces",
                                  "prefill then decode through the slab",
                                  "rows of different lengths in one bucket",
                                  "a bucket with a masked row"])
def test_engine_against_the_reference(engine, reference, case):
    want = reference(PROMPT)
    if case == "prefill alone":
        assert off(engine.new_stream().prefill(PROMPT), want[-1]) <= TOL
        return
    sched = scheduler(engine, prefill_chunk=32 if case == "prefill in two pieces" else 0)
    s0, s1 = sched.new_stream(), sched.new_stream()
    got = s0.prefill(PROMPT)  # 45 tokens: a piece of 32 and one of 13 padded to 16
    assert off(got, want[-1]) <= TOL
    if case == "prefill in two pieces":
        # ... and what is decoded from the handed-over state and tail is the reference's
        assert max(deficits(reference, PROMPT, decode(s0, got, 6))) <= TOL
        return
    if case == "prefill then decode through the slab":
        assert max(deficits(reference, PROMPT, decode(s0, got, 10))) <= TOL
        return
    other = RNG.integers(300, 16000, 30).tolist()
    got1 = s1.prefill(other)
    if case == "rows of different lengths in one bucket":
        import threading

        # both rows decode in the same chunks, at positions 45.. and 30..
        answers = {}
        threads = [threading.Thread(target=lambda s=s, g=g, k=k: answers.__setitem__(k, decode(s, g, 9)))
                   for k, (s, g) in enumerate(((s0, got), (s1, got1)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert max(deficits(reference, PROMPT, answers[0])) <= TOL
        assert max(deficits(reference, other, answers[1])) <= TOL
        return
    # row 1 holds a state of its own while row 0 decodes in a bucket of two
    assert max(deficits(reference, PROMPT, decode(s0, got, 9))) <= TOL
    # ... and row 1's state was not touched by the chunks it sat out
    assert max(deficits(reference, other, decode(s1, got1, 6))) <= TOL


def test_q40_engine_stays_inside_the_benchmarks_logit_tolerance(model, reference):
    # token by token, so that every position is compared; a dense model: the Q80 rounding of the
    # activations into every Q40 matmul is all that separates the two. At a width of 64 a rounding
    # is averaged over 32 times fewer terms than at 2048 and ten layers carry it on: the worst
    # logit of 16384 lies 1.2e-2 to 1.6e-2 of max|logit| off at the median position, 2.9e-2 at
    # the worst of 45 (measured); the limit is the benchmark's at the median, twice it at the worst
    want = reference(PROMPT)
    stream = InferenceEngine(model, dtype="q40").new_stream()
    offs = [off(stream.prefill([tok]), want[i]) for i, tok in enumerate(PROMPT)]
    tol = load_check()["logit_tol"]
    assert np.median(offs) <= tol and max(offs) <= 2 * tol, offs


def test_a_prefix_hit_resumes_from_a_snapshot_and_falls_back_to_an_earlier_one(engine, reference):
    sched = scheduler(engine, kv_pages=64)  # four snapshot slots: the three taken here stay
    s0, s1 = sched.new_stream(), sched.new_stream()
    tail = lambda n: RNG.integers(300, 16000, n).tolist()
    p1 = PROMPT[:2 * PAGE] + tail(3)  # publishes 2 pages, snapshot where page 2 ends
    assert off(s0.prefill(p1), reference(p1)[-1]) <= TOL and s0.matched_len == 0
    p2 = p1[:2 * PAGE] + tail(2 * PAGE + 5)  # resumes at 2 pages, publishes 4, snapshot at 4
    s1.prefill(p2)
    assert s1.matched_len == 2 * PAGE
    s0.reset()
    got = s0.prefill(p2)
    # state, tail AND the attention layer's pages were resumed: the cold run's logits
    assert s0.matched_len == 4 * PAGE and off(got, reference(p2)[-1]) <= TOL
    # three pages of p2 match, the third has no snapshot: back to the second
    s0.reset()
    p3 = p2[:3 * PAGE] + tail(6)
    got = s0.prefill(p3)
    assert s0.matched_len == 2 * PAGE and off(got, reference(p3)[-1]) <= TOL
    assert max(deficits(reference, p3, decode(s0, got, 5))) <= TOL
    sched.check_prefix()


@pytest.mark.parametrize("what", ["rollback", "spill", "--spec-draft", "--tp 2"])
def test_paths_that_move_a_row_by_position_refuse_by_name(engine, model, what):
    with pytest.raises(llama.RecurrentStateError, match="GRANITE_HYBRID.*state-space"):
        if what == "rollback":
            s = scheduler(engine).new_stream()
            s.prefill(PROMPT)
            s.rollback(10)
        elif what == "spill":
            scheduler(engine, host_spill_bytes=1 << 20)
        elif what == "--spec-draft":
            scheduler(engine, spec_draft=4)
        else:
            InferenceEngine(model, dtype=jnp.float32, tp=2)
    # a rewind to the start is a reset, not a refusal
    s = scheduler(engine).new_stream()
    s.prefill(PROMPT)
    s.rollback(0)
    assert s.pos == 0


def test_the_accepted_archs_keep_their_softmax_scale_and_no_multiplier(tmp_path):
    """The softmax scale is a value of the config: ``head_size ** -0.5`` for
    every file that states none, and no accepted program multiplies by a
    scale of one (``test_bench_pins`` holds the old files' bytes)."""
    from distributed_llama_tpu.formats.model_file import HeaderKey, _header_pairs, read_spec
    from distributed_llama_tpu.models.config import config_from_spec

    path, _ = modelfile.write_artifacts(tiny_root.CONFIGS["tiny-dense"], 7, str(tmp_path), 512)
    old = read_spec(path)
    assert max(int(k) for k, _ in _header_pairs(old)) < HeaderKey.HEAD_SIZE
    cfg = config_from_spec(old)
    assert (cfg.attn_scale, cfg.softmax_scale) == (0.0, cfg.head_size ** -0.5)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logits_divisor) == (1.0, 1.0, 1.0)
    assert not cfg.is_recurrent and cfg.state_mixer is None and cfg.kv_read_kinds == ()


# a file that says a multiplier is one (what a program that dropped it computes), same weights
DROPPED = {"the embedding multiplier": {"embedding_multiplier": 1},
           "the residual multiplier": {"residual_multiplier": 1},
           "the logits' divisor": {"logits_scaling": 1},
           "the softmax scale of 1/8 (16 ** -0.5 = 1/4 instead)": {"attention_multiplier": 0.25}}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_dropping_a_multiplier_fails_the_tolerance(tmp_path, reference, what):
    """The engine on a file whose header states the default where the model
    states its multiplier, against the reference on the model's own file:
    same seed, same weights, one number different."""
    path = modelfile.write_model(str(tmp_path / "dropped.m"), {**CONFIG, **DROPPED[what]}, 512, SEED)
    got = InferenceEngine(path, dtype=jnp.float32, cache_dtype=jnp.float32).prefill(PROMPT)
    # not by float32's tolerance, and not by the benchmark's for the served Q40 path either
    assert off(got, reference(PROMPT)[-1]) > load_check()["logit_tol"]
    if what.startswith("the logits"):
        # the one a greedy token cannot see: the same answer, eight times the logits
        want = reference(PROMPT)[-1]
        assert int(np.argmax(got)) == int(np.argmax(want)) and off(np.asarray(got) / 8, want) <= TOL


def _without(monkeypatch, piece):
    """Take one piece of the state-space mathematics out of the program."""
    if piece == "the decay":
        real = ssd.ssd_chunk
        monkeypatch.setattr(ssd, "ssd_chunk", lambda S, x, B, C, dt, a, n=None: real(S, x, B, C, dt, 0 * a, n))
    elif piece == "dt's bias":
        real = llama._ssm_heads
        monkeypatch.setattr(llama, "_ssm_heads", lambda cfg, lp, xbc, dt: real(
            cfg, {**lp, "dt_bias": 0 * lp["dt_bias"]}, xbc, dt))
    elif piece == "the conv":
        monkeypatch.setattr(kda, "causal_conv", lambda x, tail, taps, n=None: (x, tail))
    elif piece == "the conv's bias":
        real = llama._ssm_heads
        monkeypatch.setattr(llama, "_ssm_heads", lambda cfg, lp, xbc, dt: real(
            cfg, {**lp, "conv_bias": 0 * lp["conv_bias"]}, xbc, dt))
    elif piece == "the skip D":
        real = llama._ssm_output
        monkeypatch.setattr(llama, "_ssm_output", lambda cfg, lp, y, x, z: real(
            cfg, {**lp, "ssm_d": 0 * lp["ssm_d"]}, y, x, z))
    elif piece == "the gate before the norm (after it instead)":
        monkeypatch.setattr(llama, "_ssm_output", lambda cfg, lp, y, x, z: llama.rmsnorm(
            (y + lp["ssm_d"][None, :, None] * x).reshape(z.shape), lp["ssm_norm"]) * jax.nn.silu(z))
    elif piece == "the recurrence (the skip alone)":
        real = ssd.ssd_chunk
        monkeypatch.setattr(ssd, "ssd_chunk", lambda S, x, B, C, dt, a, n=None: (
            0 * x, real(S, x, B, C, dt, a, n)[1]))
    else:
        raise ValueError(piece)


@pytest.mark.parametrize("piece", ["the decay", "dt's bias", "the conv", "the conv's bias", "the skip D",
                                   "the gate before the norm (after it instead)",
                                   "the recurrence (the skip alone)"])
def test_leaving_a_piece_of_the_mathematics_out_fails_the_tolerance(model, reference, monkeypatch, piece):
    _without(monkeypatch, piece)
    jax.clear_caches()
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32).prefill(PROMPT)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert off(got, reference(PROMPT)[-1]) > load_check()["logit_tol"]


def test_the_seeded_answer_is_the_layers_and_not_the_tied_heads_echo(engine, reference):
    """A tied head scores the token just fed by |E_t|^2; the family draws the
    embedding small so that the greedy answer does not repeat its last token
    whatever the layers compute (``families/granitemoehybrid/modelfile.py``)."""
    stream = scheduler(engine).new_stream()
    answer = decode(stream, stream.prefill(PROMPT), 24)
    repeats = sum(a == b for a, b in zip(answer, answer[1:]))
    assert repeats <= 2 and len(set(answer)) >= 20, answer


def test_the_states_precision_is_held_in_the_logits(model, monkeypatch):
    """``tools/ssd_state_witness.py`` at the toy size: pieces that hand the
    state on, then decode steps through a slab, in float32, every logit held
    to the reference. The program as it is reads float32's rounding; a state
    kept in bfloat16, which no rule over greedy tokens sees
    (``test_granite_control.py``), reads over the limit, as on the chip (1.5e-4
    against 9.3e-3 to 4.5e-2 at the published widths: the tool's header)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "ssd_state_witness", os.path.join(tiny_root.REPO, "tools", "ssd_state_witness.py"))
    witness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(witness)
    monkeypatch.setattr(witness, "PIECE", 32)
    try:
        served, planted = witness.run(CONFIG, model, ["served", "state_bf16"], rows=2, prompt=96,
                                      steps=64, every=16, seed=7)
    finally:
        jax.clear_caches()
    assert served["ok"] and max(served["after_prompt"], *served["by_step"].values()) <= TOL
    assert not planted["ok"] and max(planted["by_step"].values()) > 2 * witness.LIMIT
    assert ssd.ssd_step.__name__ == "ssd_step"  # the witness put the program's own back


def test_the_cell_runs_through_the_harness_on_the_cpu(tmp_path, monkeypatch):
    """The toy configuration as a cell of the miniature checkout, through
    ``run_cell`` with ``--trace 2``: the family's builder, the server child,
    the probes judged by the family's reference (their prompts cross a
    prefill piece, so state and tail are handed on), warm-up, window, drain,
    the traced phase: ``correct: true``, and the state layers' counters moved."""
    import time

    import test_bench_run
    from benchmark.harness import cell as cell_mod

    root = tiny_root.build(str(tmp_path / "checkout"))
    granite_tiny.lay(root)
    monkeypatch.setattr(cell_mod, "_reduce_trace", test_bench_run._cpu_trace_as_device)
    result = cell_mod.run_cell(root, granite_tiny.CELL, 2**31 + 26, 3.0, 2, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert test_bench_run.NAMES["tiny-moe.closed"] <= set(metrics)
    assert {"ttft_p50_ms.ssm32", "tpot_p50_ms.ssm32", "stall_p50_ms.ssm32"} <= set(metrics)
    # the kernels' shares read nothing at a toy head size (the XLA path serves, under no
    # kernel's name): left out, as a program from before this arch leaves them out
    assert not {"ssd_step_roofline", "ssd_chunk_roofline", "q40_dense32_roofline.ssm"} & set(metrics)
    with open(str(tmp_path / "checkout" / "benchmark" / ".cache" / "server.log"), errors="replace") as f:
        log = f.read()
    assert "Traceback" not in log


def test_the_state_layers_tokens_are_counted_by_kind(model):
    from distributed_llama_tpu import telemetry

    telemetry.enable()
    try:
        telemetry.reset()
        tokens = telemetry.REGISTRY.counter("dllama_state_layer_tokens_total", labelnames=("mixer", "phase"))
        value = lambda mixer, phase: tokens.labels(mixer=mixer, phase=phase).value
        eng = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32)
        stream = scheduler(eng, prefill_chunk=32).new_stream()
        decode(stream, stream.prefill(PROMPT), 6)
        assert value("ssm", "prefill") == 9 * len(PROMPT)  # nine state-space layers
        # one row, chunks of 4 steps: the chunks delivered, whole
        assert value("ssm", "decode") > 0 and value("ssm", "decode") % (9 * 4) == 0
        assert value("linear", "prefill") == value("linear", "decode") == 0
        state = telemetry.REGISTRY.gauge("dllama_recurrent_state_bytes")
        assert state.value == llama.recurrent_state_bytes(eng.cfg, 2)
    finally:
        telemetry.reset()
        telemetry.disable()
