"""Solar-Open2 at a toy size on the CPU: the program's engine against the
family's plain reference through every path a served row takes (prefill,
prefill in chunks, decode through the slab, a bucket with a masked row, a
prefix hit that resumes from a state snapshot), the test that ties the
expert share to the model, the chunked recurrence against the step, what
refuses by name, and that each piece of the mathematics is load-bearing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import solar_tiny
import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.reference.qfile import QFile
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.models import llama, moe
from distributed_llama_tpu.ops import kda

CONFIG = solar_tiny.CONFIG
PAGE = 8
# float32 against float32: what is left is rounding (measured 4e-7 to 1.3e-6 of max|logit|)
TOL = 2e-5
RNG = np.random.default_rng(11)
PROMPT = RNG.integers(300, 16000, 45).tolist()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("solar"))
    return modelfile.write_artifacts(CONFIG, 2**31 + 3, directory, 512)[0]


@pytest.fixture(scope="module")
def reference(model):
    ref = families.load(CONFIG, "reference")
    qf = QFile(model, ref)

    def logits(tokens, gaps=None):
        return ref.forward(qf, np.asarray([tokens], np.int32), np.arange(len(tokens)), gaps)[0]

    return logits


@pytest.fixture(scope="module")
def engine(model):
    return InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32)


def scheduler(engine, rows=2, prefill_chunk=0, kv_pages=32, **kw):
    return BatchScheduler(engine, n_rows=rows, chunk=4, prefix_cache=True, kv_pages=kv_pages,
                          page_size=PAGE, prefill_chunk=prefill_chunk, **kw)


def off(got, want) -> float:
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def decode(stream, logits_row, n):
    """Greedy decode of ``n`` tokens after a prefill that returned
    ``logits_row``; the tokens, the first among them."""
    first = int(np.argmax(logits_row))
    toks = []

    def on_token(prev, tok):
        toks.append(tok)
        return len(toks) < n

    stream.stream_decode(first, on_token, 0.0, 0.9, seed=1, limit=stream.pos + n,
                         first_prev=int(stream._history[-1]) if stream._history else 0)
    return toks


def deficits(reference, prompt, answer):
    """Teacher-forced, as the benchmark's check does it: how far each served
    token lies below the reference's best for the same context, as a share
    of max|logit|."""
    rows = reference(prompt + answer)[len(prompt) - 1:-1]
    return [float(r.max() - r[t]) / float(np.abs(r).max()) for r, t in zip(rows, answer)]


@pytest.mark.parametrize("case", ["prefill alone", "prefill in two chunks",
                                  "prefill then decode through the slab",
                                  "a bucket with a masked row"])
def test_engine_against_the_reference(engine, reference, case):
    want = reference(PROMPT)
    if case == "prefill alone":
        assert off(engine.new_stream().prefill(PROMPT), want[-1]) <= TOL
        return
    sched = scheduler(engine, prefill_chunk=32 if case == "prefill in two chunks" else 0)
    s0, s1 = sched.new_stream(), sched.new_stream()
    got = s0.prefill(PROMPT)
    assert off(got, want[-1]) <= TOL
    if case == "prefill in two chunks":
        return
    if case == "a bucket with a masked row":
        # row 1 holds a state of its own while row 0 decodes in a bucket of two
        other = RNG.integers(300, 16000, 30).tolist()
        got1 = s1.prefill(other)
        answer = decode(s0, got, 9)
        assert max(deficits(reference, PROMPT, answer)) <= TOL
        # ... and row 1's state was not touched by the chunks it sat out
        assert max(deficits(reference, other, decode(s1, got1, 6))) <= TOL
        return
    answer = decode(s0, got, 10)
    assert max(deficits(reference, PROMPT, answer)) <= TOL


def test_with_telemetry_off_no_expert_sum_is_kept_or_read(engine):
    """The held-choice sums feed counters only: an engine built without
    telemetry keeps none of a prefill chunk's and reads none at a delivery."""
    assert not engine._tel.enabled
    sched = scheduler(engine, prefill_chunk=32)
    stream = sched.new_stream()
    decode(stream, stream.prefill(PROMPT), 6)
    assert sched._moe_pending == []


def test_q40_engine_stays_inside_the_benchmarks_logit_tolerance(model, reference):
    from benchmark.harness.cell import load_check

    # token by token, so that every position is compared: a top 2 of 16 is discontinuous, and
    # where the reference's routing is a near-tie the Q80 rounding of the activations flips it;
    # the flip moves that position's logits by 0.05 to 0.15 of max|logit| and, through the
    # recurrent state, the next twenty by a few hundredths (measured; the benchmark's check
    # leaves near-ties out by the reference's routing gap). Elsewhere the error is 3e-3 to 1e-2.
    want = reference(PROMPT)
    stream = InferenceEngine(model, dtype="q40").new_stream()
    offs = [off(stream.prefill([tok]), want[i]) for i, tok in enumerate(PROMPT)]
    tol = load_check()["logit_tol"]
    assert np.median(offs) <= tol / 2 and np.mean(np.asarray(offs) <= tol) >= 0.8, offs


def counter(name, **labels):
    from distributed_llama_tpu import telemetry

    return telemetry.REGISTRY.counter(name, "").labels(**labels).value if labels else None


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
    # the hit's logits are the cold run's: state and pages were both resumed
    cold = engine.new_stream().prefill(p2)
    s0.prefill(p2 + tail(4))
    assert s0.matched_len == 4 * PAGE
    s0.reset()
    got = s0.prefill(p2)  # strictly shorter than the prompt: 4 pages match, of a prompt of 4 pages + 5
    assert s0.matched_len == 4 * PAGE and off(got, np.asarray(cold)) <= TOL
    assert off(got, reference(p2)[-1]) <= TOL
    # three pages of p2 match, the third has no snapshot: back to the second
    s0.reset()
    p3 = p2[:3 * PAGE] + tail(6)
    got = s0.prefill(p3)
    assert s0.matched_len == 2 * PAGE and off(got, reference(p3)[-1]) <= TOL
    # ... and what it decodes from there is the reference's too
    assert max(deficits(reference, p3, decode(s0, got, 5))) <= TOL
    sched.check_prefix()


def test_eviction_takes_snapshot_and_pages_together(engine):
    sched = scheduler(engine, rows=1, kv_pages=4)
    s = sched.new_stream()
    prefix = sched._prefix
    assert prefix.snap_slots == 2
    for i in range(4):  # four prompts of two pages each through a pool of four
        s.reset()
        s.prefill(RNG.integers(300, 16000, 2 * PAGE + 3).tolist())
        sched.check_prefix()
    nodes = list(prefix._walk())
    with_snapshot = [nd for nd in nodes if nd.snap is not None]
    assert len(nodes) == 4 and len(with_snapshot) == 2
    # a snapshot sits on the LAST page of its prompt, and every slot is accounted for
    assert all(not nd.children for nd in with_snapshot)
    assert len(prefix.snap_free) == 0


@pytest.mark.parametrize("what", ["rollback", "spill", "--spec-draft", "--tp 2",
                                  "a second decode"])
def test_paths_that_move_a_row_by_position_refuse_by_name(engine, model, what):
    with pytest.raises(llama.RecurrentStateError, match="SOLAR_OPEN2"):
        if what == "rollback":
            s = scheduler(engine).new_stream()
            s.prefill(PROMPT)
            s.rollback(10)
        elif what == "spill":
            scheduler(engine, host_spill_bytes=1 << 20)
        elif what == "--spec-draft":
            scheduler(engine, spec_draft=4)
        elif what == "--tp 2":
            InferenceEngine(model, dtype=jnp.float32, tp=2)
        else:
            s = scheduler(engine).new_stream()
            s._history = [PROMPT[-1]]
            logits = s.prefill(PROMPT)
            decode(s, logits, 3)
            decode(s, logits, 3)
    # a rewind to the start is a reset, not a refusal
    s = scheduler(engine).new_stream()
    s.prefill(PROMPT)
    s.rollback(0)
    assert s.pos == 0


def test_old_model_files_read_and_write_as_before(tmp_path, model):
    """The header keys from HEAD_SIZE up are written for the new arch alone
    (``test_bench_pins`` holds the old files' bytes to the parent's): an old
    file reads back with none of them, and its head size is still
    dim / n_heads; the new arch's comes from its header."""
    from distributed_llama_tpu.formats.model_file import ArchType, HeaderKey, _header_pairs, read_spec

    path, _ = modelfile.write_artifacts(tiny_root.CONFIGS["tiny-moe"], 7, str(tmp_path), 512)
    old = read_spec(path)
    assert max(int(k) for k, _ in _header_pairs(old)) < HeaderKey.HEAD_SIZE
    assert (old.head_dim, old.attn_period, old.n_routed_experts) == (0, 0, 0)
    assert old.head_size == old.dim // old.n_heads and old.kv_dim == old.head_size * old.n_kv_heads
    new = read_spec(model)
    assert new.arch_type == ArchType.SOLAR_OPEN2 and new.head_size == 16 != new.dim // 8
    assert (new.n_experts, new.n_routed_experts, new.first_expert, new.attn_period) == (4, 16, 8, 4)


def test_the_shares_add_up_to_the_uncut_layer(engine):
    """The routed parts that all four shares of 4 experts give, plus the
    shared expert counted once, equal the layer that holds all 16."""
    cfg, rng = engine.cfg, np.random.default_rng(5)
    D, F, E = cfg.dim, cfg.moe_hidden_dim, cfg.n_routed_experts
    mat = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), jnp.float32)
    gate_up, down = mat(E, D, 2 * F) * 4, mat(E, F, D) * 4
    bank = lambda lo, hi: {"experts_gate_up": gate_up[lo:hi], "experts_down": down[lo:hi]}
    lp = {"router": mat(D, E) * 4, "router_bias": jnp.asarray(0.02 * rng.standard_normal(E), jnp.float32),
          "shared_gate_up": mat(D, 2 * F), "shared_down": mat(F, D)}
    xn = jnp.asarray(rng.standard_normal((12, D)), jnp.float32)
    routed_only = {k: v for k, v in lp.items() if not k.startswith("shared")}
    whole = moe._moe_share(
        cfg.__class__(**{**cfg.__dict__, "n_experts": E, "first_expert": 0}), xn,
        {**lp, **bank(0, E)})
    parts = sum(
        moe._moe_share(cfg.__class__(**{**cfg.__dict__, "first_expert": first}), xn,
                       {**routed_only, **bank(first, first + cfg.n_experts)})
        for first in range(0, E, cfg.n_experts))
    shared = moe._moe_share(cfg.__class__(**{**cfg.__dict__, "n_experts": 0}), xn,
                            lp)
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-5, atol=1e-5)
    # every token's top 2 of 16 are somewhere: the parts are not all zero
    assert float(jnp.abs(parts).max()) > 0.1


@pytest.mark.parametrize("length", [64, 75, 7])
def test_the_chunked_recurrence_equals_the_step_applied_token_by_token(length):
    rng = np.random.default_rng(length)
    H, dk = 3, 16
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((length, H, dk))).astype(np.float32) / 4
    k = unit(rng.standard_normal((length, H, dk))).astype(np.float32)
    v = rng.standard_normal((length, H, dk)).astype(np.float32)
    a = np.log(rng.uniform(0.5, 0.999, (length, H, dk))).astype(np.float32)
    beta = rng.uniform(0, 2, (length, H)).astype(np.float32)
    S = S0 = rng.standard_normal((H, dk, dk)).astype(np.float32)
    outs = []
    for t in range(length):
        o, S = kda.kda_step(S[None], q[t][None], k[t][None], v[t][None], a[t][None], beta[t][None])
        outs.append(o[0])
        S = S[0]
    o, S_chunked = kda.kda_chunk(S0, q, k, v, a, beta)
    np.testing.assert_allclose(o, np.stack(outs), atol=2e-6)
    np.testing.assert_allclose(S_chunked, S, atol=2e-6)
    # padding past n_real leaves the state where the last real token put it
    pad = lambda x: np.pad(x, ((0, 5),) + ((0, 0),) * (x.ndim - 1), constant_values=0.3)
    _, S_padded = kda.kda_chunk(S0, pad(q), pad(k), pad(v), -np.abs(pad(a)), pad(beta), n_real=length)
    np.testing.assert_allclose(S_padded, S, atol=2e-6)


@pytest.mark.parametrize("kernel", ["kda_step", "kda_chunk", "kda_chunk, 8 tokens"])
def test_the_kernels_at_the_published_head_size_equal_the_xla_recurrence(kernel):
    """At head size 128 both recurrences are Pallas kernels (interpret mode
    here; ``tests/test_chip_compile.py`` asks the v5e compiler whether they
    lower): the same numbers as the XLA forms the toy sizes take."""
    rng = np.random.default_rng(3)
    H, d = 16, 128
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    shape = (3,) if kernel == "kda_step" else ((8,) if kernel.endswith("8 tokens") else (64,))
    q = unit(rng.standard_normal(shape + (H, d))).astype(np.float32) / np.sqrt(d).astype(np.float32)
    k = unit(rng.standard_normal(shape + (H, d))).astype(np.float32)
    v = rng.standard_normal(shape + (H, d)).astype(np.float32)
    a = np.log(rng.uniform(0.5, 0.999, shape + (H, d))).astype(np.float32)
    beta = rng.uniform(0, 2, shape + (H,)).astype(np.float32)
    if kernel == "kda_step":
        S = rng.standard_normal((4, H, d, d)).astype(np.float32)  # a slab of 4 rows, 3 step
        active = jnp.asarray([True, False, True])
        o, S_new = kda.kda_step(jnp.asarray(S), q, k, v, a, beta, active)
        o_ref, S_ref = kda.kda_step_xla(jnp.asarray(S[:3]), q, k, v, a, beta, active)
        np.testing.assert_allclose(np.asarray(o)[[0, 2]], np.asarray(o_ref)[[0, 2]], atol=2e-6)
        np.testing.assert_allclose(S_new[:3], S_ref, atol=5e-6)
        assert np.array_equal(S_new[3], S[3]) and np.array_equal(S_new[1], S[1])
        return
    S0 = rng.standard_normal((H, d, d)).astype(np.float32)
    o, S = kda.kda_chunk(jnp.asarray(S0), q, k, v, a, beta)
    S_ref, outs = jnp.asarray(S0)[None], []
    for t in range(shape[0]):
        o_t, S_ref = kda.kda_step_xla(S_ref, q[t][None], k[t][None], v[t][None], a[t][None], beta[t][None])
        outs.append(o_t[0])
    np.testing.assert_allclose(o, np.stack(outs), atol=2e-6)
    np.testing.assert_allclose(S, S_ref[0], atol=5e-6)


def _without(monkeypatch, piece):
    """Take one piece of the mathematics out of the program."""
    if piece == "the decay":
        real = kda.kda_chunk
        monkeypatch.setattr(kda, "kda_chunk", lambda S, q, k, v, a, b, n=None: real(S, q, k, v, 0 * a, b, n))
    elif piece == "the 2 of beta":
        real = llama._linear_inputs

        def halved(cfg, lp, x):
            qkv, decay, beta, gate = real(cfg, lp, x)
            return qkv, decay, beta / 2, gate

        monkeypatch.setattr(llama, "_linear_inputs", halved)
    elif piece == "the conv":
        monkeypatch.setattr(kda, "causal_conv", lambda x, tail, taps, n=None: (x, tail))
    elif piece == "the output gate":
        real = llama._linear_output
        monkeypatch.setattr(llama, "_linear_output", lambda cfg, lp, o, g: real(cfg, lp, o, 0 * g) * 2)
    elif piece == "the softmax layers' gate":
        monkeypatch.setattr(llama, "_gated", lambda att, gate: att * 0.5)
    elif piece == "the shared expert":
        real = moe._moe_share
        monkeypatch.setattr(moe, "_moe_share", lambda cfg, xn, lp: real(
            cfg, xn, {k: v for k, v in lp.items() if not k.startswith("shared")}))
    elif piece == "the renormalisation over all 8":
        from distributed_llama_tpu.models.config import LlamaConfig

        monkeypatch.setattr(LlamaConfig, "norm_topk", property(lambda self: False))
    else:
        raise ValueError(piece)


@pytest.mark.parametrize("piece", ["the decay", "the 2 of beta", "the conv", "the output gate",
                                   "the softmax layers' gate", "the shared expert",
                                   "the renormalisation over all 8"])
def test_leaving_a_piece_of_the_mathematics_out_fails_the_tolerance(
        model, reference, monkeypatch, piece):
    from benchmark.harness.cell import load_check

    _without(monkeypatch, piece)
    jax.clear_caches()
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32).prefill(PROMPT)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # not by float32's tolerance, and not by the benchmark's for the served Q40 path either
    assert off(got, reference(PROMPT)[-1]) > load_check()["logit_tol"]


def test_the_cell_runs_through_the_harness_on_the_cpu(tmp_path, monkeypatch):
    """The toy configuration as a cell of the miniature checkout, through
    ``run_cell`` with ``--trace 2``: the family's builder, the server child,
    the probes judged by the family's reference (their prompts cross a
    prefill chunk, so the state is handed on), warm-up, window, drain, the
    traced phase: ``correct: true``, and the expert share's counters moved."""
    import time

    import test_bench_run
    from benchmark.harness import cell as cell_mod

    root = tiny_root.build(str(tmp_path / "checkout"))
    solar_tiny.lay(root)
    monkeypatch.setattr(cell_mod, "_reduce_trace", test_bench_run._cpu_trace_as_device)
    result = cell_mod.run_cell(root, solar_tiny.CELL, 2**31 + 26, 3.0, 2, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert test_bench_run.NAMES["tiny-moe.closed"] | {"moe_held_share", "moe_rows_per_expert_mean"} <= set(metrics)
    # 4 of 16 experts are held: a quarter of the choices when routing is even; a decode step of
    # one or two rows choosing 2 of 16 gives a held expert 0.125 to 0.25 rows, a prefill more
    assert 10.0 < metrics["moe_held_share"]["value"] < 40.0
    assert 0.05 < metrics["moe_rows_per_expert_mean"]["value"] < 8.0
    # the kernels' shares read nothing at a toy head size (the XLA path serves): left out
    assert "kda_step_roofline" not in metrics


@pytest.mark.parametrize("joined", [False, True], ids=["the solar cell alone", "a later cell in its lists"])
def test_the_toy_cell_finds_the_solar_cells_entries_whoever_else_lists_them(tmp_path, joined):
    """The Solar cell's own per-layer entries are found by its name IN their
    lists, so a later cell may join them (a second held-expert model, a
    second cell of 32 rows) without the toy cell losing them."""
    import json
    import os

    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    own = {m["name"] for m in real["per_layer"] if m.get("workloads") == ["solar-open2.batch_prompted"]}
    assert {"q40_held_experts_roofline", "q40_dense32_roofline", "moe_held_share",
            "moe_rows_per_expert_mean", "tpot_p50_ms.rows32"} <= own
    if joined:
        for m in real["per_layer"]:
            if m["name"] in own:
                m["workloads"].append("later.cell")
    root = tiny_root.build(str(tmp_path / "checkout"))
    solar_tiny.lay(root, real)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entries = [m["name"] for m in json.load(f)["per_layer"]
                   if solar_tiny.CELL in m.get("workloads", [solar_tiny.CELL])]
    assert own <= set(entries) and len(entries) == len(set(entries))
