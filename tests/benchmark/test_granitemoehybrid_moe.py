"""Granite-4.0-H-Small's pattern at a toy width with the PUBLISHED head sizes
on the CPU: the program's engine against the family's plain reference (a
token-by-token recurrence, a loop over the held experts, no cache) in float32
LOGITS through every path a served row takes (prefill, prefill in pieces,
decode through the slab, rows of different lengths in one bucket, a bucket
with a masked row, a prefix hit that resumes from a state snapshot); the four
chips' shares adding up to the uncut layer; what refuses by name; planted
faults in the expert layer, each multiplier and the softmax scale failing the
tolerance; the new counters; the toy cell through the harness."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_moe_tiny
import tiny_root
from benchmark import families
from benchmark.harness import modelfile
from benchmark.harness.cell import load_check
from benchmark.reference.ops import rmsnorm
from benchmark.reference.qfile import QFile
from distributed_llama_tpu.engine import InferenceEngine
from distributed_llama_tpu.engine.batch import BatchScheduler
from distributed_llama_tpu.models import llama, moe
from distributed_llama_tpu.models.config import LlamaConfig

CONFIG = granite_moe_tiny.CONFIG
PAGE = 8
SEED = 2**31 + 3
# float32 against float32, the chunked form against the recurrence, buckets against a loop: what
# is left is the order of float32 sums (measured 2e-6 to 4e-6 of max|logit|)
TOL = 2e-5
RNG = np.random.default_rng(11)
PROMPT = RNG.integers(300, 16000, 45).tolist()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    return modelfile.write_artifacts(CONFIG, SEED, str(tmp_path_factory.mktemp("granite_moe")), 512)[0]


@pytest.fixture(scope="module")
def family():
    return families.load(CONFIG, "reference")


@pytest.fixture(scope="module")
def reference(model, family):
    qf = QFile(model, family)
    return lambda tokens: family.forward(qf, np.asarray([tokens], np.int32), np.arange(len(tokens)))[0]


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
    of max|logit|. In float32 a routing near-tie is decided alike on both
    sides, so no position is left out."""
    rows = reference(prompt + answer)[len(prompt) - 1:-1]
    return [float(r.max() - r[t]) / float(np.abs(r).max()) for r, t in zip(rows, answer)]


def test_the_file_says_what_the_configuration_says(model, engine, family, tmp_path):
    from distributed_llama_tpu.formats.model_file import (ArchType, HeaderKey, ModelFileReader, _header_pairs,
                                                          read_spec)

    spec, cfg = read_spec(model), engine.cfg
    assert spec.arch_type == ArchType.GRANITE_HYBRID
    assert [cfg.layer_kind(l) for l in range(10)] == \
        [("ssm", "experts")] * 5 + [("full", "experts")] + [("ssm", "experts")] * 4
    # the scale 1/128 EXACTLY: 7812.5 millionths are no header value, 7812500 billionths are
    assert (spec.attn_scale_micro, spec.attn_scale_nano) == (0, 7812500) and cfg.softmax_scale == 0.0078125
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logits_divisor) == (12.0, 0.22, 16.0)
    assert cfg.softmax_scale != cfg.head_size ** -0.5 and not cfg.use_rope and cfg.kv_head_pack == 1
    # the router keeps its published width and its experts a token; 4 are held, from expert 4 on
    assert (cfg.router_width, cfg.n_experts, cfg.first_expert, cfg.n_active_experts) == (16, 4, 4, 2)
    assert cfg.norm_topk and not cfg.router_sigmoid and cfg.routed_scale == 1.0
    # the shared expert, 64 wide, is two of the experts' 32 side by side: the same three tensors
    assert (cfg.moe_hidden_dim, cfg.n_shared_experts) == (32, 2)
    names = ModelFileReader(model).entries
    assert names["layers.0.shared.gate"].shape == (64, 256) and names["layers.0.shared.down"].shape == (256, 64)
    assert names["layers.5.moe_router"].shape == (16, 256) and "layers.5.experts.3.down" in names
    # a softmax router chooses by its scores alone: no selection bias, no dense SwiGLU
    assert not any(n.endswith(("router_bias", ".gate_up")) or n in ("layers.0.gate", "layers.0.down") for n in names)
    assert "router_bias" not in engine.params["layers"][0] and "gate_up" not in engine.params["layers"][0]
    # the state as served: two heads of 64 a row of lanes, [4, 128, 128] a row and layer
    assert llama.init_batch_cache(cfg, 3)[0]["S"].shape == (3, 4, 128, 128)
    # a dense member's file (no expert key, a scale of whole millionths) is byte for byte what it was
    import granite_tiny
    dense = families.load(granite_tiny.CONFIG, "modelfile").model_spec(granite_tiny.CONFIG, 512)
    keys = [int(k) for k, _ in _header_pairs(dense)]
    assert max(keys) == HeaderKey.LOGITS_DIVISOR_MICRO and HeaderKey.N_ROUTED_EXPERTS not in keys
    # the reference reads the same header
    assert (QFile(model, family).h["attn_scale"], QFile(model, family).h["first_expert"]) == (0.0078125, 4)


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
    assert max(deficits(reference, other, decode(s1, got1, 6))) <= TOL


def test_a_prefix_hit_resumes_from_a_snapshot_and_falls_back_to_an_earlier_one(engine, reference):
    sched = scheduler(engine, kv_pages=64)
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


def test_q40_engine_is_as_far_from_float32_as_the_q80_rounding_puts_the_reference(model, reference, family):
    # token by token, so that every position is compared. What separates the served Q40 path from
    # float32 is the Q80 rounding of the activations into every Q40 matmul, which this lineage's
    # products of functions of the stream and its small embedding carry far (the cell's check
    # block says why), and at a width of 256 a rounding is averaged over 16 times fewer terms than
    # at 4096; where it swaps the second expert of a top 2 of 16 the logits jump. So the yardstick
    # is the family's reference with the SAME rounding at every matmul's input (precision_control's
    # q80): the engine's median position is no farther off than one and a half times that
    # reference's (measured: 4.5e-2 and 4.4e-2 of max|logit|), and both are far from a fault's
    from benchmark.tools import precision_control

    want = reference(PROMPT)
    stream = InferenceEngine(model, dtype="q40").new_stream()
    offs = [off(stream.prefill([tok]), want[i]) for i, tok in enumerate(PROMPT)]
    with precision_control.rounded(family, "q80"):
        rounded = reference(PROMPT)
    jax.clear_caches()
    ref_offs = [off(rounded[i], want[i]) for i in range(len(PROMPT))]
    assert np.median(offs) <= 1.5 * np.median(ref_offs), (np.median(offs), np.median(ref_offs))
    assert np.median(offs) <= 0.1, offs


def _share_file(uncut: str, share: dict, path: str) -> str:
    """The file of one chip's share, made of the UNCUT file's own tensors:
    everything as it is, of the experts those the share holds."""
    from distributed_llama_tpu.formats.model_file import ModelFileReader, ModelFileWriter

    reader = ModelFileReader(uncut)
    spec = families.load(share, "modelfile").model_spec(share, 512)
    first = share["first_routed_expert"]
    with open(path, "wb") as f:
        w = ModelFileWriter(f, spec)
        for e in list(w.remaining()):
            parts = e.name.split(".")
            if "experts" in parts:
                parts[parts.index("experts") + 1] = str(int(parts[parts.index("experts") + 1]) + first)
            w.write_raw(np.asarray(reader.raw(".".join(parts))), e.name)
        w.finish()
    return path


@pytest.mark.parametrize("layer", [0, 5], ids=["behind a state-space mixer", "behind the softmax mixer"])
def test_the_four_shares_add_up_to_the_uncut_layer(tmp_path_factory, family, layer):
    """Experts 0-3, 4-7, 8-11 and 12-15 on four chips: what each chip's
    program computes of a layer's expert sum (its held experts' part, routed
    over all 16), with what every chip computes alike (router, shared expert)
    counted once, is what the reference gives for the whole layer on the uncut
    file, times the residual multiplier on top of the stream."""
    tmp = tmp_path_factory.mktemp("shares")
    whole = {**CONFIG, "name": "uncut", "num_local_experts": 16, "reduced": [], "reduced_from": {},
             "first_routed_expert": 0}
    uncut = modelfile.write_model(str(tmp / "uncut.m"), whole, 512, SEED + 1)
    qf = QFile(uncut, family)
    h = jnp.asarray(np.random.default_rng(layer).standard_normal((24, 256)), jnp.float32)
    u = rmsnorm(h, qf.f32(f"layers.{layer}.rms_ffn"))
    want = np.asarray(h + 0.22 * (family.held_experts(qf, layer, u[None])[0]
                                  + family.shared_expert(qf, layer, u[None])[0]))
    parts, shared = [], None
    for j in range(4):
        share = {**CONFIG, "name": f"share{j}", "first_routed_expert": 4 * j}
        eng = InferenceEngine(_share_file(uncut, share, str(tmp / f"share{j}.m")), dtype=jnp.float32,
                              cache_dtype=jnp.float32)
        lp, cfg = eng.params["layers"][layer], eng.cfg
        assert (cfg.first_expert, cfg.n_experts, cfg.router_width) == (4 * j, 4, 16)
        alike = np.asarray(moe._moe_share(dataclasses.replace(cfg, n_experts=0), u, lp))  # the shared expert alone
        if shared is None:
            shared = alike
        np.testing.assert_array_equal(alike, shared)  # every chip computes it alike
        parts.append(np.asarray(moe._moe_share(cfg, u, lp)) - alike)
        # ... and the chip's own block is the stream plus 0.22 x (its part + the shared expert)
        block = np.asarray(moe.moe_block(cfg, h, lp, None))
        assert np.abs(block - (np.asarray(h) + 0.22 * (parts[-1] + alike))).max() <= 1e-5
    assert all(np.abs(p).max() > 0 for p in parts)  # every share got some token
    got = np.asarray(h) + 0.22 * (sum(parts) + shared)
    assert np.abs(got - want).max() / np.abs(want).max() <= TOL


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


def _plant(monkeypatch, fault):
    """Plant one fault in the program's expert layer."""
    if fault == "the experts' branch joins the stream unscaled by 0.22":
        def unscaled(cfg, x, lp, axis_name, ep_axis=None, n_real=None):
            xn = llama.rmsnorm(x, lp["rms_ffn"])
            return x + moe.moe_ffn(cfg, xn, lp, axis_name, ep_axis=ep_axis, n_real=n_real).astype(x.dtype)
        monkeypatch.setattr(moe, "moe_block", unscaled)
    elif fault == "a softmax over all 16 experts, the chosen two not renormalised":
        monkeypatch.setattr(LlamaConfig, "norm_topk", property(lambda self: False))
    elif fault == "the shared expert dropped":
        real = moe._moe_share
        monkeypatch.setattr(moe, "_moe_share", lambda cfg, xn, lp: real(
            cfg, xn, {k: v for k, v in lp.items() if not k.startswith("shared_")}))
    elif fault == "the held experts dropped (the shared one alone)":
        real = moe._moe_share
        monkeypatch.setattr(moe, "_moe_share", lambda cfg, xn, lp: real(
            dataclasses.replace(cfg, n_experts=0), xn, lp))
    elif fault == "the held experts taken for experts 0 to 3 (they are 4 to 7)":
        real = moe._moe_share
        monkeypatch.setattr(moe, "_moe_share", lambda cfg, xn, lp: real(
            dataclasses.replace(cfg, first_expert=0), xn, lp))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["the experts' branch joins the stream unscaled by 0.22",
                                   "a softmax over all 16 experts, the chosen two not renormalised",
                                   "the shared expert dropped",
                                   "the held experts dropped (the shared one alone)",
                                   "the held experts taken for experts 0 to 3 (they are 4 to 7)"])
def test_a_planted_fault_in_the_expert_layer_fails_the_tolerance(model, reference, monkeypatch, fault):
    _plant(monkeypatch, fault)
    jax.clear_caches()
    try:
        got = InferenceEngine(model, dtype=jnp.float32, cache_dtype=jnp.float32).prefill(PROMPT)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # not by float32's tolerance, and not by the benchmark's for the served Q40 path either
    assert off(got, reference(PROMPT)[-1]) > load_check()["logit_tol"]


# a file that says a multiplier is one (what a program that dropped it computes), same weights
DROPPED = {"the embedding multiplier": {"embedding_multiplier": 1},
           "the residual multiplier": {"residual_multiplier": 1},
           "the logits' divisor": {"logits_scaling": 1},
           "the softmax scale of 1/128 (128 ** -0.5 instead)": {"attention_multiplier": 0.088388348}}


@pytest.mark.parametrize("what", sorted(DROPPED))
def test_dropping_a_multiplier_fails_the_tolerance(tmp_path, reference, what):
    path = modelfile.write_model(str(tmp_path / "dropped.m"), {**CONFIG, **DROPPED[what]}, 512, SEED)
    got = InferenceEngine(path, dtype=jnp.float32, cache_dtype=jnp.float32).prefill(PROMPT)
    want = reference(PROMPT)[-1]
    if what.startswith("the logits"):
        # the one a greedy token cannot see: the same answer, sixteen times the logits
        assert int(np.argmax(got)) == int(np.argmax(want)) and off(np.asarray(got) / 16, want) <= TOL
    if what.startswith("the softmax scale"):
        # ONE softmax layer of ten, whose scores are small either way: it moves the logits by
        # more than float32's tolerance a hundred times over, and that is what holds it
        assert off(got, want) > 100 * TOL
    else:
        assert off(got, want) > load_check()["logit_tol"]


def test_the_seeded_answer_is_the_layers_and_not_the_tied_heads_echo(engine):
    """A tied head scores the token just fed by |E_t|^2; the family draws the
    embedding small so that the greedy answer does not repeat its last token
    whatever the layers compute (``families/granitemoehybrid_moe/modelfile.py``)."""
    stream = scheduler(engine).new_stream()
    answer = decode(stream, stream.prefill(PROMPT), 24)
    repeats = sum(a == b for a, b in zip(answer, answer[1:]))
    assert repeats <= 2 and len(set(answer)) >= 18, answer


def test_the_states_precision_is_held_in_the_logits(model, monkeypatch):
    """``tools/ssd_state_witness.py`` on this family at the toy size (the
    published head sizes): pieces that hand the state on, then decode steps
    through a slab, in float32, every logit held to the reference; a state
    kept in bfloat16 reads over the limit."""
    import importlib.util

    from distributed_llama_tpu.ops import ssd

    spec = importlib.util.spec_from_file_location(
        "ssd_state_witness", os.path.join(tiny_root.REPO, "tools", "ssd_state_witness.py"))
    witness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(witness)
    monkeypatch.setattr(witness, "PIECE", 32)
    try:
        served, planted = witness.run(CONFIG, model, ["served", "state_bf16"], rows=2, prompt=96,
                                      steps=32, every=16, seed=7)
    finally:
        jax.clear_caches()
    assert served["ok"] and max(served["after_prompt"], *served["by_step"].values()) <= TOL
    assert not planted["ok"] and max(planted["by_step"].values()) > 2 * witness.LIMIT
    assert ssd.ssd_step.__name__ == "ssd_step"  # the witness put the program's own back


def test_the_expert_rows_and_the_padded_bytes_are_counted(model):
    from distributed_llama_tpu import telemetry

    telemetry.enable()
    try:
        telemetry.reset()
        eng = InferenceEngine(model, dtype="q40")
        series = lambda name: {key: child.value for key, child in telemetry.REGISTRY.get(name)._children.items()}
        padded = series("dllama_q40_padded_weight_bytes")
        # every Q40 leaf's name is there; of this toy's only the two whose contraction or
        # columns no tile divides hold padding: the experts' down bank (32 -> 64 values in) and
        # the state-space input projection (1288 -> 2048 columns)
        assert {("ssm_in",), ("wo",), ("experts_gate_up",), ("experts_down",), ("shared_gate_up",),
                ("shared_down",), ("qkv",), ("wcls",)} == set(padded)
        assert {k for k, v in padded.items() if v} == {("ssm_in",), ("experts_down",)}
        # 10 layers x 4 experts x (32 of 64 rows) x 256 columns at 0.5 B + 4 B a block of 32
        assert padded[("experts_down",)] == 10 * 4 * (16 * 256 + 4 * 1 * 256)
        stream = scheduler(eng, prefill_chunk=32).new_stream()
        decode(stream, stream.prefill(PROMPT), 6)
        rows = series("dllama_moe_expert_rows_total")
        # every piece here is too small to bucket (32 and 16 rows: the bucket is the whole step):
        # the 4 held experts of each of the 10 layers multiplied every row of both programs
        assert rows[("computed", "piece")] == 10 * 4 * (32 + 16)
        held = series("dllama_moe_assignments_total")[("yes",)]
        assert rows[("chosen", "piece")] + rows[("chosen", "decode")] == held
        # a quarter of the router's width is held, 2 of 16 chosen: computed / chosen is 8 when even
        assert 3.0 < rows[("computed", "piece")] / rows[("chosen", "piece")] < 20.0
        # one row decoding in a bucket of one: chunks of 4 steps, every held expert over its one row
        assert rows[("computed", "decode")] > 0 and rows[("computed", "decode")] % (10 * 4 * 4) == 0
        tokens = series("dllama_state_layer_tokens_total")
        assert tokens[("ssm", "prefill")] == 9 * len(PROMPT) and tokens[("ssm", "decode")] % (9 * 4) == 0
    finally:
        telemetry.reset()
        telemetry.disable()


def test_the_cell_runs_through_the_harness_on_the_cpu(tmp_path, monkeypatch):
    """The toy configuration as a cell of the miniature checkout, through
    ``run_cell`` with ``--trace 2``: the family's builder, the server child,
    the probes judged by the family's reference with its routing gaps, warm-up,
    window, drain, the traced phase: ``correct: true``, and the real cell's own
    entries read what this PR's counters count."""
    import time

    import test_bench_run
    from benchmark.harness import cell as cell_mod

    root = tiny_root.build(str(tmp_path / "checkout"))
    granite_moe_tiny.lay(root)
    monkeypatch.setattr(cell_mod, "_reduce_trace", test_bench_run._cpu_trace_as_device)
    result = cell_mod.run_cell(root, granite_moe_tiny.CELL, 2**31 + 26, 3.0, 2, "cpu", time.monotonic())
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert {"out_tok_s", "setup_s", "device_idle_share", "peak_hbm_gb",
            "moe_held_share.ep4", "moe_rows_per_expert_mean.ep4", "moe_computed_rows_per_chosen",
            "moe_piece_bucketed_share", "ttft_p50_ms.ep4", "tpot_p50_ms.ep4",
            "stall_p50_ms.ep4"} <= set(metrics)
    # 4 of 16 experts held: a quarter of the assignments when routing is even
    assert 10.0 < metrics["moe_held_share.ep4"]["value"] < 45.0
    # the toy's pieces (32 rows) are too small to bucket: every held expert over every row
    assert metrics["moe_piece_bucketed_share"]["value"] == 0.0
    assert 3.0 < metrics["moe_computed_rows_per_chosen"]["value"] < 20.0
    # the kernels' shares and the step's read nothing at a toy width on the CPU (the XLA paths
    # serve, under no kernel's and no module's name)
    assert not {"decode_hbm_share", "ssd_step_roofline.ep4", "q40_held_experts_roofline.ep4",
                "q40_dense32_roofline.ep4"} & set(metrics)
    with open(str(tmp_path / "checkout" / "benchmark" / ".cache" / "server.log"), errors="replace") as f:
        assert "Traceback" not in f.read()


def test_the_real_cells_entries_are_its_own_or_lists_it_joined():
    """``BENCHMARK.json`` as this PR leaves it: the cell's name at the END of
    the lists it joined, entries of its own where an accepted test holds a
    list to its present members, one configuration, one chip."""
    import json

    with open(os.path.join(tiny_root.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    cell = granite_moe_tiny.REAL_CELL
    lists = {m["name"]: m.get("workloads") for m in real["per_layer"]}
    for name in ("queue_ms_mean.open", "prefix_hit_share.open", "prefill_ms_mean.open",
                 "compiles_in_window.open", "prefill_chunks_ahead_mean.open", "server_ttft_ms_mean.open",
                 "moe_piece_bucketed_share"):
        assert lists[name][-1] == cell and len(lists[name]) > 1, name
    for name in ("q40_held_experts_roofline.ep4", "moe_held_share.ep4", "moe_rows_per_expert_mean.ep4",
                 "moe_computed_rows_per_chosen", "ssd_step_roofline.ep4", "ssd_chunk_roofline.ep4",
                 "q40_dense32_roofline.ep4", "ttft_p50_ms.ep4", "tpot_p50_ms.ep4", "stall_p50_ms.ep4"):
        assert lists[name] == [cell], name
    # the lists an accepted test holds to their present members stay as they were
    # (tests/benchmark/test_solar_open2.py; tests/benchmark/granite_tiny.py: lay)
    for name in ("q40_held_experts_roofline", "moe_held_share", "moe_rows_per_expert_mean", "q40_dense32_roofline"):
        assert lists[name] == ["solar-open2.batch_prompted"], name
    for name in ("ssd_step_roofline", "ssd_chunk_roofline", "q40_dense32_roofline.ssm", "ttft_p50_ms.ssm32",
                 "tpot_p50_ms.ssm32", "stall_p50_ms.ssm32"):
        assert lists[name] == ["granite-4.0-h-micro.batch_prompted"], name
    entry = real["workloads"][-1]
    assert (entry["name"], entry["traffic"], entry["chips"]) == (cell, "batch_prompted", 1)
    config = real["configs"][-1]
    assert config["name"] == entry["config"] == "granite-4.0-h-small-q40-10l-ep4"
    assert config["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert len(real["workloads"]) == 10 and len(real["configs"]) == 8
