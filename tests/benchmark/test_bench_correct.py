"""What ``correct`` lets through and what it stops. The served path returns no
logits, so the rule sees greedy tokens only: these tests hold it to a faithful
engine's roundings (passes) and to a dropped layer, a wrong rope pairing, a
stale context and activations at three mantissa bits (all fail), and show that
the benchmark's weights give a network whose answers depend on their context
while the package's uniform-nibble weights do not."""

import contextlib

import numpy as np
import pytest

import tiny_root
from benchmark import families
from benchmark.harness import cell, modelfile

CONFIG = {**tiny_root.TINY, "name": "mid-dense", "family": "llama", "arch": "llama",
          "num_hidden_layers": 4}
MOE = {**CONFIG, "name": "mid-moe", "arch": "mixtral", "num_local_experts": 8, "num_experts_per_tok": 2}
CHECK = cell.load_check()  # the defaults: what both accepted configurations are judged by
PROBES, PROMPT, ANSWER = CHECK["probes"], 48, CHECK["probe_tokens"]


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("mid") / "mid.m")
    return modelfile.write_model(path, CONFIG, 512, 2**31 + 5)


def probe_tokens():
    rng = np.random.default_rng(3)
    tokens = rng.integers(3, 259, (PROBES, PROMPT + ANSWER)).astype(np.int32)
    tokens[:, 0] = 1
    return tokens, np.arange(PROMPT - 1, PROMPT - 1 + ANSWER)


def q80(x):
    import jax.numpy as jnp

    blocks = x.reshape(x.shape[:-1] + (x.shape[-1] // 32, 32))
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1, keepdims=True), 1e-30) / 127.0
    return (jnp.round(blocks / scale) * scale).reshape(x.shape)


def three_mantissa_bits(x):
    import jax.numpy as jnp

    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16) / 16, e)


@contextlib.contextmanager
def activations_rounded_by(act):
    """The reference with ``act`` applied to the input of every Q40 matmul."""
    import jax

    ref = families.load(CONFIG, "reference")
    plain = ref.matmul
    ref.matmul = lambda x, raw: plain(act(x), raw)
    jax.clear_caches()
    try:
        yield
    finally:
        ref.matmul = plain
        jax.clear_caches()


def logits_of(model, tokens, positions, act=None, drop_layers=0, swap_rope=False, router_gaps=None):
    from benchmark.reference.qfile import QFile

    ref = families.load(CONFIG, "reference")
    qf = QFile(model, ref)
    qf.h["n_layers"] -= drop_layers
    if swap_rope:
        qf.h["rope_type"] = ref.ROPE_HALF_SPLIT
    with activations_rounded_by(act) if act else contextlib.nullcontext():
        return ref.forward(qf, tokens, positions, router_gaps)


def verdict(reference_logits, served_logits, router_gaps=None):
    """``correct``'s verdict on a system whose greedy tokens are the argmax of
    ``served_logits``, teacher-forced like the probes."""
    from benchmark.reference.probe_child import score

    return cell.judge_probes([r for probe in score(reference_logits, served_logits.argmax(-1).tolist(),
                                                   router_gaps) for r in probe], CHECK)


@pytest.fixture(scope="module")
def reference_logits(model):
    return logits_of(model, *probe_tokens())


def test_the_engines_own_roundings_pass(model, reference_logits):
    ok, note = verdict(reference_logits, logits_of(model, *probe_tokens(), act=q80))
    assert ok, note


@pytest.mark.parametrize("fault", ["dropped_layer", "wrong_rope_pairing", "three_mantissa_bits",
                                   "stale_context"])
def test_a_faulty_system_fails(model, reference_logits, fault):
    tokens, positions = probe_tokens()
    if fault == "dropped_layer":
        served = logits_of(model, tokens, positions, drop_layers=1)
    elif fault == "wrong_rope_pairing":
        served = logits_of(model, tokens, positions, swap_rope=True)
    elif fault == "three_mantissa_bits":
        served = logits_of(model, tokens, positions, act=three_mantissa_bits)
    else:  # attention reads another request's early context
        other = tokens.copy()
        other[:, 1:PROMPT // 2] = np.roll(other[:, 1:PROMPT // 2], 1, axis=0)
        served = logits_of(model, other, positions)
    ok, note = verdict(reference_logits, served)
    assert not ok, note


LONG_PROMPT = 448  # 7 pages and, at the miniature cell's 32-row prefill chunks, 14 chunks: with ANSWER under 512


@pytest.fixture(scope="module")
def long_context(model):
    """A cell's own rule with a long probe (``benchmark/workloads/<cell>.json``,
    ``long_probes``): 7 probes of PROMPT tokens and 1 of LONG_PROMPT, each the
    reference's pass over its prompt length, judged together."""
    rng = np.random.default_rng(5)
    tokens = rng.integers(3, 259, (1, LONG_PROMPT + ANSWER)).astype(np.int32)
    tokens[:, 0] = 1
    short, positions = probe_tokens()
    return [(short[:PROBES - 1], positions), (tokens, np.arange(LONG_PROMPT - 1, LONG_PROMPT - 1 + ANSWER))]


def long_verdict(model, long_context, **served):
    from benchmark.reference.probe_child import score

    scored = []
    for tokens, positions in long_context:
        want = logits_of(model, tokens, positions)
        got = logits_of(model, tokens, positions, **served)
        scored += [r for probe in score(want, got.argmax(-1).tolist()) for r in probe]
    assert len(scored) == PROBES * ANSWER
    return cell.judge_probes(scored, CHECK), max(r["deficit"] for r in scored[-ANSWER:])


def test_with_a_long_probe_among_them_the_engines_roundings_still_pass(model, long_context):
    (ok, note), worst_long = long_verdict(model, long_context, act=q80)
    assert ok and worst_long <= CHECK["miss_tol"], note


@pytest.mark.parametrize("fault", ["three_mantissa_bits", "dropped_layer", "wrong_rope_pairing"])
def test_with_a_long_probe_among_them_the_control_and_the_faults_still_fail(model, long_context, fault):
    """The control (the nearest precision under the engine's Q80) and two
    faults of the path, with one probe of the eight at a long context as the
    document cell's rule has it: not correct; and the long probe's own
    positions show the fault too."""
    served = {"three_mantissa_bits": dict(act=three_mantissa_bits), "dropped_layer": dict(drop_layers=1),
              "wrong_rope_pairing": dict(swap_rope=True)}[fault]
    (ok, note), worst_long = long_verdict(model, long_context, **served)
    assert not ok, note
    assert worst_long > CHECK["miss_tol"], note


def rows(n, misses=(), router_gap=None):
    out = [{"server": 5, "reference": 5, "deficit": 0.0, "router_gap": router_gap} for _ in range(n)]
    for i, d in enumerate(misses):
        out[i] = {"server": 6, "reference": 5, "deficit": d, "router_gap": router_gap}
    return out


@pytest.mark.parametrize("case,rows_,want", [
    ("all equal", rows(256), True),
    ("seven misses of 256 are the allowance", rows(256, [0.011] * 7), True),
    ("one at the same position of each of the 8 probes is not", rows(256, [0.011] * 8), False),
    ("nor in a sparse-expert model", rows(256, [0.5] * 8, router_gap=0.3), False),
    ("a dense model may not be far off anywhere", rows(256, [0.031]), False),
    ("a decided router may be swapped after an earlier swap, once", rows(256, [0.2], router_gap=0.3), True),
    ("a routing near-tie is not compared", rows(200, router_gap=0.3) + rows(56, [0.2] * 56, router_gap=0.019), True),
    ("under the miss line is no miss", rows(256, [0.009] * 40), True),
    ("too little read back", rows(CHECK["min_compared"] - 1), False),
    ("too many near-ties", rows(CHECK["min_compared"] - 1, router_gap=0.3) + rows(200, router_gap=0.001), False),
])
def test_the_rule(case, rows_, want):
    ok, note = cell.judge_probes(rows_, CHECK)
    assert ok is want, f"{case}: {note}"


def dequantized(blocks):
    scales = blocks[:, :2].copy().view(np.float16).astype(np.float32)
    qs = blocks[:, 2:]
    values = np.concatenate([(qs & 0xF).astype(np.int32) - 8, (qs >> 4).astype(np.int32) - 8], axis=1)
    return values * scales


@pytest.mark.parametrize("gain", [1.0, modelfile.RESIDUAL_GAIN])
def test_the_seeded_weights_have_no_common_mean(gain):
    w = dequantized(modelfile.q40_blocks(np.random.default_rng(1), 1 << 15, 4096, gain))
    assert abs(w.mean()) < 0.01 * w.std()  # uniform nibbles: -0.11 of a std
    assert w.var() == pytest.approx(gain**2 / 4096, rel=0.02)
    assert set(np.unique(np.round(w / w[w > 0].min()))) >= {-1.0, 0.0, 1.0} and (w == 0).mean() > 0.1


def test_no_token_but_a_filler_can_be_the_greedy_answer(reference_logits):
    from benchmark.harness.traffic import FIRST_FILLER_ID

    assert np.all(reference_logits[..., :FIRST_FILLER_ID] == 0.0)  # EOS, bytes, word pieces
    assert reference_logits.argmax(-1).min() >= FIRST_FILLER_ID
    assert reference_logits.max(-1).min() > 2.0


def test_the_same_seed_gives_the_same_file_and_another_seed_another(tmp_path):
    one = {**CONFIG, "num_hidden_layers": 1}
    a, b, c = (open(modelfile.write_model(str(tmp_path / n), one, 512, seed), "rb").read()
               for n, seed in (("a.m", 2**31 + 9), ("b.m", 2**31 + 9), ("c.m", 2**31 + 10)))
    assert a == b and a != c


def test_answers_depend_on_their_context_with_these_weights_and_not_with_uniform_nibbles(tmp_path):
    """Uniform nibbles give every matrix a common mean of -0.5 steps: a rank-one
    term along the all-ones direction that outgrows the random part with the
    width (4x at 1024 here, 7x at 4096), swallows the state, and leaves logits
    that hardly depend on the input. It is why PR 22's first reference checks
    saw mirrored answers at isolated positions."""
    from distributed_llama_tpu.formats.synthetic import write_random_q40_model

    wide = {**CONFIG, "hidden_size": 1024, "intermediate_size": 1024, "head_dim": 128}
    spec = families.load(wide, "modelfile").model_spec(wide, 512)
    tokens, positions = probe_tokens()
    tokens, positions = tokens[:4, :40], np.arange(32, 40)
    changed = tokens.copy()
    changed[:, 1:8] = (changed[:, 1:8] + 17) % 250 + 3
    moved = {}
    for name, path in (("seeded", modelfile.write_model(str(tmp_path / "s.m"), wide, 512, 7)),
                       ("uniform", write_random_q40_model(str(tmp_path / "u.m"), spec, seed=7))):
        a, b = logits_of(path, tokens, positions), logits_of(path, changed, positions)
        moved[name] = float(np.median(np.abs(a - b).max(-1) / np.abs(a).max(-1)))
    assert moved["seeded"] > 0.1 > 10 * moved["uniform"]


def test_a_sparse_expert_model_with_the_engines_roundings_passes(tmp_path):
    path = modelfile.write_model(str(tmp_path / "moe.m"), MOE, 512, 2**31 + 5)
    tokens, positions = probe_tokens()
    gaps: list = []
    reference = logits_of(path, tokens, positions, router_gaps=gaps)
    assert len(gaps) == MOE["num_hidden_layers"] and gaps[0].shape == (PROBES, ANSWER)
    ok, note = verdict(reference, logits_of(path, tokens, positions, act=q80), gaps)
    assert ok and "near-ties left out" in note, note
    ok, note = verdict(reference, logits_of(path, tokens, positions, drop_layers=1), gaps)
    assert not ok, note
