"""The traffic generator: a pure function of (mix, seed), the same work for
every seed, token counts that match the program's tokenizer."""

import json
import os

import pytest

from benchmark.harness import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark", "traffic")))


def mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", f"{name}.json")) as f:
        return json.load(f)


def served_positions(name, root=REPO):
    """``S``: the smallest ``--max-seq-len`` among the flags of the cells of
    ``<root>/BENCHMARK.json`` that send mix ``name`` (the server's default
    where a cell names none). A mix's caps follow the cells that use it."""
    from benchmark.harness.cell import Cell

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"] if w["traffic"] == name]
    assert cells, f"no cell of BENCHMARK.json sends {name}"
    return min(Cell(root, cell).flag("--max-seq-len", 2048) for cell in cells)


def out_of_bounds(m, served, requests):
    """What of mix ``m`` does not fit a row of ``served`` positions: [] if
    nothing. The context leaves 32 positions (a decode chunk dispatched ahead
    of a request's end); the prompt leaves 256, because engine/batch.py pads a
    prefill chunk to a power of two of at most 256 rows unless that passes the
    row's end, and then compiles a program of the exact length."""
    wrong = []
    if m["context_cap"] > served - 32:
        wrong.append(f"context_cap {m['context_cap']} > {served} - 32")
    if m.get("prompt_cap", m["context_cap"]) > served - 256:
        wrong.append(f"prompt_cap {m.get('prompt_cap')} > {served} - 256: an exact-length compile")
    for r in requests:
        if r.prompt_tokens + r.max_tokens > m["context_cap"] or r.prompt_tokens > m.get("prompt_cap", m["context_cap"]):
            wrong.append(f"request {r.index}: {r.prompt_tokens} + {r.max_tokens} tokens")
    return wrong


def requests_of(m, seed, seconds=20.0, n=40):
    if m["loop"] == "open":
        return traffic.open_loop_schedule(m, seed, seconds)
    gen = traffic.closed_loop_requests(m, seed)
    return [next(gen) for _ in range(n)]


@pytest.fixture(scope="module")
def tokenizer():
    from distributed_llama_tpu.formats.synthetic import synthetic_tokenizer_data
    from distributed_llama_tpu.tokenizer import ChatItem, ChatTemplate, Tokenizer, detect_chat_template

    data = synthetic_tokenizer_data(vocab_size=32000)
    tok = Tokenizer(data)
    template = ChatTemplate(detect_chat_template(data.chat_template), data.chat_template, "</s>")

    def encode(messages):
        text = template.generate([ChatItem(m["role"], m["content"]) for m in messages],
                                 append_generation_prompt=True)
        return tok.encode(text, add_bos=True)

    return encode


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_a_pure_function_of_mix_and_seed(name):
    a, b = requests_of(mix(name), 2**31 + 5), requests_of(mix(name), 2**31 + 5)
    assert [(r.due_s, r.body) for r in a] == [(r.due_s, r.body) for r in b]
    c = requests_of(mix(name), 6)
    assert [r.body for r in a] != [r.body for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_token_counts_match_the_programs_tokenizer(name, tokenizer):
    for r in requests_of(mix(name), 3)[:12]:
        ids = tokenizer(r.body["messages"])
        assert ids == traffic.encode_chat(r.body["messages"])
        assert len(ids) == r.prompt_tokens == traffic.chat_tokens(r.body["messages"])


@pytest.mark.parametrize("name", MIXES)
def test_every_request_fits_the_context_and_never_needs_an_exact_length_compile(name):
    m = mix(name)
    sent = requests_of(m, 11, seconds=45.0, n=200)
    assert out_of_bounds(m, served_positions(name), sent) == []
    assert all(r.body["temperature"] == 0.0 and r.body["stream"] is True for r in sent)


@pytest.mark.parametrize("name,served,fits", [
    ("long_doc_qa", 8192, True),  # its cell's --max-seq-len
    ("long_doc_qa", 2048, False),  # the same mix under a cell that serves 2048 positions
    ("long_doc_qa", 7648, False),  # the context fits, the longest prompt's last chunk would not pad
    ("chat_shared", 2048, True), ("batch_decode", 2048, True), ("single_stream", 2048, True),
    ("batch_prompted", 2048, True),  # the four accepted mixes, unchanged
    ("chat_shared", 2016, False),
])
def test_a_mixs_bounds_follow_the_positions_its_cell_serves(name, served, fits):
    m = mix(name)
    assert (out_of_bounds(m, served, requests_of(m, 11, seconds=45.0, n=64)) == []) is fits
    if name != "long_doc_qa":
        assert served_positions(name) == 2048
    else:
        assert served_positions(name) == 8192


def test_every_seed_sends_the_same_work_at_the_same_instants():
    m = mix("chat_shared")
    runs = [traffic.open_loop_schedule(m, seed, 45.0) for seed in (1, 2, 3, 2**31 + 9)]
    first = [r for r in runs[0] if r.due_s >= m["lead_in_s"]]
    for run in runs[1:]:
        win = [r for r in run if r.due_s >= m["lead_in_s"]]
        assert [(r.due_s, r.prompt_tokens, r.max_tokens, r.turn) for r in win] == \
            [(r.due_s, r.prompt_tokens, r.max_tokens, r.turn) for r in first]
        # what the seed does change: the text
        assert [r.body for r in win] != [r.body for r in first]


def test_open_loop_rate_and_sharing_are_what_the_mix_says():
    m = mix("chat_shared")
    sched = traffic.open_loop_schedule(m, 5, 45.0)
    rate = len(sched) / (m["lead_in_s"] + 45.0)
    assert 0.85 * m["rate_rps"] <= rate <= 1.1 * m["rate_rps"]
    systems = {r.body["messages"][0]["content"] for r in sched}
    assert 2 <= len(systems) <= m["system_prompts"]["pool"]
    later = [r for r in sched if r.turn > 0]
    assert later and all(len(r.body["messages"]) == 2 + 2 * r.turn for r in later)
    assert [r.due_s for r in sched] == sorted(r.due_s for r in sched)


def test_closed_loop_blocks_hold_the_same_multiset_for_every_seed():
    m = mix("batch_decode")
    a = requests_of(m, 1, n=m["block"])
    b = requests_of(m, 2, n=m["block"])
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
    assert sorted(r.prompt_tokens for r in a) == sorted(r.prompt_tokens for r in b)
    assert len({r.body["messages"][0]["content"] for r in a + b}) == 2 * m["block"]


def test_under_documents_a_callers_stream_is_sessions_that_share_their_document():
    m = mix("long_doc_qa")
    asks, block = m["documents"]["asks"], m["block"]
    loop = traffic.closed_loop_requests(m, 2**31 + 7)
    mine, other = loop.caller(0), loop.caller(1)
    a = [next(mine) for _ in range(asks)]
    b = [next(other) for _ in range(asks)]  # drawn in between: a session stays its caller's
    a += [next(mine) for _ in range(asks)]
    for session in (a[:asks], a[asks:], b):
        ids = [traffic.encode_chat(r.body["messages"]) for r in session]
        doc = session[0].body["messages"][0]
        assert doc["role"] == "system" and 4096 <= len(doc["content"]) <= 7168
        assert all(r.body["messages"][0] == doc and len(r.body["messages"]) == 2 for r in session)
        assert [r.turn for r in session] == list(range(asks)) and len({r.session for r in session}) == 1
        shared = 2 + len("<|im_start|>system\n") + len(doc["content"])  # BOS, the space, the document
        for x, y in zip(ids, ids[1:]):
            same = next(i for i, (p, q) in enumerate(zip(x, y)) if p != q)
            assert shared + len("<|im_end|>\n<|im_start|>user\n") == same  # and they differ after it
        assert all(32 <= len(r.body["messages"][1]["content"]) <= 96 and 64 <= r.max_tokens <= 128
                   for r in session)
    assert len({r.body["messages"][0]["content"] for r in a + b}) == 3  # each session a fresh document
    assert loop.caller(0) is mine  # a later phase of the run goes on where the caller stood
    assert len({r.index for r in a + b}) == 3 * asks
    # a block holds the same shapes for every seed: block // asks documents, block questions
    blocks = []
    for seed in (1, 2):
        gen = traffic.closed_loop_requests(m, seed)
        reqs = [next(gen) for _ in range(block)]
        blocks.append((sorted(len(r.body["messages"][0]["content"]) for r in reqs[::asks]),
                       sorted(len(r.body["messages"][1]["content"]) for r in reqs),
                       sorted(r.max_tokens for r in reqs)))
        assert [r.turn for r in reqs] == list(range(asks)) * (block // asks)
    assert blocks[0] == blocks[1] and len(blocks[0][0]) == block // asks
    with pytest.raises(ValueError, match="whole sessions"):
        next(traffic.closed_loop_requests(dict(m, block=62), 1))


def test_without_documents_every_callers_stream_is_the_one_shared_stream():
    m = mix("batch_decode")
    loop, flat = traffic.closed_loop_requests(m, 5), traffic.closed_loop_requests(m, 5)
    drawn = [next(loop.caller(i % 3)) for i in range(12)]  # three callers by turns
    assert [(r.index, r.body, r.session, r.turn) for r in drawn] == \
        [(r.index, r.body, 0, 0) for r in (next(flat) for _ in range(12))]


@pytest.mark.parametrize("name", MIXES)
def test_a_mix_draws_its_text_from_its_own_alphabet_or_the_generators(name):
    m = mix(name)
    chars = traffic.mix_alphabet(m)
    assert chars == m.get("alphabet", traffic.ALPHABET)
    text = "".join(msg["content"] for r in requests_of(m, 5) for msg in r.body["messages"])
    assert set(text) == set(chars)  # some thousands of characters: every one is drawn


@pytest.mark.parametrize("chars", ["", "abca", "ab c", "abh", "ab\u00e9", "ab\n"],
                         ids=["empty", "twice", "space", "merges", "not-ascii", "control"])
def test_an_alphabet_that_could_break_one_character_one_token_is_refused(chars):
    m = dict(mix("batch_decode"), alphabet=chars)
    with pytest.raises(ValueError, match="alphabet"):
        next(traffic.closed_loop_requests(m, 1))
    with pytest.raises(ValueError, match="alphabet"):
        traffic.open_loop_schedule(dict(mix("chat_shared"), alphabet=chars), 1, 5.0)


@pytest.mark.parametrize("name", MIXES)
def test_warmup_touches_every_prefill_bucket_the_mix_can_reach(name):
    m = mix(name)
    pool = 384 * 64
    waves = traffic.warmup_waves(dict(m, warm_pool_overflow=True), 1, 16, pool)
    # past 16 pages: 2068 and 4116 tokens, then the longest prompt itself (long_doc_qa alone)
    longer = [2068, 4116, m["prompt_cap"]] if m["prompt_cap"] >= 2068 else []
    plain = traffic.warmup_waves(dict(m, warm_pool_overflow=False), 1, 16, pool)
    assert len(plain) == 12 + len(longer)
    assert [w[0].prompt_tokens for w in plain[5:5 + len(longer)]] == longer
    singles = [w[0].prompt_tokens for w in waves if len(w) == 1]

    def buckets(n):
        out = set()
        while n > 0:
            c = min(256, n)
            out.add(max(8, 1 << (c - 1).bit_length()))
            n -= c
        return out

    def page_bucket(n):  # engine/batch.py: _page_bucket over the whole pages a cold prompt publishes
        return 1 << max(0, n // 64 - 1).bit_length()

    sent = requests_of(m, 4, n=64)
    warmed = set().union(*(buckets(n) for n in singles))
    needed = set().union(*(buckets(r.prompt_tokens) for r in sent))
    assert needed <= warmed
    # ... and every count of pages a prompt of the mix publishes at once, its longest one's too
    assert {page_bucket(r.prompt_tokens) for r in sent} | {page_bucket(m["prompt_cap"])} <= \
        {page_bucket(n) for n in singles}
    if longer:  # the deepest scan over a row's own positions runs before the window
        assert max(singles) == m["prompt_cap"] >= max(r.prompt_tokens for r in sent)
    assert sum(n for n in singles if n >= 1024) > pool  # the pool overflows
    ramp = waves[-1]
    assert len(ramp) == 16 and ramp[0].due_s == 0.0 and ramp[-1].due_s > ramp[0].due_s


@pytest.mark.parametrize("text,count,want", [
    ("<filler_300><filler_31999><filler_5>", 3, [300, 31999, 5]),
    ("<filler_300><filler_301>", 3, []),  # a token left no text: where, nobody knows
    ("<filler_300><filler_301>", 1, []),
    ("<filler_300>x<filler_5>", 3, []),  # a letter is a piece or a byte
    ("<filler_9><<filler_5>", 3, []),
    ("hello", 1, []),
    ("", 0, []),
])
def test_answer_ids_reads_back_only_what_is_certain(text, count, want):
    assert traffic.answer_ids(text, count) == want


def test_the_first_filler_follows_the_synthetic_vocabularys_pieces():
    from distributed_llama_tpu.formats.synthetic import synthetic_tokenizer_data

    vocab = synthetic_tokenizer_data(vocab_size=400).vocab
    assert vocab[traffic.FIRST_FILLER_ID] == f"<filler_{traffic.FIRST_FILLER_ID}>".encode()
    assert not vocab[traffic.FIRST_FILLER_ID - 1].startswith(b"<filler_")
