"""The one general traffic generator: (mix parameters, seed, seconds) -> schedule.

A traffic mix is a data file under ``benchmark/traffic/``; this module is the
only code that reads one. Sizes, think times and arrival gaps are stratified
quantiles of the mix's distributions, so every seed gets the same multiset of
them, and different text: a seed that changed the amount of work would show
as run-to-run spread that is not the system's.

Prompt text is drawn from characters the synthetic tokenizer never merges, so
one character is one token and ``chat_tokens`` is exact; set-up checks it
against the server's ``usage.prompt_tokens``. A mix may name the characters
its own text is drawn from (``alphabet``): how many distinct tokens a prompt
holds decides how alike its rows route in a sparse-expert model, which is
work (PERF.md, PR 29).
"""

from __future__ import annotations

import dataclasses
import math
import random
import re
import statistics

# the synthetic vocabulary (formats/synthetic.py): <unk> <s> </s>, 256 byte
# pieces, then these pieces in this order, then never-merged fillers
_PIECES = (" ", "h", "e", "l", "o", "he", "ll", "hell", "hello", " hello",
           "w", "r", "d", "wo", "wor", "worl", "world", " world")
_PIECE_ID = {p: 259 + i for i, p in enumerate(_PIECES)}
BOS_ID = 1
# every id below this is a special, a byte or a word piece; the benchmark's
# weights never emit one (harness/modelfile.py), so an answer is fillers only
FIRST_FILLER_ID = 259 + len(_PIECES)
# pairs of single-character pieces that the tokenizer would merge
_MERGES = ("he", "ll", "wo")
# prompt characters: ASCII letters and digits outside every multi-char piece
ALPHABET = "abcfgijkmnpqstuvxyz0123456789"
_FILLER_RE = re.compile(r"<filler_(\d+)>")


def render_chatml(messages: list[dict]) -> str:
    """The text the server's chatml template makes of ``messages`` (with the
    generation prompt), as tokenizer.ChatTemplate renders it."""
    out = [f"<|im_start|>{m['role']}\n{m['content']}<|im_end|>\n" for m in messages]
    return "".join(out) + "<|im_start|>assistant\n"


def encode_chat(messages: list[dict]) -> list[int]:
    """Token ids the server prefills for ``messages``: BOS, the dummy-prefix
    space, then one id per character (a vocabulary piece where the character
    is one, its byte-fallback id otherwise). Raises if the text holds a pair
    the tokenizer would merge, so a wrong count cannot pass silently."""
    text = render_chatml(messages)
    for pair in _MERGES:
        if pair in text:
            raise ValueError(f"prompt text contains the mergeable pair {pair!r}")
    if not text.isascii():
        raise ValueError("prompt text must be ASCII")
    ids = [BOS_ID, _PIECE_ID[" "]]
    ids.extend(_PIECE_ID.get(ch, 3 + ord(ch)) for ch in text)
    return ids


def chat_tokens(messages: list[dict]) -> int:
    return 2 + len(render_chatml(messages))


def answer_ids(text: str, count: int) -> list[int]:
    """The token ids of a completion of ``count`` tokens: the text of a model
    with the benchmark's weights is ``count`` pieces ``<filler_N>``, one per
    token N. [] where it is anything else: a piece that is not a filler could
    be either of two tokens or hide one, and then no position is certain."""
    ids = [int(n) for n in _FILLER_RE.findall(text)]
    whole = "".join(f"<filler_{n}>" for n in ids) == text
    return ids if whole and len(ids) == count else []


# ---------------------------------------------------------------------------
# stratified draws
# ---------------------------------------------------------------------------


def _ppf(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "constant":
        x = float(dist["value"])
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "exponential":
        x = -dist["mean"] * math.log1p(-u)
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * statistics.NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        x = max(x, dist["min"])
    if "max" in dist:
        x = min(x, dist["max"])
    return x


def stratified(dist: dict, n: int, rng: random.Random, integer: bool = False) -> list:
    """``n`` values at the quantiles (i + 0.5)/n of ``dist``, shuffled by
    ``rng``: the same multiset for every seed."""
    vals = [_ppf(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [int(round(v)) for v in vals]
    rng.shuffle(vals)
    return vals


def _zipf_counts(pool: int, s: float, n: int) -> list[int]:
    """How many of ``n`` draws go to each of ``pool`` items under Zipf(s),
    by largest remainder: fixed counts, not a random sample."""
    w = [1.0 / (i + 1) ** s for i in range(pool)]
    total = sum(w)
    exact = [n * x / total for x in w]
    counts = [int(e) for e in exact]
    order = sorted(range(pool), key=lambda i: exact[i] - counts[i], reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _geometric_turns(mean: float, cap: int, n: int) -> list[int]:
    """Stratified turns per session: geometric with the given mean, capped."""
    if cap <= 1 or mean <= 1.0:
        return [1] * n
    p = 1.0 / mean
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        k = 1 + int(math.log1p(-u) / math.log1p(-p))
        out.append(min(k, cap))
    return out


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One request the generator will send. ``due_s`` is seconds after the
    start of the lead-in (open loop); closed-loop requests carry 0."""

    index: int
    due_s: float
    body: dict
    prompt_tokens: int
    max_tokens: int
    session: int = 0
    turn: int = 0


def _text(rng: random.Random, n: int, chars: str = ALPHABET) -> str:
    return "".join(rng.choices(chars, k=n))


def mix_alphabet(mix: dict) -> str:
    """The characters the mix's prompt text is drawn from: its ``alphabet``,
    else ``ALPHABET``. Each is a printable ASCII character, named once, and
    none is a letter of a multi-character piece, so no two can merge and one
    character stays one token."""
    chars = mix.get("alphabet", ALPHABET)
    merging = set("".join(p for p in _PIECES if len(p) > 1))
    bad = [c for c in chars if not ("!" <= c <= "~") or c in merging]
    if not chars or bad or len(set(chars)) != len(chars):
        raise ValueError(f"mix alphabet {chars!r}: empty, a character twice, or one that is not "
                         f"printable ASCII or could merge: {bad!r}")
    return chars


def _body(messages: list[dict], max_tokens: int) -> dict:
    return {"messages": messages, "max_tokens": max_tokens, "temperature": 0.0,
            "seed": 0, "stream": True, "stop": []}


def open_loop_schedule(mix: dict, seed: int, seconds: float) -> list[Request]:
    """Sessions arriving on a fixed schedule. The slots (arrival instants,
    turns per session, think times, prompt and output sizes) come from the
    mix's ``template_seed`` and are the same for every seed, so every seed
    sends the same requests at the same instants: at the rates this system
    sustains a window holds some tens of requests, and a seed that moved
    sizes or shared prefixes between them would move the work by a tenth (two
    seeds' 95th-percentile TTFTs differed by a third when the seed dealt the
    system prompts, PERF.md PR 22). The seed makes the text, and the weights.
    Sessions start ``backfill_s`` before the lead-in so that later turns of
    earlier sessions are already arriving when measurement starts; turns due
    before 0 are not sent (a turn's body carries its whole history, so
    nothing depends on them)."""
    rng = random.Random(seed)
    chars = mix_alphabet(mix)
    slot_rng = random.Random(mix.get("template_seed", 1))
    ses = mix.get("sessions", {})
    turns_cap = int(ses.get("turns_max", 1))
    turns_mean = float(ses.get("turns_mean", 1.0))
    backfill = float(ses.get("backfill_s", 0.0)) if turns_cap > 1 else 0.0
    horizon = float(mix["lead_in_s"]) + seconds
    span = backfill + horizon
    thousand = _geometric_turns(turns_mean, turns_cap, 1000)
    n_sessions = max(1, round(mix["rate_rps"] * span / (sum(thousand) / 1000)))

    turns = _geometric_turns(turns_mean, turns_cap, n_sessions)
    slot_rng.shuffle(turns)
    gaps = stratified({"dist": "exponential", "mean": 1.0}, n_sessions, slot_rng)
    scale = span / sum(gaps)
    n_turns_total = sum(turns)
    thinks = (stratified(ses["think_s"], n_turns_total, slot_rng)
              if turns_cap > 1 else [0.0] * n_turns_total)
    outs = stratified(mix["output_tokens"], n_turns_total, slot_rng, integer=True)

    users = stratified(mix["user_tokens"], n_turns_total, slot_rng, integer=True)
    sysp = mix.get("system_prompts")
    sys_texts = ([_text(rng, int(sysp["tokens"]), chars) for _ in range(int(sysp["pool"]))]
                 if sysp else [])
    sys_ids = ([i for i, c in enumerate(_zipf_counts(len(sys_texts), float(sysp["zipf_s"]), n_sessions))
                for _ in range(c)] if sysp else [None] * n_sessions)
    slot_rng.shuffle(sys_ids)
    cap = int(mix["context_cap"])
    prompt_cap = int(mix.get("prompt_cap", cap))
    min_user = int(mix["user_tokens"].get("min", 1))

    out: list[Request] = []
    t, at = -backfill, 0
    for s, k in enumerate(turns):
        t += gaps[s] * scale
        messages: list[dict] = []
        if sys_ids[s] is not None:
            messages.append({"role": "system", "content": sys_texts[sys_ids[s]]})
        due = t
        for turn in range(k):
            if turn:
                due += thinks[at + turn]
            n_out = outs[at + turn]
            # a user turn shrinks to what the context still holds
            room = min(cap - n_out, prompt_cap) - chat_tokens(messages + [{"role": "user", "content": ""}])
            n_user = min(users[at + turn], room)
            if n_user < min_user or due >= horizon:
                break  # the session has filled its context, or the run ends
            messages = messages + [{"role": "user", "content": _text(rng, n_user, chars)}]
            n_prompt = chat_tokens(messages)
            if due >= 0.0:
                out.append(Request(0, due, _body(messages, n_out), n_prompt, n_out, s, turn))
            # the history a later turn carries: generator text of the asked length
            messages = messages + [{"role": "assistant", "content": _text(rng, n_out, chars)}]
        at += k
    out.sort(key=lambda r: r.due_s)
    for i, r in enumerate(out):
        r.index = i
    return out


def _sessions(mix: dict, seed: int):
    """An endless stream of a closed loop's sessions, each a list of requests
    one caller sends in turn: blocks of ``block`` stratified shapes, each block
    shuffled by the seed. Without ``documents`` a session is ONE single-turn
    request. With ``documents`` ``{"tokens": <dist>, "asks": <n>}`` it is a
    fresh document (a ``system`` message of that many tokens, text from the
    seed) asked ``asks`` times, each ask with its own question and answer
    length: the asks share the document's tokens as a prefix and differ after
    it. A block then holds ``block // asks`` documents, their sizes stratified
    like the questions' and the answers'."""
    rng = random.Random(seed)
    chars = mix_alphabet(mix)
    block = int(mix.get("block", 64))
    cap = int(mix["context_cap"])
    prompt_cap = int(mix.get("prompt_cap", cap))
    docs = mix.get("documents")
    asks = int(docs["asks"]) if docs else 1
    if asks < 1 or block % asks:
        raise ValueError(f"a block of {block} shapes does not hold whole sessions of {asks} asks")
    index = count = 0
    while True:
        users = stratified(mix["user_tokens"], block, rng, integer=True)
        outs = stratified(mix["output_tokens"], block, rng, integer=True)
        sizes = stratified(docs["tokens"], block // asks, rng, integer=True) if docs else []
        for s in range(block // asks):
            head = [{"role": "system", "content": _text(rng, sizes[s], chars)}] if docs else []
            session = []
            for turn in range(asks):
                n_user, n_out = users[s * asks + turn], outs[s * asks + turn]
                messages = head + [{"role": "user", "content": _text(rng, n_user, chars)}]
                n_prompt = chat_tokens(messages)
                if n_prompt + n_out > cap or n_prompt > prompt_cap:
                    raise ValueError(f"closed-loop shape {n_prompt}+{n_out} exceeds context_cap {cap} "
                                     f"or prompt_cap {prompt_cap}")
                session.append(Request(index, 0.0, _body(messages, n_out), n_prompt, n_out, count, turn))
                index += 1
            count += bool(docs)  # a mix of single requests numbers no sessions, as before
            yield session


class ClosedLoop:
    """A closed loop's requests. Iterated, it is the sessions' requests one
    after the other: the stream every caller draws from where a session is one
    request. ``caller(i)`` is caller ``i``'s own stream, the same one each
    time it is asked for: whole sessions, each taken from the shared stream
    when the caller's last one is used up, so that the asks of one document
    are sent in turn by the one caller that holds it, in a later phase of the
    run too. Not locked: the load loop draws under its own lock."""

    def __init__(self, mix: dict, seed: int):
        self._sessions = _sessions(mix, seed)
        self._callers: dict = {}

    def _stream(self):
        while True:
            yield from next(self._sessions)

    def caller(self, i: int):
        if i not in self._callers:
            self._callers[i] = self._stream()
        return self._callers[i]

    def __iter__(self):
        return self

    def __next__(self) -> Request:
        return next(self.caller(None))


def closed_loop_requests(mix: dict, seed: int) -> ClosedLoop:
    """An endless stream of requests for a closed loop (``_sessions`` says
    what a mix makes of it)."""
    return ClosedLoop(mix, seed)


def probe_requests(seed: int, count: int, prompt_tokens: int, max_tokens: int,
                   long_count: int = 0, long_prompt_tokens: int = 0) -> list[Request]:
    """Fixed greedy probes (unique text from the seed), sent alone and not
    streamed: the answers the reference is compared with. The last
    ``long_count`` of the ``count`` have prompts of ``long_prompt_tokens``
    tokens: the positions of a long context are compared too. The short ones
    come first, so they are the same probes whatever follows them."""
    rng = random.Random(seed ^ 0x5EED)
    out = []
    for i in range(count):
        n_prompt = long_prompt_tokens if i >= count - long_count else prompt_tokens
        n_user = n_prompt - chat_tokens([{"role": "user", "content": ""}])
        messages = [{"role": "user", "content": _text(rng, n_user)}]
        body = {**_body(messages, max_tokens), "stream": False, "cache": "off"}
        out.append(Request(i, 0.0, body, chat_tokens(messages), max_tokens))
    return out


def warmup_waves(mix: dict, seed: int, rows: int, pool_tokens: int) -> list[list[Request]]:
    """Waves of unmeasured requests that touch every shape the mix can make
    the server compile; a wave's requests are sent ``due_s`` after the wave
    starts and the next wave waits for all of them. First, alone, one prompt
    for each power-of-two count of pages a prefill publishes (1..16, and on
    doubling while a prompt of that many pages fits the mix's ``prompt_cap``,
    then one of ``prompt_cap`` tokens itself: the bucket of the longest prompt)
    and one whose tail covers each prefill bucket (8..256 rows). Then, where the mix says
    ``warm_pool_overflow`` (its traffic fills the page pool within a run),
    enough unique long prompts to overflow the pool of ``pool_tokens``
    positions: the evictor's spill program runs, and the pool is left full,
    its steady state in a server that has been up for a while. Last a ramp of
    1, 2, 4, .. ``rows`` concurrent streams, so the batched decode program
    runs at every row bucket."""
    rng = random.Random(seed ^ 0xA11)
    base = chat_tokens([{"role": "user", "content": ""}])

    def one(n_prompt: int, n_out: int, due_s: float = 0.0) -> Request:
        messages = [{"role": "user", "content": _text(rng, n_prompt - base)}]
        return Request(0, due_s, _body(messages, n_out), chat_tokens(messages), n_out)

    cap = int(mix.get("prompt_cap", int(mix["context_cap"]) - 64))
    lengths = [64 * pages + 20 for pages in (1, 2, 4, 8, 16)]
    pages = 32
    while 64 * pages + 20 <= cap:  # a mix of longer prompts publishes more pages at once
        lengths.append(64 * pages + 20)
        pages *= 2
    if pages > 32 and lengths[-1] < cap:
        # ... and its longest prompt lies between two powers: the page bucket above the last
        # doubling, and the deepest scan over a row's own positions, are built here too
        lengths.append(cap)
    # the prefill program is keyed by its chunk's padded rows alone: one tail per bucket
    lengths += [256 + b - 3 for b in (8, 16, 32, 64, 128, 256)]
    if mix.get("warm_pool_overflow"):
        fill = max(cap - 256, cap // 2)
        lengths += [fill] * (pool_tokens // fill + 2)
    waves = [[one(min(n, cap), 1)] for n in lengths]
    ramp, stage, at = [], 0, 0
    while at < rows:
        add = max(1, at)  # 1, 1, 2, 4, 8: rows 0, 1, 2-3, 4-7, 8-15
        for _ in range(min(add, rows - at)):
            # every stream outlives the last stage by about one decode chunk
            ramp.append(one(base + 24, 48 + 64 * (5 - min(stage, 5)), 1.5 * stage))
        at += add
        stage += 1
    waves.append(ramp)
    return waves
