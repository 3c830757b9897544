"""Tokenizer, sampler, chat templates and streaming stop detection.

Capability parity with the reference's `src/tokenizer.cpp` (SentencePiece-style
BPE encode at tokenizer.cpp:170-292, decode at 150-161, Sampler at 294-415,
ChatTemplate at 436-500, EosDetector at 502-575) — reimplemented for a host
Python runtime driving a TPU model. The vocabulary is kept as raw ``bytes``
(the reference's char* vocab), so arbitrary byte-fallback tokens round-trip.

The sampler here is the *host* sampler used by the CLI for parity with the
reference's semantics (including its xorshift RNG so seeded runs match).
The TPU decode loop has an additional on-device sampler (see
``distributed_llama_tpu.models.sampling``) that avoids per-token host sync.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Sequence

import numpy as np

from distributed_llama_tpu.formats.tokenizer_file import TokenizerData, read_tokenizer_file

_RAW_BYTE_RE = re.compile(rb"^<0x([0-9A-Fa-f]{2})>$")


class Tokenizer:
    """Byte-level SentencePiece/BPE tokenizer over a `.t` vocabulary.

    Encode algorithm (reference: src/tokenizer.cpp:170-292): optional BOS,
    optional dummy-prefix space token, UTF-8 codepoint split with byte
    fallback (+3 offset), then greedy highest-score pair merging.
    """

    def __init__(self, data: TokenizerData):
        self.data = data
        self.vocab: list[bytes] = data.vocab
        self.scores: list[float] = data.scores
        self.bos_id = data.bos_id
        self.eos_id = data.eos_id
        self.chat_eos_id = data.chat_eos_id
        self.chat_template = data.chat_template
        self.chat_stop = data.chat_stop
        # first-wins (lowest id) for duplicate pieces; the reference's
        # qsort+bsearch resolves duplicates arbitrarily, a dict is
        # deterministic and O(1)
        self._index: dict[bytes, int] = {}
        for i, tok in enumerate(self.vocab):
            self._index.setdefault(tok, i)
        # the O(n^2) split+merge core runs natively when the host lib is
        # built (same algorithm, see native/bpe_native.cpp)
        from distributed_llama_tpu import native

        self._native = (
            native.NativeBpe(self.vocab, self.scores) if native.available() else None
        )

    @classmethod
    def from_file(cls, path: str, model_vocab_size: int | None = None) -> "Tokenizer":
        data = read_tokenizer_file(path)
        if model_vocab_size is not None and data.vocab_size != model_vocab_size:
            raise ValueError(
                f"tokenizer vocab size {data.vocab_size} != model vocab size {model_vocab_size}"
            )
        return cls(data)

    @property
    def vocab_size(self) -> int:
        return len(self.vocab)

    def encode(self, text: str | bytes, add_bos: bool = False, add_eos: bool = False) -> list[int]:
        if isinstance(text, str):
            text = text.encode("utf-8")
        tokens: list[int] = []
        if add_bos:
            tokens.append(self.bos_id)

        if self._native is not None:
            # the dummy-prefix space token participates in merging exactly as
            # if the text began with a literal space (it is the " " piece)
            prefixed = (b" " if text and b" " in self._index else b"") + text
            tokens.extend(self._native.encode(prefixed))
            if add_eos:
                tokens.append(self.eos_id)
            return tokens

        # dummy prefix space (sentencepiece add_dummy_prefix;
        # reference: src/tokenizer.cpp:198-207)
        if text:
            space_id = self._index.get(b" ")
            if space_id is not None:
                tokens.append(space_id)

        # split into UTF-8 codepoints (≤4 bytes), byte-fallback unknown ones
        i = 0
        n = len(text)
        while i < n:
            j = i + 1
            # extend while continuation bytes, capped at 4 bytes total
            while j < n and (text[j] & 0xC0) == 0x80 and (j - i) < 4:
                j += 1
            piece = text[i:j]
            tid = self._index.get(piece)
            if tid is not None:
                tokens.append(tid)
            else:
                # byte fallback: first 3 vocab entries are <unk>, <s>, </s>
                # (reference: src/tokenizer.cpp:247-252)
                tokens.extend(b + 3 for b in piece)
            i = j

        # greedy merge: repeatedly replace the adjacent pair whose
        # concatenation has the best vocab score
        # (reference: src/tokenizer.cpp:257-286)
        while True:
            best_score = -1e10
            best_id = -1
            best_idx = -1
            for k in range(len(tokens) - 1):
                merged = self.vocab[tokens[k]] + self.vocab[tokens[k + 1]]
                mid = self._index.get(merged)
                if mid is not None and self.scores[mid] > best_score:
                    best_score = self.scores[mid]
                    best_id = mid
                    best_idx = k
            if best_idx == -1:
                break
            tokens[best_idx : best_idx + 2] = [best_id]

        if add_eos:
            tokens.append(self.eos_id)
        return tokens

    def decode_piece(self, prev_token: int, token: int) -> bytes:
        """Decode a single token following ``prev_token`` to raw bytes.

        Mirrors reference src/tokenizer.cpp:150-161: strips one leading space
        after BOS and converts `<0xNN>` raw-byte pieces to their byte. (The
        reference gates the raw-byte branch on ``sscanf(...) == bosId``, which
        only fires when bosId==1 — true for every sentencepiece vocab that
        actually contains `<0xNN>` pieces, so matching the pattern directly is
        behaviorally identical.)
        """
        piece = self.vocab[token]
        if prev_token == self.bos_id and piece.startswith(b" "):
            piece = piece[1:]
        m = _RAW_BYTE_RE.match(piece)
        if m:
            return bytes([int(m.group(1), 16)])
        return piece

    def decode(self, tokens: Sequence[int]) -> str:
        out = bytearray()
        prev = self.bos_id
        for t in tokens:
            if t == self.bos_id:
                prev = t
                continue
            out += self.decode_piece(prev, t)
            prev = t
        return out.decode("utf-8", errors="replace")


def is_safe_piece(piece: bytes) -> bool:
    """Filter lone control bytes (reference: src/tokenizer.cpp:19-31).

    Deliberate deviation from the reference's C-locale isprint: lone bytes
    >= 0x80 are KEPT — they are byte-fallback fragments of multi-byte UTF-8
    (e.g. 'é' emitted as <0xC3><0xA9>) that downstream byte buffers
    (EosDetector, the API chunker) reassemble into real characters; the
    reference silently drops them. Lone ASCII control bytes (except
    whitespace) and DEL are still unsafe."""
    if not piece:
        return False
    if len(piece) == 1:
        b = piece[0]
        if b < 0x20:
            return b in (0x09, 0x0A, 0x0B, 0x0C, 0x0D)
        return b != 0x7F
    return True


# ---------------------------------------------------------------------------
# RNG + sampling (host path)
# ---------------------------------------------------------------------------


class XorshiftRng:
    """xorshift64* RNG, bit-identical to the reference for seeded parity
    (reference: src/utils.cpp:79-90)."""

    MASK = (1 << 64) - 1

    def __init__(self, seed: int):
        self.state = seed & self.MASK

    def next_u32(self) -> int:
        s = self.state
        s ^= (s >> 12)
        s ^= (s << 25) & self.MASK
        s ^= (s >> 27)
        self.state = s
        return ((s * 0x2545F4914F6CDD1D) & self.MASK) >> 32

    def next_f32(self) -> float:
        return (self.next_u32() >> 8) / 16777216.0


def _softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max()
    e = np.exp(x, dtype=np.float64)
    return (e / e.sum()).astype(np.float32)


@dataclasses.dataclass
class Sampler:
    """Greedy / temperature / top-k / top-p sampling on host logits
    (reference: src/tokenizer.cpp:371-415).

    Two RNG modes:

    * legacy (``counter=False``): the reference's sequential xorshift64*
      state — one coin per call in call order, bit-identical to the
      reference's draw sequence (the interop contract).
    * counter (``counter=True``): the stateless counter PRNG of
      :mod:`distributed_llama_tpu.prng`, coin keyed ``(seed, pos)`` — the
      host half of the device-sampling parity contract (ISSUE 13). Fed
      the same f32 logits, this mode replays a device-sampled stream
      token for token: identical candidate order (descending scaled
      logit, ties by id), identical f32 filter/CDF arithmetic, identical
      coins. ``sample`` then REQUIRES ``pos`` (the absolute position of
      the consumed token). Exact on the filtered (top-k/top-p) paths;
      the unfiltered multinomial path walks a full-vocab cumsum whose
      device counterpart may associate differently by ulps.

    Every ``sample`` call counts toward
    ``dllama_host_sampler_fallback_total``: with the fused device sampler
    in place, host sampling IS the fallback path."""

    vocab_size: int
    temperature: float = 0.8
    topp: float = 0.9
    seed: int = 0
    topk: int = 0
    counter: bool = False

    def __post_init__(self):
        self._rng = XorshiftRng(self.seed)
        from distributed_llama_tpu import prng as _prng

        self._seed32 = _prng.fold_seed(self.seed)
        # sampler-distribution counters (ISSUE 1): bound once per sampler —
        # shared no-op singletons when telemetry is disabled, so the
        # per-token host-sampling path never touches the registry
        from distributed_llama_tpu import telemetry

        self._tel = telemetry.SamplerInstruments()

    def set_seed(self, seed: int) -> None:
        from distributed_llama_tpu import prng as _prng

        self.seed = seed
        self._rng = XorshiftRng(seed)
        self._seed32 = _prng.fold_seed(seed)

    def set_temperature(self, temperature: float) -> None:
        self.temperature = temperature

    def set_topk(self, topk: int) -> None:
        self.topk = int(topk)

    def _coin(self, pos: int | None) -> float:
        if not self.counter:
            return self._rng.next_f32()
        if pos is None:
            raise ValueError(
                "counter-mode Sampler.sample needs pos (the absolute "
                "position of the consumed token) to key its coin"
            )
        from distributed_llama_tpu import prng as _prng

        return float(_prng.coin_f32(self._seed32, pos, _prng.DRAW_SAMPLE))

    def sample(self, logits: np.ndarray, pos: int | None = None) -> int:
        logits = np.asarray(logits, dtype=np.float32).reshape(-1)[: self.vocab_size]
        if not np.isfinite(logits).all():
            # validate BEFORE sampling (ISSUE 10 satellite): NaN/Inf
            # logits pushed through the softmax/CDF below launder into a
            # perfectly in-vocab token id — the device path's
            # out-of-vocab check never sees it, and greedy argmax just
            # returns the first NaN's index. Fail typed instead; the
            # serving layer retires the request like any corrupt chunk.
            from distributed_llama_tpu.engine import faults

            raise faults.NonFiniteLogits(
                "host sampler got non-finite logits "
                f"({int((~np.isfinite(logits)).sum())} of {logits.size} "
                "entries); refusing to sample a plausible-but-wrong token"
            )
        self._tel.fallback.inc()
        if self.temperature == 0.0:
            self._tel.sampled.labels(method="greedy").inc()
            return int(np.argmax(logits))
        if self.counter or 0 < self.topk < logits.size:
            # top-k predates nothing: the legacy draw arithmetic never had
            # it, so an ACTIVE top-k always routes through the fused-pick
            # mirror (fed the legacy sequential coin when counter is off)
            # rather than being silently ignored
            return self._sample_counter(logits, self._coin(pos))
        probs = _softmax(logits / self.temperature)
        coin = self._coin(pos)
        if self.topp <= 0 or self.topp >= 1:
            self._tel.sampled.labels(method="multinomial").inc()
            return self._sample_mult(probs, coin)
        self._tel.sampled.labels(method="topp").inc()
        return self._sample_topp(probs, coin)

    def _sample_counter(self, logits: np.ndarray, coin: float) -> int:
        """The device fused sampler's arithmetic, op for op in f32
        (models/sampling.py ``fused_pick``): candidates ordered by
        descending temperature-scaled logit (ties by lower id), the kept
        prefix is min(top-k, nucleus), the draw is inverse-CDF over the
        kept prefix's f32 cumulative mass — same values, same coin, same
        pick as the device program this mode verifies."""
        n = logits.size
        scaled = (logits / np.float32(self.temperature)).astype(np.float32)
        m = scaled.max()
        e = np.exp(scaled - m, dtype=np.float32)
        probs = (e / e.sum(dtype=np.float32)).astype(np.float32)
        coin = np.float32(coin)
        topp_act = 0.0 < self.topp < 1.0
        topk_act = 0 < self.topk < n
        if not (topp_act or topk_act):
            # multinomial: vocab-order inverse CDF over the full mass
            self._tel.sampled.labels(method="multinomial").inc()
            cdf = np.cumsum(probs, dtype=np.float32)
            r = coin * cdf[-1]
            return min(int(np.sum(cdf <= r)), n - 1)
        self._tel.sampled.labels(method="topp" if topp_act else "topk").inc()
        order = np.argsort(-scaled, kind="stable")
        vals = probs[order]
        cum = np.cumsum(vals, dtype=np.float32)
        n_nuc = int(np.sum(cum - vals < np.float32(self.topp))) if topp_act else n
        n_k = self.topk if topk_act else n
        n_keep = max(1, min(n_nuc, n_k, n))
        total = cum[n_keep - 1]
        r = coin * total
        idx = min(int(np.sum(cum[:n_keep] <= r)), n_keep - 1)
        return int(order[idx])

    @staticmethod
    def _sample_mult(probs: np.ndarray, coin: float) -> int:
        cdf = np.cumsum(probs, dtype=np.float64)
        idx = int(np.searchsorted(cdf, coin, side="right"))
        return min(idx, probs.size - 1)

    def _sample_topp(self, probs: np.ndarray, coin: float) -> int:
        n = probs.size
        # pre-filter: values below (1-topp)/(n-1) can never be in the nucleus
        # (reference: src/tokenizer.cpp:334-345)
        cutoff = (1.0 - self.topp) / (n - 1)
        cand = np.nonzero(probs >= cutoff)[0]
        order = cand[np.argsort(-probs[cand], kind="stable")]
        sorted_probs = probs[order]
        cum = np.cumsum(sorted_probs, dtype=np.float64)
        # truncate where cumulative prob exceeds topp (inclusive)
        over = np.nonzero(cum > self.topp)[0]
        last_idx = int(over[0]) if over.size else order.size - 1
        total = cum[last_idx]
        r = coin * total
        idx = int(np.searchsorted(cum[: last_idx + 1], r, side="right"))
        idx = min(idx, last_idx)
        return int(order[idx])


# ---------------------------------------------------------------------------
# Chat templates
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ChatItem:
    role: str
    message: str


class ChatTemplateType:
    UNKNOWN = "unknown"
    LLAMA2 = "llama2"
    LLAMA3 = "llama3"
    ZEPHYR = "zephyr"
    CHATML = "chatml"


def detect_chat_template(template: str | None) -> str:
    """Substring-sniff the embedded jinja template
    (reference: src/tokenizer.cpp:440-450)."""
    if template is None:
        raise ValueError("the tokenizer does not include a chat template")
    if "[INST]" in template:
        return ChatTemplateType.LLAMA2
    if "<|start_header_id|>" in template:
        return ChatTemplateType.LLAMA3
    if "<|user|>" in template:
        return ChatTemplateType.ZEPHYR
    if "<|im_start|>" in template:
        return ChatTemplateType.CHATML
    raise ValueError("unsupported chat template")


class ChatTemplate:
    """Hardcoded renderers per detected template family
    (reference: src/tokenizer.cpp:468-500)."""

    def __init__(self, template_type: str, chat_template: str | None, eos: str):
        if template_type == ChatTemplateType.UNKNOWN:
            template_type = detect_chat_template(chat_template)
        self.type = template_type
        self.eos = eos

    def generate(self, items: Sequence[ChatItem], append_generation_prompt: bool = True) -> str:
        out: list[str] = []
        if self.type == ChatTemplateType.LLAMA2:
            i = 0
            if len(items) >= 2 and items[0].role == "system" and items[1].role == "user":
                out.append(
                    f"[INST] <<SYS>>\n{items[0].message}\n<</SYS>>\n\n{items[1].message} [/INST]{self.eos}"
                )
                i = 2
            for item in items[i:]:
                if item.role == "assistant":
                    out.append(f"{item.message}{self.eos}")
                elif item.role == "user":
                    out.append(f"[INST] {item.message} [/INST]{self.eos}")
        elif self.type == ChatTemplateType.LLAMA3:
            for item in items:
                out.append(
                    f"<|start_header_id|>{item.role}<|end_header_id|>\n\n{item.message}{self.eos}"
                )
            if append_generation_prompt:
                out.append("<|start_header_id|>assistant<|end_header_id|>\n\n")
        elif self.type == ChatTemplateType.CHATML:
            for item in items:
                out.append(f"<|im_start|>{item.role}\n{item.message}<|im_end|>\n")
            if append_generation_prompt:
                out.append("<|im_start|>assistant\n")
        elif self.type == ChatTemplateType.ZEPHYR:
            for item in items:
                out.append(f"<|{item.role}|>\n{item.message}{self.eos}\n")
            if append_generation_prompt:
                out.append("<|assistant|>\n")
        else:
            raise ValueError(f"unsupported chat template type: {self.type}")
        return "".join(out)


def chat_stops(tokenizer: Tokenizer) -> list[str]:
    """Stop strings for chat mode: the chat EOS token text plus the optional
    extra stop string (reference: src/tokenizer.cpp:417-430)."""
    stops = [tokenizer.vocab[tokenizer.chat_eos_id].decode("utf-8", errors="replace")]
    if tokenizer.chat_stop:
        stops.append(tokenizer.chat_stop)
    return stops


# ---------------------------------------------------------------------------
# Streaming EOS / stop-sequence detection
# ---------------------------------------------------------------------------


class EosDetectorResult:
    NOT_EOS = 0
    EOS = 1
    MAYBE_EOS = 2


class EosDetector:
    """Streaming multi-token stop-string matcher.

    Buffers generated text; when a prefix of a stop string is seen at the tail
    the result is MAYBE_EOS (hold output), a full match is EOS, otherwise
    NOT_EOS and the buffered delta is safe to emit. ``padding_left`` allows a
    stop string to begin up to N characters into the buffer (tokens often glue
    whitespace before the stop marker); ``padding_right`` allows trailing
    characters after it (reference: src/tokenizer.cpp:502-575).
    """

    def __init__(
        self,
        eos_ids: int | Iterable[int],
        stops: Sequence[str],
        padding_left: int = 0,
        padding_right: int = 0,
    ):
        self.eos_ids = {eos_ids} if isinstance(eos_ids, int) else set(eos_ids)
        self.stops = [s.encode("utf-8") if isinstance(s, str) else s for s in stops]
        self.padding_left = padding_left
        self.padding_right = padding_right
        self.buffer = bytearray()
        self.eos_pos = -1

    def append(self, token_id: int, piece: bytes | str) -> int:
        if isinstance(piece, str):
            piece = piece.encode("utf-8")
        piece_len = len(piece)
        self.buffer += piece

        if token_id in self.eos_ids:
            self.eos_pos = len(self.buffer) - piece_len
            return EosDetectorResult.EOS
        self.eos_pos = -1

        for stop in self.stops:
            stop_size = len(stop)
            if len(self.buffer) > stop_size + self.padding_left + self.padding_right:
                continue
            for lo in range(self.padding_left + 1):
                n = len(self.buffer) - lo
                if n == 0 or n > stop_size + self.padding_right:
                    continue
                n = min(n, stop_size)
                if self.buffer[lo : lo + n] == stop[:n]:
                    if n == stop_size:
                        self.eos_pos = lo
                        return EosDetectorResult.EOS
                    return EosDetectorResult.MAYBE_EOS
        return EosDetectorResult.NOT_EOS

    def get_delta(self) -> bytes | None:
        """Text that is safe to emit after the last append()
        (reference: src/tokenizer.cpp:566-571)."""
        if self.eos_pos == -1:
            return bytes(self.buffer) if self.buffer else b""
        if self.eos_pos == 0:
            return None
        return bytes(self.buffer[: self.eos_pos])

    def flush_delta(self) -> bytes:
        """Drain buffered text on a non-EOS exit (length/context limit):
        text held back as a possible stop-string prefix (MAYBE_EOS) would
        otherwise be silently dropped. Clears the buffer."""
        delta = self.get_delta() or b""
        self.clear()
        return delta

    def clear(self) -> None:
        self.buffer = bytearray()
