"""`dllama-tpu` CLI: inference / generate / chat / worker modes.

Command surface parity with the reference's dllama app
(reference: src/apps/dllama/dllama.cpp:223-254, arg parsing
src/app.cpp:28-113), adapted to the TPU runtime:

* ``--workers host:port...`` (TCP worker list) becomes ``--tp N`` (shard over
  N local chips) plus multi-host flags (``--coordinator``, ``--num-hosts``,
  ``--host-id``) that drive ``jax.distributed`` — the SPMD equivalent of the
  reference's root/worker split where every host runs the *same* program.
* ``--nthreads`` is accepted but ignored: the thread pool's job is done by
  XLA inside one chip (SURVEY.md §2, intra-node thread parallelism).
* ``--buffer-float-type`` is accepted but advisory: the wire-quantization it
  controls in the reference (Q80 activations over TCP, src/tasks.cpp:96-135)
  does not exist here — activations never leave the chip mesh except over ICI.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from distributed_llama_tpu.telemetry import Stopwatch
from distributed_llama_tpu.tokenizer import (
    ChatItem,
    ChatTemplate,
    ChatTemplateType,
    EosDetector,
    EosDetectorResult,
    Sampler,
    Tokenizer,
    chat_stops,
    is_safe_piece,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dllama-tpu")
    p.add_argument("mode", choices=["inference", "generate", "chat", "worker"])
    p.add_argument("--model", required=True)
    p.add_argument("--tokenizer", required=True)
    p.add_argument("--prompt", default=None)
    p.add_argument("--steps", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument(
        "--topk", type=int, default=0,
        help="top-k sampling filter (0 = off); composes with --topp as "
        "min(top-k, nucleus), fused into the device decode program",
    )
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--max-seq-len", type=int, default=None)
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel shards (chips)")
    p.add_argument(
        "--pod", type=str, default=None, metavar="DATAxMODEL",
        help="one-process pod serving on a single ('data','model') mesh "
        "(e.g. 2x2): tensor parallelism over 'model' inside every slice, "
        "data-parallel replicas as slices of the SAME mesh sharing ONE "
        "weights tree (no N-replica weight copies). The "
        "server runs one supervised replica per data slice — a mesh-slice "
        "failure IS a replica loss with the PR 9/10 failover/replay/"
        "restart contract, and a slice rebuild never reloads weights. "
        "Mutually exclusive with --tp/--sp/--ep; testable under "
        "JAX_PLATFORMS=cpu with --xla_force_host_platform_device_count",
    )
    p.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel shards: KV cache sharded over the sequence, "
        "ring-attention prefill (long-context mode; composes with --tp on a "
        "2-D tp x sp mesh)",
    )
    p.add_argument(
        "--ep", type=int, default=1,
        help="expert-parallel shards (MoE models): each shard owns "
        "n_experts/ep whole experts; prefill routes tokens with all_to_all "
        "dispatch/combine, decode runs local experts + psum (composes with "
        "--tp on a 2-D tp x ep mesh)",
    )
    p.add_argument(
        "--dtype",
        choices=["bf16", "f32", "q40"],
        default="bf16",
        help="on-device weight dtype (q40 = packed 4-bit via the fused Pallas kernel)",
    )
    p.add_argument("--chat-template", default=None,
                   choices=[None, "llama2", "llama3", "zephyr", "chatml"])
    p.add_argument(
        "--decode",
        choices=["device", "host"],
        default="device",
        help="device = chunked on-device decode+sampling (fast path: fused "
        "temperature/top-k/top-p + counter-PRNG coins inside the decode "
        "program); host = per-token host sampling (the reference's regime, "
        "one host<->device round trip per token; the counter-mode xorshift "
        "sampler replays the device stream token for token per seed)",
    )
    p.add_argument(
        "--decode-chunk", type=int, default=32,
        help="tokens per device dispatch for --decode device",
    )
    p.add_argument(
        "--spec-draft", type=int, default=0,
        help="self-speculative decoding: up to K prompt-lookup draft tokens "
        "(n-gram matches over the request's own prompt + output — no draft "
        "model) verified per decode step in ONE weight read; greedy output "
        "is bit-identical to plain decode, sampled output preserves the "
        "distribution (Leviathan rejection sampling). Wins on repetitive/"
        "structured output, degenerates gracefully when acceptance "
        "collapses. 0 (default) = off; single-chip --decode device only",
    )
    p.add_argument(
        "--spec-ngram", type=int, default=3,
        help="widest n-gram the prompt-lookup drafter matches (falls "
        "through to shorter n-grams; --spec-draft must be > 0)",
    )
    p.add_argument(
        "--cache-dtype",
        choices=["auto", "bf16", "f32", "i8"],
        default="auto",
        help="KV-cache dtype (auto = bf16, or f32 with --dtype f32). i8 "
        "stores int8 rows with per-(slot, head) scales: half the cache HBM "
        "of bf16 — the TPU-native replacement for the reference's "
        "disc-backed --kv-cache-storage (longer contexts in the same memory)",
    )
    p.add_argument(
        "--telemetry", action="store_true", default=False,
        help="enable the telemetry subsystem: metrics registry (served at "
        "GET /metrics by dllama-tpu-api) + span tracer (Chrome trace JSON "
        "written to --trace-out after a generate/inference run). "
        "DLLAMA_TELEMETRY=1 in the environment enables it too; off by "
        "default — disabled instruments are no-ops on the decode hot path",
    )
    p.add_argument(
        "--trace-out", default="dllama-trace.json", metavar="PATH",
        help="where a --telemetry generate/inference run writes its Chrome "
        "trace-event JSON (open in chrome://tracing or ui.perfetto.dev)",
    )
    p.add_argument(
        "--compile-cache-dir", default=None, metavar="DIR",
        help="XLA persistent compilation-cache directory: a fresh process "
        "reuses compiled programs instead of paying the cold compile. "
        "Where JAX_COMPILATION_CACHE_DIR is set, that directory is used "
        "and this flag is ignored. Default: DLLAMA_COMPILE_CACHE env, else "
        ".jax_compile_cache/ inside the checkout; DLLAMA_COMPILE_CACHE='' "
        "disables. Cache-served compiles count in "
        "dllama_compile_cache_hits_total under --telemetry",
    )
    # accepted-for-parity flags (see module docstring)
    p.add_argument("--nthreads", type=int, default=None, help=argparse.SUPPRESS)
    p.add_argument("--buffer-float-type", default=None, help=argparse.SUPPRESS)
    p.add_argument("--weights-float-type", default=None, help=argparse.SUPPRESS)
    p.add_argument("--kv-cache-storage", default=None, help=argparse.SUPPRESS)
    # multi-host (jax.distributed) participation
    p.add_argument("--coordinator", default=None, help="host:port of jax.distributed coordinator")
    p.add_argument("--num-hosts", type=int, default=1)
    p.add_argument("--host-id", type=int, default=0)
    return p


def _parse_dtypes(args):
    import jax.numpy as jnp

    from distributed_llama_tpu.engine.weights import QUANTIZED_DTYPE

    if getattr(args, "kv_cache_storage", None) not in (None, "ram"):
        # the reference spills the KV cache to disc-backed mmap buffers
        # (reference: src/utils.cpp:50-67); on TPU the cache lives in HBM
        # inside a jitted program and cannot be file-backed — rejected
        # here so BOTH engine paths (classic and --pod) refuse it
        raise SystemExit(
            f"--kv-cache-storage {args.kv_cache_storage} is not supported on "
            "TPU (the KV cache is device HBM); use --cache-dtype i8 for 2x "
            "cache-memory headroom and/or --max-seq-len to bound it"
        )
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32, "q40": QUANTIZED_DTYPE}[args.dtype]
    cache_dtype = {
        "auto": None, "bf16": jnp.bfloat16, "f32": jnp.float32, "i8": "i8",
    }[getattr(args, "cache_dtype", "auto")]
    return dtype, cache_dtype


def _make_sampler(args, vocab_size: int) -> Sampler:
    # wall-clock as entropy for a default sampling seed, never a duration
    seed = args.seed if args.seed is not None else int(time.time())  # dllama: noqa[CLK-001]
    # counter mode: the host sampler draws the SAME coins the fused device
    # sampler draws (stateless, keyed on (seed, position)), so a --decode
    # host run replays a --decode device stream token for token — the
    # xorshift-parity verification mode (ISSUE 13)
    return Sampler(
        vocab_size=vocab_size,
        temperature=args.temperature,
        topp=args.topp,
        topk=args.topk,
        seed=seed,
        counter=True,
    )


def make_pod_group(args):
    """Build the one-process pod substrate from the serving flags: ONE
    model load placed on the single ('data','model') mesh, plus the
    tokenizer/sampler pair ``make_engine`` would return. The returned
    group IS the serving layer's engine factory (slice engines share the
    pod's weights and compiled programs; a replica rebuild never reloads
    the file)."""
    from distributed_llama_tpu.parallel.pod import PodGroup, parse_pod

    if getattr(args, "tp", 1) > 1 or getattr(args, "sp", 1) > 1 or getattr(args, "ep", 1) > 1:
        raise SystemExit(
            "--pod owns the whole mesh layout; it does not compose with "
            "--tp/--sp/--ep (the pod's 'model' axis IS the tensor-parallel "
            "degree)"
        )
    data, model = parse_pod(args.pod)
    dtype, cache_dtype = _parse_dtypes(args)
    group = PodGroup.build(
        args.model, data, model,
        dtype=dtype,
        max_seq_len=args.max_seq_len,
        cache_dtype=cache_dtype,
    )
    tokenizer = Tokenizer.from_file(args.tokenizer, group.cfg.vocab_size)
    return group, tokenizer, _make_sampler(args, group.cfg.vocab_size)


def make_engine(args):
    from distributed_llama_tpu.engine import InferenceEngine

    if getattr(args, "pod", None):
        # one-off pod engine (generate/chat/inference modes): one slice of
        # a freshly built pod group — the long-lived group path is
        # serve()'s (the factory must outlive the engine for rebuilds)
        group, tokenizer, sampler = make_pod_group(args)
        return group.slice_engine(), tokenizer, sampler
    dtype, cache_dtype = _parse_dtypes(args)
    engine = InferenceEngine(
        args.model, dtype=dtype, max_seq_len=args.max_seq_len, tp=args.tp,
        sp=getattr(args, "sp", 1), ep=getattr(args, "ep", 1),
        cache_dtype=cache_dtype,
    )
    tokenizer = Tokenizer.from_file(args.tokenizer, engine.cfg.vocab_size)
    return engine, tokenizer, _make_sampler(args, engine.cfg.vocab_size)


def _print(s: str) -> None:
    sys.stdout.write(s)
    sys.stdout.flush()


def generate(args, benchmark: bool) -> None:
    """The generate/inference loop (reference: src/apps/dllama/dllama.cpp:17-94).

    TPU-first deviations: the prompt is prefilled in one batched forward
    instead of token-by-token (per-token stats lines cover the decode phase,
    prefill is its own line), and with ``--decode device`` (the default) the
    decode loop runs on device in chunks — sampling included — so no
    host<->device round trip is paid per token. ``--decode host`` restores
    the reference's regime (host xorshift sampler, stepwise).
    """
    if args.prompt is None:
        raise SystemExit("Prompt is required")
    engine, tokenizer, sampler = make_engine(args)
    add_bos = engine.cfg.arch.name != "GROK1"  # (reference: dllama.cpp:26)
    prompt_tokens = tokenizer.encode(args.prompt, add_bos=add_bos)

    n_prompt = len(prompt_tokens)
    if n_prompt < 1:
        raise SystemExit("Expected at least 1 prompt token")

    total_sw = Stopwatch()
    if args.decode == "device":
        # prefill→decode fusion: the first token is sampled on device and the
        # first decode chunk is dispatched before anything is fetched — the
        # device never idles through a host fetch (engine.prefill_device)
        first_dev = engine.prefill_device(
            prompt_tokens, args.temperature, args.topp, seed=sampler.seed,
            topk=args.topk,
        )
        logits = None
    else:
        logits = engine.prefill(prompt_tokens)
    # fused path: the prefill stats entry only gains its device-compute
    # drain time when the first token is fetched (engine._fetch_fused_first),
    # so the P line is deferred until then — printing it here would report
    # async dispatch overhead, not prefill latency
    p_entry = engine.stats[-1] if benchmark else None
    p_printed = False
    if benchmark and args.decode != "device":
        _print(f"🔷 P {p_entry.generation_ms:5.0f} ms ({n_prompt} prompt tokens) ")
        p_printed = True
    _print(tokenizer.decode(prompt_tokens))
    if benchmark:
        _print("\n")

    def print_p_line() -> None:
        nonlocal p_printed
        if benchmark and not p_printed:
            _print(f"🔷 P {p_entry.generation_ms:5.0f} ms ({n_prompt} prompt tokens)\n")
            p_printed = True

    def emit(prev: int, tok: int) -> None:
        print_p_line()
        stats = engine.stats[-1]
        if benchmark:
            _print(
                f"🔶 G {stats.generation_ms:4.0f} ms I {stats.inference_ms:4.0f} ms "
                f"T {stats.transfer_ms:4.0f} ms "
            )
        piece = tokenizer.decode_piece(prev, tok)
        if is_safe_piece(piece):
            _print(piece.decode("utf-8", errors="replace"))
        if benchmark:
            _print("\n")

    token = prompt_tokens[-1]
    generated = 0
    if args.decode == "device":

        def on_token(prev: int, t: int) -> bool:
            nonlocal generated, token
            if t == tokenizer.bos_id:
                return False  # BOS delimits sequences (dllama.cpp:68-71)
            emit(prev, t)
            generated += 1
            token = t
            return True

        engine.stream_decode(
            first_dev, on_token, args.temperature, args.topp,
            seed=sampler.seed, chunk=args.decode_chunk, limit=args.steps,
            first_prev=prompt_tokens[-1],
            spec_draft=getattr(args, "spec_draft", 0),
            spec_ngram=getattr(args, "spec_ngram", 3),
            prompt_tokens=prompt_tokens,
            topk=args.topk,
        )
        print_p_line()  # zero-token streams (immediate BOS) still report P
    else:
        # first generated token samples on host from the prefill logits;
        # the counter sampler keys each coin on the consumed position, so
        # this stepwise stream is token-identical to --decode device
        next_token = sampler.sample(logits, pos=engine.pos - 1)
        if next_token != tokenizer.bos_id:  # BOS delimits sequences (dllama.cpp:68-71)
            emit(token, next_token)
            generated += 1
            token = next_token
            while engine.pos < args.steps:
                logits = engine.decode_step(token)
                next_token = sampler.sample(logits, pos=engine.pos - 1)
                if next_token == tokenizer.bos_id:
                    break
                emit(token, next_token)
                generated += 1
                token = next_token

    avg = engine.avg_stats()
    total_ms = total_sw.elapsed_ms()
    n = max(1, engine.total_tokens())
    _print("\n")
    _print(f"Generated tokens:    {generated}\n")
    _print(f"Avg tokens / second: {1000.0 * n / max(total_ms, 1e-9):.2f}\n")
    _print(f"Avg generation time: {avg.generation_ms:.2f} ms\n")
    _print(f"Avg inference time:  {avg.inference_ms:.2f} ms\n")
    _print(f"Avg transfer time:   {avg.transfer_ms:.2f} ms\n")


def chat(args) -> None:
    """Multi-turn REPL (reference: src/apps/dllama/dllama.cpp:111-203)."""
    engine, tokenizer, sampler = make_engine(args)
    stops = chat_stops(tokenizer)
    template_type = args.chat_template or ChatTemplateType.UNKNOWN
    template = ChatTemplate(template_type, tokenizer.chat_template, stops[0])
    max_stop = max(len(s) for s in stops)

    items: list[ChatItem] = []
    sys_prompt = input("💻 System prompt (optional): ")
    if sys_prompt:
        items.append(ChatItem("system", sys_prompt))

    seq_len = engine.cfg.seq_len
    while engine.pos < seq_len:
        user = ""
        while not user:
            user = input("\n👱 User\n> ")
        items.append(ChatItem("user", user))
        prompt = template.generate(items, append_generation_prompt=True)
        items = []  # only deltas are fed each turn (reference keeps full list; we re-feed deltas against the live KV cache)
        tokens = tokenizer.encode(prompt, add_bos=engine.pos == 0)

        budget = seq_len - engine.pos
        tokens = tokens[:budget]
        turn_seed = sampler.seed + engine.pos  # vary the stream per turn
        sampler.set_seed(turn_seed)  # counter coins re-key per turn too
        if args.decode == "device":
            # prefill→decode fusion (see generate): first token sampled on
            # device, no host round trip between prompt and reply
            first_dev = engine.prefill_device(
                tokens, args.temperature, args.topp, seed=turn_seed,
                topk=args.topk,
            )
            logits = None
        else:
            logits = engine.prefill(tokens)
        _print("\n🤖 Assistant\n")

        detector = EosDetector(
            {tokenizer.chat_eos_id}, stops, padding_left=max_stop, padding_right=max_stop
        )

        def feed(prev: int, token: int) -> EosDetectorResult:
            piece = tokenizer.decode_piece(prev, token)
            res = detector.append(token, piece if is_safe_piece(piece) else b"")
            if res in (EosDetectorResult.NOT_EOS, EosDetectorResult.EOS):
                delta = detector.get_delta()
                if delta:
                    _print(delta.decode("utf-8", errors="replace"))
                detector.clear()
            return res

        if args.decode == "device":
            res = EosDetectorResult.NOT_EOS

            def on_token(prev: int, t: int) -> bool:
                nonlocal res, token
                res = feed(prev, t)
                token = t
                return res != EosDetectorResult.EOS

            engine.stream_decode(
                first_dev, on_token, args.temperature, args.topp,
                seed=turn_seed, chunk=args.decode_chunk,
                limit=seq_len, first_prev=tokens[-1],
                spec_draft=getattr(args, "spec_draft", 0),
                spec_ngram=getattr(args, "spec_ngram", 3),
                prompt_tokens=tokens,
                topk=args.topk,
            )
        else:
            prev = tokens[-1]
            token = sampler.sample(logits, pos=engine.pos - 1)
            res = feed(prev, token)
            if res != EosDetectorResult.EOS and engine.pos < seq_len:
                while engine.pos < seq_len:
                    logits = engine.decode_step(token)
                    prev = token
                    token = sampler.sample(logits, pos=engine.pos - 1)
                    res = feed(prev, token)
                    if res == EosDetectorResult.EOS:
                        break
        if res != EosDetectorResult.EOS:
            # context-limit exit: flush text held back as a possible
            # stop-string prefix so the reply tail is not lost
            tail = detector.flush_delta()
            if tail:
                _print(tail.decode("utf-8", errors="replace"))
    _print("\n(end of context)\n")


def worker(args) -> None:
    """Multi-host participant: joins the jax.distributed mesh and runs the
    same SPMD program as the root host.

    The reference's worker blocks on a TCP accept and receives streamed
    weight slices (reference: dllama.cpp:205-221, transformer.cpp:541-616);
    here every host loads its own shard of the `.m` file and the collective
    mesh is formed by jax.distributed.
    """
    if args.coordinator is None:
        raise SystemExit(
            "worker mode needs --coordinator host:port, --num-hosts and --host-id "
            "(every host runs the same program; start the root with the same flags "
            "and --host-id 0)"
        )
    import jax

    jax.distributed.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_hosts,
        process_id=args.host_id,
    )
    # after initialization, every host must execute the same SPMD program
    # with identical flags (the multi-host contract: same --prompt, --steps,
    # --tp, --seed on all hosts). A missing prompt is a contract violation —
    # a silently defaulted one would diverge from the root's program and
    # deadlock the collectives, so fail loudly instead.
    if args.prompt is None:
        raise SystemExit(
            "worker mode requires the SAME --prompt (and --steps/--tp/--seed) "
            "as every other host: all hosts execute one SPMD program"
        )
    generate(args, benchmark=False)


def main(argv=None) -> None:
    from distributed_llama_tpu.platform import enable_compilation_cache

    args = build_parser().parse_args(argv)
    from distributed_llama_tpu import telemetry

    # must happen BEFORE make_engine: instruments bind at construction,
    # and the compile cache must be configured before the first jit
    if args.telemetry:
        telemetry.enable()
    enable_compilation_cache(args.compile_cache_dir)
    if args.mode == "inference":
        generate(args, benchmark=True)
    elif args.mode == "generate":
        generate(args, benchmark=False)
    elif args.mode == "chat":
        chat(args)
    elif args.mode == "worker":
        worker(args)
    if telemetry.is_enabled() and args.mode in ("inference", "generate"):
        path = telemetry.export_chrome_trace(args.trace_out)
        _print(f"📊 telemetry: Chrome trace written to {path}\n")


if __name__ == "__main__":
    main()
