#!/usr/bin/env python3
"""A prefix hit of an EVA arch, held to the reference on the chip (PR 39).

The benchmark's probes are sent with ``cache: off``, so ``correct`` never
sees a row that was resumed from COPIED summaries and a window tail. This
script does, with the harness's own pieces and no edit to them: the cell's
server (its flags, its configuration at the published widths, weights from
``--seed``), one document of ``--document`` positions as a ``system``
message (4300: it ends in its third window, and the reference's pass over a
prompt of 4.4k positions fits the machine beside nothing else; one over 7k
did not fit 40 GiB), and five asks of it (questions of ``--question`` positions, so
that every prompt ends in the window its document ends in: a publish keeps
the keys and values of the window a PROMPT ends in), each greedy and not
streamed:

1. cold, cache on: a miss; it publishes the document's summaries and the
   keys and values of its last window;
2. another question, cache on: a HIT at a page inside the document's last
   window (summaries of every block, the window's pages up to the hit);
3. the same prompt with ``cache: off``: the cold answer to compare the
   tokens with;
4. after ``--others`` other documents (of ``--other-document`` positions,
   ending late in their window) have pushed the first one's pages out
   of the window pool (its summaries stay): a third question, cache on,
   SHORTENED to the window's start (summaries only);
5. that prompt cold.

``dllama_prefix_window_tail_total{outcome}`` has to move by one ``hit`` at 2
and one ``shortened`` at 4, and the answers of 2 and 4 are scored by the
family's float32 reference (``benchmark.reference.probe_child``, teacher-
forced with the served tokens) under the cell's own rule (``judge_probes``
with the numbers of ``check.json`` and the cell's check block).

    python3 tools/eva_cache_witness.py [--workload evabyte.doc_sessions] [--seed N]

from the root of a checkout whose ``BENCHMARK.json`` lists the cell. Last
line of stdout: one JSON object, ``"ok": true`` when both outcomes were met
and both answers are inside the rule; exit code 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import time

ROOT = os.getcwd()
sys.path.insert(0, ROOT)

from benchmark.harness import cell as cell_mod  # noqa: E402
from benchmark.harness import modelfile, prom, stats, traffic  # noqa: E402

OUTCOMES = ("hit", "shortened", "miss")
ANSWER = 32  # positions an ask decodes: a probe's


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", default="evabyte.doc_sessions")
    ap.add_argument("--seed", type=int, default=2**31 + 3901)
    ap.add_argument("--document", type=int, default=4300)
    ap.add_argument("--question", type=int, default=48)
    ap.add_argument("--others", type=int, default=7)
    ap.add_argument("--other-document", type=int, default=8000)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    cell = cell_mod.Cell(ROOT, args.workload)
    cache = os.path.join(cell.dir, ".cache")
    model_dir = os.path.join(cache, "model")
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
    rng, chars = random.Random(args.seed ^ 0xE7A), traffic.mix_alphabet(cell.mix)
    model, tokenizer = modelfile.write_artifacts(
        cell.config, args.seed, model_dir, cell.config["max_position_embeddings"], cell.dir)
    server = cell_mod.Server(cell, model, tokenizer, cache, args.platform)
    answered = []
    try:
        server.wait_ready(1000.0)
        cell_mod.log(f"[witness] server ready; device {json.dumps(server.control('/device'))}")

        def ask(document: str):
            messages = [{"role": "system", "content": document},
                        {"role": "user", "content": traffic._text(rng, args.question, chars)}]
            body = {**traffic._body(messages, ANSWER), "stream": False}
            return traffic.Request(0, 0.0, body, traffic.chat_tokens(messages), ANSWER)

        def send(req, label: str) -> tuple[stats.Record, dict]:
            """The answer, and how the outcome counters and the matched tokens moved."""
            before, t = server.scrape(), time.monotonic()
            rec = cell_mod._probe(server, req)
            after = server.scrape()
            moved = {o: int(prom.delta(before, after, "dllama_prefix_window_tail_total", {"outcome": o}) or 0)
                     for o in OUTCOMES}
            moved["matched_tokens"] = int(
                prom.delta(before, after, "dllama_prefix_cache_matched_tokens_sum") or 0)
            cell_mod.log(f"[witness] {label}: {req.prompt_tokens} prompt tokens, "
                         f"{time.monotonic() - t:.1f} s, {json.dumps(moved)}")
            return rec, moved

        def with_cache_off(req):
            return traffic.Request(0, 0.0, {**req.body, "cache": "off"}, req.prompt_tokens, ANSWER)

        document = traffic._text(rng, args.document, chars)
        send(ask(document), "cold, publishes")
        report, ok = {}, True
        for label, want in (("hit", "hit"), ("shortened", "shortened")):
            if label == "shortened":
                for i in range(args.others):  # documents that end late in their window: long tails
                    send(ask(traffic._text(rng, args.other_document, chars)), f"other document {i + 1}")
            req = ask(document)
            rec, moved = send(req, f"asked again ({label} expected)")
            cold, _ = send(with_cache_off(req), "the same prompt, cache off")
            answered.append((req, rec))
            met = moved[want] == 1 and sum(moved[o] for o in OUTCOMES) == 1 and moved["matched_tokens"] > 0
            ok = ok and met
            report[label] = {"outcome_met": met, "moved": moved, "prompt_tokens": req.prompt_tokens,
                             "answer_equals_cold": rec.text == cold.text}
        # the reference's passes one after the other, the server gone: a pass over a prompt of
        # 4.4k positions holds gigabytes of scores a layer, and the machine has 40 GiB
        cell_mod.log(f"[witness] server exit code after SIGTERM: {server.stop()}")
        check = dict(cell.check, min_compared=int(ANSWER * cell.check["min_compared_share"]))
        for (label, entry), (req, rec) in zip(report.items(), answered):
            ref = cell_mod._Reference(cell, model, cache, [req], [rec], f"_{label}")
            server.beside.append(ref)
            rows, seconds = ref.rows()
            inside, note = cell_mod.judge_probes(rows, check)
            entry.update(inside_the_rule=inside, worst_deficit=max(r["deficit"] for r in rows),
                         equal_to_reference=sum(r["server"] == r["reference"] for r in rows),
                         positions=len(rows), reference_s=round(seconds, 1))
            cell_mod.log(f"[witness] {label}: {note}")
            ok = ok and inside
        print(json.dumps({"ok": ok, "workload": cell.name, "seed": args.seed,
                          "document_tokens": args.document, **report}), flush=True)
        return 0 if ok else 1
    finally:
        for child in server.beside:
            child.kill()
        if server.proc.poll() is None:
            server.proc.kill()
            server.proc.wait()
        shutil.rmtree(model_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
