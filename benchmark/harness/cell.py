"""One run of one cell: files from the seed, the server child, warm-up, the
measured window, drain, the reference, and the result object.

The parent (this module) never imports JAX: a chip belongs to one process at a
time, and that process is the server child (the reference child computes on
the host). ``require_platform`` is an argument so that
the tests can rehearse the whole run on the CPU; ``run.py`` passes ``"tpu"``
and has no way to pass anything else.
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time

from benchmark import families
from benchmark.harness import client, prom, readers, stats, traffic

# The reference check's numbers (probe count and lengths, the rule's tolerances) are data:
# ``benchmark/check.json`` holds each with its reason, ``load_check`` reads them.
TRACE_SECONDS = 2.0  # 5 s of a 16-layer server's ops crashed the profiler at stop_trace (PR 22)
# --trace 2: the lead-in of the traced phase, at most (the mix's own if shorter): long enough
# for the rows to refill and, in a closed loop, for the callers to fall out of step again. A mix
# whose callers need longer for that (long prompts) says so itself: ``trace_lead_in_s``
TRACE_LEAD_IN_S = 6.0


class BenchFailure(RuntimeError):
    """The run cannot produce a result: no line is printed, exit code 1."""


def log(msg: str) -> None:
    print(msg, flush=True)
    if msg.startswith("[check]"):
        # each number compared beside its limit: the last lines of standard error too, which is
        # what a record keeps of a run that was not correct
        print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_check(bench_dir: str = families.BENCH_DIR, config: dict | None = None,
               launch: dict | None = None) -> dict:
    """The reference check's numbers: the defaults of ``<bench_dir>/check.json``,
    each replaced by the value a configuration's ``check`` block gives it, and
    that by the value the cell's own block gives it (``launch``: the cell's
    file), for that cell alone. A block carries its reason under ``why``; a
    name the defaults do not have is an error. ``min_compared``, the positions
    a verdict needs, follows."""
    check = {k: v["value"] for k, v in load_json(os.path.join(bench_dir, "check.json")).items()}
    for kind, holder in (("configuration", config), ("cell", launch)):
        override = dict((holder or {}).get("check") or {})
        if override:
            why = override.pop("why", "")
            unknown = sorted(set(override) - set(check))
            if unknown or not (isinstance(why, str) and why.strip()):
                raise BenchFailure(f"{kind} {holder.get('name')!r}: its check block needs a "
                                   f"\"why\" and may set {sorted(check)} only, not {unknown}")
            check.update(override)
    if not 0 <= check["long_probes"] <= check["probes"] or (
            check["long_probes"] and check["long_probe_prompt"] <= check["probe_prompt"]):
        raise BenchFailure(f"check: {check['long_probes']} long probes of {check['long_probe_prompt']} "
                           f"tokens among {check['probes']} of {check['probe_prompt']}")
    check["min_compared"] = int(check["probes"] * check["probe_tokens"] * check["min_compared_share"])
    return check


class Cell:
    """The data of one cell, found by name from ``BENCHMARK.json``."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in self.bench["workloads"] if w["name"] == workload), None)
        if entry is None:
            raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
        self.dir = os.path.join(root, self.bench["paths"][0])
        self.name = workload
        self.chips = int(entry["chips"])
        self.launch = load_json(os.path.join(self.dir, "workloads", f"{workload}.json"))
        cfg_entry = next(c for c in self.bench["configs"] if c["name"] == entry["config"])
        self.config_path = os.path.join(root, cfg_entry["file"])
        self.config = load_json(self.config_path)
        try:
            self.counts = families.counts(self.config, self.dir)  # and every key is known to it
        except families.FamilyError as e:
            raise BenchFailure(str(e)) from None
        self.check = load_check(self.dir, self.config, self.launch)
        self.mix = load_json(os.path.join(self.dir, "traffic", f"{entry['traffic']}.json"))
        for key in ("config", "traffic", "chips"):
            if self.launch[key] != entry[key]:
                raise BenchFailure(f"{workload}: its file and BENCHMARK.json differ on {key!r}")

    def metrics_for(self, group: str) -> list[dict]:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def flag(self, name: str, default: int) -> int:
        flags = self.launch["flags"]
        return int(flags[flags.index(name) + 1]) if name in flags else default


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(root: str, cache: str) -> dict:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    # the compile cache: one fixed directory inside the checkout, whatever the
    # machine's own says, so that two checkouts share nothing and the path (part
    # of the cache key) never moves; no size cap, or the big programs evict each
    # other and every run compiles
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "jax")
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return env


class Server:
    """The server child and the control channel beside it."""

    def __init__(self, cell: Cell, model: str, tokenizer: str, cache: str, require_platform: str):
        self.port, self.control_port = _free_port(), _free_port()
        self.beside: list = []  # other children of the run, killed with it
        self.log_path = os.path.join(cache, "server.log")
        cmd = [sys.executable, "-m", "benchmark.harness.server_child", require_platform,
               str(cell.chips), str(self.control_port),
               "--model", model, "--tokenizer", tokenizer, "--port", str(self.port),
               "--trace-out", os.path.join(cache, "dllama-trace.json"), *cell.launch["flags"]]
        if cell.chips > 1 and "--tp" not in cell.launch["flags"]:
            raise BenchFailure("a cell on several chips names its --tp in its flags")
        log(f"[setup] server: {' '.join(cmd[2:])}")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(cmd, cwd=cell.root, env=_child_env(cell.root, cache),
                                     stdout=self._log, stderr=subprocess.STDOUT)

    def tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait_ready(self, limit_s: float) -> None:
        t0 = time.monotonic()
        while True:
            if self.proc.poll() is not None:
                raise BenchFailure(f"server exited with code {self.proc.returncode} before it "
                                   f"was ready:\n{self.tail()}")
            if time.monotonic() - t0 > limit_s:
                raise BenchFailure(f"server not ready after {limit_s:.0f} s:\n{self.tail()}")
            try:
                status, _ = client.http_json(self.port, "GET", "/readyz", timeout=5.0)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.5)

    def control(self, path: str, body: dict | None = None) -> dict:
        status, raw = client.http_json(self.control_port, "POST", path, body or {}, timeout=120.0)
        if status != 200:
            raise BenchFailure(f"control {path}: HTTP {status}")
        return json.loads(raw)

    def scrape(self) -> list:
        status, raw = client.http_json(self.port, "GET", "/metrics", timeout=30.0)
        if status != 200:
            raise BenchFailure(f"/metrics: HTTP {status}")
        return prom.parse(raw.decode())

    def stop(self) -> int | None:
        """SIGTERM, wait for the drain; the exit code, or None if it had to be killed."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                self._log.close()
                return None
        self._log.close()
        return self.proc.returncode


def _probe(server: Server, req) -> stats.Record:
    rec = stats.Record(index=req.index, due=time.monotonic(), asked=req.max_tokens)
    client.send(server.port, rec, req.body, timeout=900.0)
    if not rec.ok:
        raise BenchFailure(f"probe {req.index} failed: status {rec.status} error {rec.error} "
                           f"finish {rec.finish}")
    return rec


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: int,
             require_platform: str, t_process: float) -> dict:
    """Run the cell; return the result object (the last line of stdout).
    ``trace``: 0 measures; 1 measures with the profiler in the middle of the
    window and gives the per-layer metrics; 2 measures as 0 does and then
    traces a short second phase of the same traffic in the same process,
    and gives both kinds of metric."""
    trace = int(trace)
    cell = Cell(root, workload)
    try:
        from benchmark.harness import modelfile  # imports the package's writers
        import distributed_llama_tpu  # noqa: F401
    except ImportError as e:
        raise BenchFailure(f"the system under test is not in this directory: {e}") from None
    cache = os.path.join(cell.dir, ".cache")
    model_dir = os.path.join(cache, "model")
    trace_dir = os.path.join(cache, "trace")
    for d in (model_dir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)  # one seed's files at a time: they are gigabytes
    os.makedirs(trace_dir)
    server = None
    try:
        t = time.monotonic()
        model, tokenizer = modelfile.write_artifacts(
            cell.config, seed, model_dir, cell.config["max_position_embeddings"], cell.dir)
        size_gb = os.path.getsize(model) / 1e9
        log(f"[setup] {size_gb:.2f} GB model file from seed {seed} in {time.monotonic() - t:.1f} s")
        t = time.monotonic()
        server = Server(cell, model, tokenizer, cache, require_platform)
        server.wait_ready(1000.0)
        device = server.control("/device")
        log(f"[setup] server ready in {time.monotonic() - t:.1f} s; device {json.dumps(device)}")
        return _measure(cell, server, device, model, cache, trace_dir, seed, seconds, trace,
                        require_platform, t_process)
    finally:
        if server is not None:
            for child in server.beside:
                child.kill()
            if server.proc.poll() is None:
                server.proc.kill()
                server.proc.wait()
        shutil.rmtree(model_dir, ignore_errors=True)


def _measure(cell: Cell, server: Server, device: dict, model: str, cache: str, trace_dir: str,
             seed: int, seconds: float, trace: int, require_platform: str,
             t_process: float) -> dict:
    # probes: alone, not streamed, prefix cache off; they are also the token-count check. The
    # reference scores each group's answers on the host's cores while the warm-up, which is one
    # Python thread tracing programs, goes on; it is over before the lead-in, so the window is
    # not disturbed. The long probes go first: their pass is the reference's longest (minutes at
    # 7B widths), and it starts while the short ones are still being answered
    check = cell.check
    probes = traffic.probe_requests(seed, check["probes"], check["probe_prompt"], check["probe_tokens"],
                                    check["long_probes"], check["long_probe_prompt"])
    n_short = len(probes) - check["long_probes"]
    t = time.monotonic()
    answers: list = [None] * len(probes)
    references = []
    for tag, group in (("_long", range(n_short, len(probes))), ("", range(n_short))):
        for i in group:
            answers[i] = _probe(server, probes[i])
            if answers[i].prompt_tokens != probes[i].prompt_tokens:
                raise BenchFailure(f"the generator counts {probes[i].prompt_tokens} prompt tokens, the "
                                   f"server {answers[i].prompt_tokens}: one character is not one token")
        if group:
            references.append(_Reference(cell, model, cache, [probes[i] for i in group],
                                         [answers[i] for i in group], tag))
            server.beside.append(references[-1])  # run_cell stops it if the run fails first
    building = sum(e["seconds"] for e in server.control("/compiles")["events"])
    log(f"[setup] {len(probes)} probes answered in {time.monotonic() - t:.1f} s, {building:.1f} s of it "
        f"building or loading programs; prompt and completion token counts agree with usage")

    mix = cell.mix
    rows = cell.flag("--parallel", 2)
    t = time.monotonic()
    loop_rows = rows if mix["loop"] == "open" else min(rows, int(mix["callers"]))
    pool_tokens = cell.flag("--kv-pages", 0) * cell.flag("--kv-page-size", 64)
    warm = client.waves(server.port, traffic.warmup_waves(mix, seed, loop_rows, pool_tokens),
                        timeout=900.0)
    bad = [r for r in warm if not r.ok]
    if bad:
        raise BenchFailure(f"{len(bad)} warm-up requests failed: {bad[0].status} {bad[0].error}")
    built = server.control("/compiles")
    slowest = sorted(built["events"], key=lambda e: e["seconds"], reverse=True)[:6]
    log(f"[setup] warm-up: {len(warm)} requests in {time.monotonic() - t:.1f} s; "
        f"{built['count']} programs built so far, {built['cache_hits']} from the compile cache, "
        f"{sum(e['seconds'] for e in built['events']):.1f} s in the compiler; slowest "
        f"{[(e['fun'], round(e['seconds'], 1)) for e in slowest]}")

    log(f"[setup] waited {sum(r.wait() for r in references):.1f} s after the warm-up for the reference")
    lead_in = float(mix["lead_in_s"])
    drain = float(mix["drain_limit_s"])
    t0 = time.monotonic() + 0.2  # the load's clock; the window opens lead_in later
    w0, w1 = t0 + lead_in, t0 + lead_in + seconds
    marks: dict = {}

    def at_edges() -> None:
        """Scrapes at the window's edges, and the profiler in its middle."""
        time.sleep(max(0.0, w0 - time.monotonic()))
        marks["before"] = server.scrape()
        if trace == 1:
            span = min(TRACE_SECONDS, seconds / 2)
            time.sleep(max(0.0, w0 + (seconds - span) / 2 - time.monotonic()))
            marks["trace_start"] = time.monotonic()
            server.control("/profile", {"action": "start", "dir": trace_dir})
            time.sleep(span)
            server.control("/profile", {"action": "stop"})
            marks["trace_stop"] = time.monotonic()
        time.sleep(max(0.0, w1 - time.monotonic()))
        marks["after"] = server.scrape()
        marks["memory"] = server.control("/memory")

    edge = threading.Thread(target=at_edges, daemon=True)
    edge.start()
    life0 = server.scrape()
    if mix["loop"] == "open":
        schedule = traffic.open_loop_schedule(mix, seed, seconds)
        log(f"[load] open loop: {len(schedule)} requests at {mix['rate_rps']} req/s over "
            f"{lead_in:.0f} s lead-in + {seconds:.0f} s window")
        records = client.open_loop(server.port, schedule, t0, w1 + drain, timeout=drain + seconds + lead_in)
        sent_prompt = {r.index: s.prompt_tokens for r, s in zip(records, schedule)}
    else:
        log(f"[load] closed loop: {mix['callers']} callers over {lead_in:.0f} s lead-in + "
            f"{seconds:.0f} s window")
        time.sleep(max(0.0, t0 - time.monotonic()))
        requests = traffic.closed_loop_requests(mix, seed)
        records = client.closed_loop(server.port, requests, int(mix["callers"]), w1, w1 + drain,
                                     timeout=drain + seconds + lead_in)
        sent_prompt = {r.index: r.prompt_tokens for r in records}
    edge.join(drain + 60.0)
    if edge.is_alive() or "after" not in marks:
        raise BenchFailure("the window's second scrape never came")
    setup_s = w0 - t_process

    in_window = [r for r in records if w0 <= r.due < w1]
    all_deltas = [t for r in records for t in r.deltas]
    wanted = [m["name"] for m in cell.metrics_for("end_to_end")]
    metrics, details = stats.end_to_end(in_window, w0, seconds, all_deltas, wanted)
    metrics["setup_s"] = setup_s
    log(f"[window] {json.dumps(details)}")
    # every request's own numbers, so that a later reading can try another statistic
    log("[window] per request, ms (ttft, tpot, stall): " + json.dumps(
        [[round(1e3 * v, 1) if v is not None else None for v in (r.ttft, r.tpot, r.stall)]
         for r in in_window if r.ok]))
    log(f"[window] end to end: {json.dumps({k: round(v, 3) for k, v in metrics.items()})}")

    # after the drain: the same greedy probe, alone again, must answer the same ...
    life1 = server.scrape()
    # ... the first request the server ever served (probe 0; a cell's first long probe where it
    # has some, since those go first): a later probe's FIRST answer depends on what the server
    # answered before it (PERF.md §7, PR 36), the first request's never has
    first = n_short if check["long_probes"] else 0
    same = _probe(server, probes[first]).text == answers[first].text
    which = f" (the long one, {probes[first].prompt_tokens} prompt tokens)" if first else ""
    log(f"[check] greedy probe{which} repeated after the drain: {'identical' if same else 'DIFFERENT'}")
    built = server.control("/compiles")
    # time.monotonic is one clock for every process of a machine (CLOCK_MONOTONIC),
    # so the child's instants compare with the parent's
    in_win = [e for e in built["events"] if w0 <= e["at"] < w1]
    log(f"[check] programs built inside the window: {len(in_win)} "
        f"{[e['fun'] for e in in_win][:8]}")
    paths = {json.dumps(lab, sort_keys=True): v for n, lab, v in life1 if n == "dllama_kernel_path_total"}
    log(f"[check] dllama_kernel_path_total: {json.dumps(paths)}")
    traced = None
    if trace == 2:
        # everything above is the --trace 0 run, to the instant; the server is still up
        traced = _traced_phase(server, mix, seed, seconds,
                               requests if mix["loop"] == "closed" else None, trace_dir)
        marks["memory"] = server.control("/memory")  # the peak of the whole run
    rc = server.stop()
    log(f"[check] server exit code after SIGTERM: {rc}")

    # token totals: dllama_tokens_generated_total counts whole decode chunks per
    # row, the pipelined chunk dispatched ahead of a stream's end included, so
    # it is an upper bound on what clients received, never an equal
    got = sum(len(r.deltas) for r in records)
    made = prom.delta(life0, life1, "dllama_tokens_generated_total") or 0.0
    totals_ok = 0 < got <= made
    log(f"[check] tokens: client received {got}, server counted {made:.0f} generated "
        f"({'consistent' if totals_ok else 'INCONSISTENT'}; {sum(not r.ok for r in records)} "
        f"of {len(records)} requests not completed)")

    ref_ok, ref_note = _reference_verdict(references, check)
    log(f"[check] reference: {ref_note}")
    correct = bool(device["platform"] == require_platform and device["count"] >= cell.chips
                   and rc == 0 and same and totals_ok and ref_ok)

    peak = max(marks["memory"]["peak_bytes"], default=0)
    result = {
        "correct": correct,
        "attempted": details["attempted"],
        "failed": details["failed"],
        "metrics": {},
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"], "memory_peak_bytes": peak},
    }
    if trace != 1:
        for m in cell.metrics_for("end_to_end"):
            if m["name"] not in metrics:
                raise BenchFailure(f"the window gave no {m['name']}")
            result["metrics"][m["name"]] = {"value": metrics[m["name"]], "unit": m["unit"]}
        if trace == 0:
            return result

    # --trace 2: counters over the MEASURED window's scrapes (the whole untraced window, not
    # one with the profiler in its middle); the device trace, and the rows and positions live
    # under it, from the traced phase
    facts = _trace_facts(cell, cache, trace_dir, device, records, sent_prompt, marks, w0, w1,
                         len(in_win), peak, traced)
    if traced is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)  # reduced: tens of megabytes
    # the window's latencies as the client saw them, for cells that record
    # them without judging them
    for quantity, ps in details["percentiles_ms"].items():
        for p, value in ps.items():
            facts[f"client.{quantity}_{p}_ms"] = value
    ctx = readers.Context(marks["before"], marks["after"], facts)
    for m in cell.metrics_for("per_layer"):
        value, unit = readers.read_metric(os.path.join(cell.dir, "layer_metrics"), m["name"], ctx)
        if value is None:
            log(f"[trace] {m['name']}: nothing to read, left out")
            continue
        result["metrics"][m["name"]] = {"value": value, "unit": unit}
    result["device"]["busy_s"] = facts["trace.busy_s"]
    result["device"]["window_s"] = facts["trace.window_s"]
    result["breakdown"] = {"device_ops": facts["trace.device_ops"],
                           "idle_gaps": facts["trace.idle_gaps"]}
    return result


def judge_probes(rows: list[dict], check: dict) -> tuple[bool, str]:
    """The verdict on the probes' positions. ``rows``: per answered position
    the served token, the reference's best, the served token's deficit and,
    from a sparse-expert model, the position's routing gap. ``check``: the
    rule's numbers (``load_check``; ``check.json`` says what each is for)."""
    answered = len(rows)
    dense = all(r.get("router_gap") is None for r in rows)
    rows = [r for r in rows if r.get("router_gap") is None or r["router_gap"] >= check["router_tie"]]
    ties = answered - len(rows)
    if len(rows) < check["min_compared"]:
        return False, (f"only {len(rows)} positions could be compared ({ties} more are routing "
                       f"near-ties), {check['min_compared']} are needed")
    equal = sum(1 for r in rows if r["server"] == r["reference"])
    misses = [r for r in rows if r["deficit"] > check["miss_tol"]]
    worst = max(r["deficit"] for r in rows)
    allowed = int(check["max_miss_share"] * len(rows))
    ok = len(misses) <= allowed and (not dense or worst <= check["dense_hard_tol"])
    note = (f"{len(rows)} positions compared" + (f" ({ties} routing near-ties left out)" if ties else "")
            + f": {equal} equal the reference's greedy token, "
            f"{len(misses)} over {check['miss_tol']:.0e} of max|logit| below its best ({allowed} allowed); "
            f"worst {worst:.2e}"
            + (f" (a dense model: at most {check['dense_hard_tol']:.0e})" if dense else ""))
    return ok, note


class _Reference:
    """The plain reference over a group of probes the server answered (one
    prompt length a group), in a child on the host's CPU (the chip is the
    server's), teacher-forced with the server's own tokens."""

    def __init__(self, cell: Cell, model: str, cache: str, probes: list, answers: list, tag: str = ""):
        self.proc, self.skipped = None, 0
        self.prompt_tokens = probes[0].prompt_tokens
        items = []
        for p, a in zip(probes, answers):
            ids = traffic.answer_ids(a.text or "", len(a.deltas))
            self.skipped += len(a.deltas) - len(ids)
            if ids:
                items.append({"prompt": traffic.encode_chat(p.body["messages"]), "answer": ids})
        self.read_back = len(items)
        if not items:
            return
        probes_path = os.path.join(cache, f"probes{tag}.json")
        self.out_path = os.path.join(cache, f"reference{tag}.json")
        with open(probes_path, "w") as f:
            json.dump(items, f)
        self._err = open(os.path.join(cache, f"reference{tag}.log"), "w+")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.reference.probe_child", cell.dir, cell.config_path,
             model, probes_path, self.out_path],
            cwd=cell.root, env=dict(_child_env(cell.root, cache), JAX_PLATFORMS="cpu"),
            stdout=self._err, stderr=subprocess.STDOUT)

    def wait(self) -> float:
        """Wait for the child; the seconds waited."""
        t = time.monotonic()
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.wait(600)
            except subprocess.TimeoutExpired:
                self.kill()
        return time.monotonic() - t

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def rows(self) -> tuple[list[dict], float]:
        """(a row for every answered position of the group's probes, the
        child's seconds); raises ``BenchFailure`` if the child gave none."""
        self.wait()
        if self.proc.returncode != 0:
            self._err.seek(0)
            raise BenchFailure(f"reference child exited with code {self.proc.returncode}: "
                               f"{self._err.read()[-2000:]}")
        out = load_json(self.out_path)
        return [dict(r, probe=i, position=j, prompt_tokens=self.prompt_tokens)
                for i, probe in enumerate(out["probes"]) for j, r in enumerate(probe)], out["seconds"]


def _reference_verdict(references: list, check: dict) -> tuple[bool, str]:
    """The reference rule's verdict over the groups of probes (the long
    ones, where the cell's check block asks for some, and the short ones),
    all their positions judged together by ``judge_probes``."""
    if not any(r.proc for r in references):
        return False, "no probe answer could be read back as tokens"
    rows, seconds = [], []
    try:
        for ref in references:
            if ref.proc is not None:
                got, took = ref.rows()
                rows += got
                seconds.append(took)
    except BenchFailure as e:
        return False, str(e)
    for r in rows:
        if r["deficit"] > check["miss_tol"]:
            tie = r["router_gap"] is not None and r["router_gap"] < check["router_tie"]
            log(f"[check] over the miss line{' (a routing near-tie, left out)' if tie else ''}: "
                f"{json.dumps(r)}")
    margins = sorted(r["margin"] for r in rows)
    ok, note = judge_probes(rows, check)
    if check["long_probes"]:
        # the long context is what such a cell times: every long probe has to be among the compared
        long_refs = [r for r in references if r.prompt_tokens > check["probe_prompt"]]
        read_back = sum(r.read_back for r in long_refs)
        after_long = [r["deficit"] for r in rows if r["prompt_tokens"] > check["probe_prompt"]]
        ok = ok and read_back == check["long_probes"]
        note += (f"; {len(after_long)} of the positions answered follow a prompt of "
                 f"{check['long_probe_prompt']} tokens, worst {max(after_long, default=0.0):.2e}, "
                 f"{sum(d > check['miss_tol'] for d in after_long)} over the miss line "
                 f"({read_back} of {check['long_probes']} long probes read back, all are needed)")
    return ok, (f"{note}; {sum(r.skipped for r in references)} answered positions not read back as "
                f"tokens; the reference's top-1/top-2 margin: median {margins[len(margins) // 2]:.2e}; "
                f"{' + '.join(f'{t:.1f}' for t in seconds)} s on the host beside the warm-up")


def _traced_phase(server: Server, mix: dict, seed: int, seconds: float, requests,
                  trace_dir: str) -> dict:
    """``--trace 2``, after the measured run: the same mix again for a short
    lead-in plus ``TRACE_SECONDS`` traced through the program's own capture
    control (``POST /debug/profile``), then a drain. Returns the phase's
    records, the prompt tokens it sent and the traced span's edges, in the
    form ``_trace_facts`` reads.

    Closed loop: the same callers go on drawing ``requests``, the generator
    the window drew from, so every prompt is new. Open loop: the part of the
    one seeded schedule, made for a run this much longer, that is due after
    the window's end, moved to this phase's clock: the same process at the
    same rate, the same system prompts (already cached, as in a server that
    has been up a while), new user text. It is not a replay of prompts the
    window sent; what it cannot have is the earlier turns of its own
    sessions in the cache."""
    def capture(body: dict) -> dict:
        status, raw = client.http_json(server.port, "POST", "/debug/profile", body, timeout=120.0)
        if status != 200:
            raise BenchFailure(f"capture {body['action']}: HTTP {status} {raw[:300]!r}")
        return json.loads(raw)

    # the first start of the profiler in a process costs seconds: paid here, into no number
    scratch = trace_dir + ".first"
    capture({"action": "start", "dir": scratch, "max_seconds": 30})
    first = capture({"action": "stop"})
    shutil.rmtree(scratch, ignore_errors=True)
    log(f"[trace] profiler started and stopped once, trace thrown away "
        f"({first['stop_trace_seconds']:.2f} s to stop)")

    lead_in = float(mix.get("trace_lead_in_s", min(float(mix["lead_in_s"]), TRACE_LEAD_IN_S)))
    drain = float(mix["drain_limit_s"])
    t0 = time.monotonic() + 0.2
    t_trace = t0 + lead_in
    t_stop = t_trace + TRACE_SECONDS + 1.0  # load goes on until the capture has stopped
    out: dict = {}

    def load() -> None:
        if requests is None:
            horizon = float(mix["lead_in_s"]) + seconds  # where the window's schedule ended
            longer = traffic.open_loop_schedule(mix, seed, seconds + (t_stop - t0))
            schedule = [r for r in longer if r.due_s >= horizon]
            for i, r in enumerate(schedule):
                r.due_s -= horizon
                r.index = i
            out["records"] = client.open_loop(server.port, schedule, t0, t_stop + drain,
                                              timeout=drain + (t_stop - t0))
            out["sent_prompt"] = {r.index: s.prompt_tokens
                                  for r, s in zip(out["records"], schedule)}
        else:
            time.sleep(max(0.0, t0 - time.monotonic()))
            out["records"] = client.closed_loop(server.port, requests, int(mix["callers"]),
                                                t_stop, t_stop + drain,
                                                timeout=drain + (t_stop - t0))
            out["sent_prompt"] = {r.index: r.prompt_tokens for r in out["records"]}

    loader = threading.Thread(target=load, daemon=True)
    loader.start()
    time.sleep(max(0.0, t_trace - time.monotonic()))
    out["trace_start"] = time.monotonic()
    capture({"action": "start", "dir": trace_dir, "max_seconds": TRACE_SECONDS + 5.0})
    time.sleep(TRACE_SECONDS)
    out["trace_stop"] = time.monotonic()  # the profiler collects until it is asked to stop ...
    stopped = capture({"action": "stop"})
    t_stopped = time.monotonic()  # ... and takes seconds more to hand the trace over
    loader.join(drain + (t_stop - t0) + 60.0)
    if loader.is_alive() or "records" not in out:
        raise BenchFailure("the traced phase's load never ended")
    done = sum(r.ok for r in out["records"])
    log(f"[trace] second phase: {lead_in:.0f} s lead-in + {TRACE_SECONDS:.0f} s traced by the "
        f"program's capture control ({stopped['seconds']:.2f} s captured, {stopped['spans']} host "
        f"spans, {stopped['stop_trace_seconds']:.2f} s to stop); {len(out['records'])} requests "
        f"sent, {done} completed")
    # what the capture costs while it runs (recorded, judged nowhere): the phase's own traffic
    # before the capture against the traffic that met it, its seconds of stopping included
    a, b = out["trace_start"], min(t_stop, t_stopped)  # b: load was offered until then

    def rate(lo: float, hi: float) -> float:
        return sum(lo <= t < hi for r in out["records"] for t in r.deltas) / (hi - lo)

    def tpot(records: list) -> str:
        ms = [1e3 * (r.deltas[-1] - r.deltas[0]) / (len(r.deltas) - 1) for r in records
              if len(r.deltas) > 1]
        return f"{statistics.median(ms):.3f} ms over {len(ms)} requests" if ms else "no request"

    streamed = [r for r in out["records"] if r.deltas]
    log(f"[trace] before the capture: {rate(t0 + lead_in / 2, a):.1f} tokens/s received in the "
        f"lead-in's second half, tpot p50 {tpot([r for r in streamed if r.deltas[-1] < a])} that "
        f"ended before it; under it: {rate(a, b):.1f} tokens/s in its first {b - a:.1f} s, tpot p50 "
        f"{tpot([r for r in streamed if r.deltas[-1] >= a and r.deltas[0] < t_stopped])} that met "
        f"it or the {t_stopped - out['trace_stop']:.1f} s of its stopping")
    return out


def _reduce_trace(cell: Cell, trace_dir: str) -> dict:
    """The trace reduction, in a child that may import JAX (this process never does)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cell.root)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.harness.trace_reduce", trace_dir, str(cell.chips)],
        cwd=cell.root, env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _trace_facts(cell: Cell, cache: str, trace_dir: str, device: dict, records: list,
                 sent_prompt: dict, marks: dict, w0: float, w1: float,
                 compiles_in_window: int, peak: int, traced: dict | None = None) -> dict:
    """Named values for the per-layer readers: from the generator, the control
    thread, the trace reduction, the bytes model and the table of peaks.
    ``traced`` (``--trace 2``): the second phase, whose records and span the
    facts about the traced span come from; the window's facts stay the
    window's."""
    red = _reduce_trace(cell, trace_dir)
    with open(os.path.join(cache, "trace_inventory.json"), "w") as f:
        json.dump({"inventory": red["inventory"], "modules": red.get("modules")}, f)
    if "error" in red:
        raise BenchFailure(f"trace: {red['error']}; planes {list(red['inventory'])}")
    peaks = load_json(os.path.join(cell.dir, "peaks.json"))
    if device["kind"] not in peaks:
        raise BenchFailure(f"no published peaks for device kind {device['kind']!r}")
    peaks = peaks[device["kind"]]
    log(f"[trace] {red['window_s']:.2f} s traced, device busy {red['busy_s']:.2f} s; modules "
        f"{json.dumps({k: [v['count'], round(v['seconds'], 3)] for k, v in red['modules'].items()})}")
    longest = sorted(red["ops"].items(), key=lambda kv: kv[1]["seconds"], reverse=True)[:12]
    log(f"[trace] longest ops (launches, seconds): "
        f"{json.dumps({k: [v['count'], round(v['seconds'], 4)] for k, v in longest})}")
    facts = {
        "gen.prompt_tokens_in_window": float(sum(sent_prompt[r.index] for r in records
                                                 if w0 <= r.sent < w1)),
        "control.compiles_in_window": float(compiles_in_window),
        "control.peak_hbm_gb": peak / 1e9,
        "trace.idle_share": red["idle_share"],
        "trace.busy_s": red["busy_s"],
        "trace.window_s": red["window_s"],
        "trace.device_ops": red["device_ops"],
        "trace.idle_gaps": red["idle_gaps"],
        "trace.modules": red["modules"],
        "trace.ops": red["ops"],
        "peaks": peaks,
        "model.kernel_launch": functools.partial(cell.counts.kernel_launch, cell.config),
    }
    # the decode step's share of the memory roofline, over the traced span:
    # rows and live context as the client saw them in that span
    under = traced or {"records": records, "sent_prompt": sent_prompt, **marks}
    a, b = under["trace_start"], under["trace_stop"]
    rows = positions = 0.0
    for r in under["records"]:
        if len(r.deltas) > 1:
            share = max(0.0, min(b, r.deltas[-1]) - max(a, r.deltas[0])) / (b - a)
            seen = sum(1 for t in r.deltas if t < (a + b) / 2)
            rows += share
            positions += share * (under["sent_prompt"][r.index] + seen)
    facts["gen.live_rows"] = rows
    facts["gen.live_positions"] = positions
    facts["server.decode_chunk"] = float(device["decode_chunk"])
    facts["model.decode_step_bytes"] = cell.counts.decode_step_bytes(
        cell.config, max(1.0, rows), positions)
    log(f"[trace] decode step floor: {facts['model.decode_step_bytes'] / 1e9:.3f} GB at "
        f"{rows:.1f} live rows and {positions:.0f} live positions")
    return facts
