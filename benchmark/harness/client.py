"""HTTP client and the two load loops. One thread per request in flight, all
blocked on sockets; instants are ``time.monotonic``.

Open loop: every request is sent at its due instant whether or not earlier
ones have finished, and timed from the DUE instant, so a stall's cost to the
requests behind it is counted. Closed loop: each caller sends its next request
the moment the last one ends; its due instant is that moment."""

from __future__ import annotations

import http.client
import json
import threading
import time

from benchmark.harness.stats import Record

HOST = "127.0.0.1"


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 600.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection(HOST, port, timeout=timeout)
    try:
        payload = json.dumps(body) if body is not None else None
        conn.request(method, path, payload, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def send(port: int, rec: Record, body: dict, timeout: float) -> Record:
    """One completion over a fresh connection (each arrival is its own
    client). Streams when the body says so; fills ``rec`` in place."""
    conn = http.client.HTTPConnection(HOST, port, timeout=timeout)
    try:
        rec.sent = time.monotonic()
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.status = resp.status
        if resp.status != 200:
            rec.error = f"http_{resp.status}"
            resp.read()
            return rec
        if not body.get("stream"):
            out = json.loads(resp.read())
            rec.deltas = [time.monotonic()] * out["usage"]["completion_tokens"]
            rec.prompt_tokens = out["usage"]["prompt_tokens"]
            rec.finish = out["choices"][0]["finish_reason"]
            rec.text = out["choices"][0]["message"]["content"]
            rec.done = True
            return rec
        for raw in resp:
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                rec.done = True
                break
            evt = json.loads(payload)
            if "error" in evt:
                rec.error = str(evt["error"].get("type", "server_error"))
                break
            choice = evt["choices"][0]
            text = (choice.get("delta") or {}).get("content", "")
            if text:
                rec.deltas.append(time.monotonic())
            elif choice.get("finish_reason"):
                rec.finish = choice["finish_reason"]
    except (OSError, ValueError, KeyError, http.client.HTTPException) as e:
        rec.error = f"client:{type(e).__name__}:{e}"
    finally:
        conn.close()
    return rec


def _record(req, due: float) -> Record:
    return Record(index=req.index, due=due, asked=req.max_tokens, prompt_tokens=req.prompt_tokens)


def open_loop(port: int, schedule: list, t_start: float, t_end: float, timeout: float) -> list[Record]:
    """Send ``schedule`` (sorted by ``due_s``) on its clock, which starts at
    ``t_start``; wait for every stream to end, at most until ``t_end``. A
    stream still open then is left as it is: unfinished, so failed."""
    records = [_record(r, t_start + r.due_s) for r in schedule]
    threads = []
    for req, rec in zip(schedule, records):
        wait = rec.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=send, args=(port, rec, req.body, timeout), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(max(0.0, t_end - time.monotonic()))
    return records


def closed_loop(port: int, requests, callers: int, t_stop: float, t_end: float,
                timeout: float) -> list[Record]:
    """``callers`` threads, each sending the next request of its own stream
    (``requests.caller(i)``: ``traffic.ClosedLoop``) until ``t_stop``; then
    wait for the last streams until ``t_end``."""
    lock = threading.Lock()
    records: list[Record] = []

    def caller(mine) -> None:
        while True:
            now = time.monotonic()
            if now >= t_stop:
                return
            with lock:
                req = next(mine)
                rec = _record(req, now)
                records.append(rec)
            send(port, rec, req.body, timeout)
            if not rec.ok:
                time.sleep(0.2)  # a refusing server must not be hammered in a spin

    threads = [threading.Thread(target=caller, args=(requests.caller(i),), daemon=True)
               for i in range(callers)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(max(0.0, t_end - time.monotonic()))
    with lock:
        return list(records)


def waves(port: int, waves_: list[list], timeout: float) -> list[Record]:
    """Warm-up: each wave's requests at their offsets, the next wave after
    all of them have ended."""
    out: list[Record] = []
    for wave in waves_:
        t0 = time.monotonic() + 0.01
        out.extend(open_loop(port, sorted(wave, key=lambda r: r.due_s), t0, t0 + timeout, timeout))
    return out
