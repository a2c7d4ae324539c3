"""Load from one process with few threads: SSE ``POST /generate`` against
the replica, open loop (a schedule, timed from when each request was due)
or closed loop (clients that wait for their reply)."""

from __future__ import annotations

import http.client
import json
import threading
import time
from dataclasses import dataclass, field, replace

from .traffic import Request


@dataclass
class Result:
    index: int
    due: float  # monotonic
    sent: float = 0.0
    token_times: list[float] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)
    done: bool = False
    error: str | None = None
    prompt_tokens: int = 0
    max_new_tokens: int = 0


def generate_once(port: int, req: Request, result: Result, timeout: float) -> None:
    """One streamed request; fills ``result`` as events arrive."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        body = json.dumps({
            "prompt": list(req.prompt), "max_new_tokens": req.max_new_tokens,
            "temperature": 0.0, "stream": True,
        })
        result.sent = time.monotonic()
        conn.request("POST", "/generate", body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            result.error = f"HTTP {resp.status} {resp.read(200)!r}"
            return
        while True:
            raw = resp.readline()
            if not raw:
                result.error = result.error or "stream cut before done"
                return
            if not raw.startswith(b"data:"):
                continue
            now = time.monotonic()
            ev = json.loads(raw[5:])
            if "token" in ev:
                result.token_times.append(now)
                result.tokens.append(ev["token"])
            elif ev.get("done"):
                result.done = True
                result.tokens = list(ev["tokens"])
                return
            elif "error" in ev:
                result.error = str(ev["error"])
                return
    except (OSError, http.client.HTTPException, ValueError) as e:
        result.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def _new_result(req: Request, due: float) -> Result:
    return Result(req.index, due, prompt_tokens=len(req.prompt), max_new_tokens=req.max_new_tokens)


def _close(results: list[Result], threads: list[threading.Thread], end: float) -> list[Result]:
    """Wait for every answer until ``end``; one still out then never came
    (its record is frozen as failed: what the replica says to it after the
    harness has begun to stop the replica is not an answer)."""
    for th in threads:
        th.join(max(end - time.monotonic(), 0.0))
    return [
        replace(res, token_times=list(res.token_times), tokens=list(res.tokens), done=False,
                error=res.error or "no answer within the wait after the window")
        if th.is_alive() and res is not None else res
        for res, th in zip(results, threads)
    ]


def run_open(port: int, requests: list[Request], t0: float, seconds: float, drain_s: float) -> list[Result]:
    """Send each request when it is due (a thread per request in flight,
    started by one pacing loop); after the window wait up to ``drain_s``
    for the answers still out."""
    results, threads = [], []
    timeout = seconds + drain_s
    for req in requests:
        due = t0 + req.due_s
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        res = _new_result(req, due)
        results.append(res)
        th = threading.Thread(target=generate_once, args=(port, req, res, timeout), daemon=True)
        th.start()
        threads.append(th)
    return _close(results, threads, t0 + seconds + drain_s)


def run_closed(port: int, source, clients: int, t0: float, seconds: float, drain_s: float, sent: list[Request]) -> list[Result]:
    """``clients`` threads, each sending its next request (drawn from
    ``source``, recorded in ``sent``) as soon as the last one is answered;
    no new request starts after the window's end."""
    results: list[Result] = []
    lock = threading.Lock()
    it = iter(source)
    t_end = t0 + seconds

    last: dict[int, Result] = {}

    def client(me: int) -> None:
        while time.monotonic() < t_end:
            with lock:
                req = next(it, None)
                if req is None:
                    return
                res = last[me] = _new_result(req, time.monotonic())
                results.append(res)
                sent.append(req)
            generate_once(port, req, res, seconds + drain_s)

    threads = [threading.Thread(target=client, args=(i,), daemon=True) for i in range(clients)]
    for th in threads:
        th.start()
    out_still = _close([last.get(i) for i in range(clients)], threads, t_end + drain_s)
    frozen = {id(last[i]): res for i, res in enumerate(out_still) if i in last and res is not last[i]}
    with lock:
        return [frozen.get(id(res), res) for res in results]


def get(port: int, path: str, timeout: float = 30.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def post(port: int, path: str, body: dict, timeout: float = 60.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()
