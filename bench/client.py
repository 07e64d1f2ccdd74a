"""The load generator: streamed ``/v1/completions`` over raw sockets to
the in-process gateway, timed at the client.

The SSE reader follows ``benchmarks/bench_serve.py``'s ``_sse_request``,
with two changes: a request is timed from the moment it was due (so a
stall counts against the requests queued behind it), and every token's id
and arrival time are kept (the correctness check reads the ids, the
latency metrics the times).

Two ways to send a schedule: ``open_loop`` sends each request at its due
time whatever is in flight; ``closed_loop`` keeps a fixed number of
clients, each sending its next request once its previous answer ended.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List

from bench.traffic.generate import Ask, Schedule


@dataclasses.dataclass
class Record:
    ask: Ask
    due: float                     # monotonic seconds
    sent: float = 0.0
    status: int = 0
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: str = ""

    @property
    def ok(self) -> bool:
        """Answered in full: every asked token, ending at its length."""
        return (self.status == 200 and self.finish == "length"
                and len(self.tokens) == self.ask.max_tokens)


def payload(model_id: str, ask: Ask) -> dict:
    body = {"model": model_id, "prompt": ask.prompt,
            "max_tokens": ask.max_tokens, "stream": True,
            "temperature": ask.temperature}
    if ask.temperature > 0:
        body.update(top_k=ask.top_k, seed=ask.seed)
    return body


async def stream(port: int, body: dict, rec: Record) -> Record:
    """Send one request and read its SSE stream into ``rec``."""
    data = json.dumps(body).encode("utf-8")
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        rec.sent = time.monotonic()
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(data)}\r\n\r\n".encode()
                     + data)
        await writer.drain()
        parts = (await reader.readline()).split()
        rec.status = int(parts[1]) if len(parts) > 1 else 0
        await reader.readuntil(b"\r\n\r\n")
        if rec.status != 200:
            rec.finish = f"http_{rec.status}"
            return rec
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            chunk = line[len(b"data: "):].strip()
            if chunk == b"[DONE]":
                break
            obj = json.loads(chunk)
            if "error" in obj:
                rec.finish = f"error: {obj['error']['message']}"
                break
            choice = obj["choices"][0]
            ids = choice.get("token_ids") or []
            if ids:
                now = time.monotonic()
                rec.stamps.extend([now] * len(ids))
                rec.tokens.extend(int(t) for t in ids)
            if choice.get("finish_reason"):
                rec.finish = choice["finish_reason"]
        return rec
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, RuntimeError):
            pass


async def run_asks(port: int, model_id: str, asks: List[Ask]
                   ) -> List[Record]:
    """Send ``asks`` together and wait for every answer (the warm-up)."""
    now = time.monotonic()
    recs = [Record(a, now) for a in asks]
    await asyncio.gather(*[stream(port, payload(model_id, r.ask), r)
                           for r in recs])
    return recs


async def _finish(tasks: list, deadline: float) -> None:
    """Wait for ``tasks`` until ``deadline``; cancel what is left (those
    records stay unfinished and count as failed)."""
    pending = [t for t in tasks if not t.done()]
    if pending:
        _, late = await asyncio.wait(
            pending, timeout=max(0.0, deadline - time.monotonic()))
        for t in late:
            t.cancel()
        await asyncio.gather(*late, return_exceptions=True)


async def open_loop(port: int, model_id: str, sched: Schedule, t0: float,
                    seconds: float, grace: float) -> List[Record]:
    """Every ask due before the window closes is sent at its due time,
    whatever is still in flight."""
    recs = [Record(a, t0 + a.due_s) for a in sched.asks
            if a.due_s < seconds]

    async def one(rec: Record):
        await asyncio.sleep(max(0.0, rec.due - time.monotonic()))
        await stream(port, payload(model_id, rec.ask), rec)

    tasks = [asyncio.ensure_future(one(r)) for r in recs]
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    await _finish(tasks, t0 + seconds + grace)
    return recs


async def closed_loop(port: int, model_id: str, sched: Schedule, t0: float,
                      seconds: float, grace: float) -> List[Record]:
    """``sched.clients`` clients take the asks in order, each sending its
    next once its previous answer has ended, until the window closes at
    ``t0 + seconds``; a request is due when it is sent.  Clients that find
    the list used up stop."""
    asks = iter(sched.asks)
    recs: List[Record] = []

    async def client():
        for ask in asks:
            if time.monotonic() >= t0 + seconds:
                return
            rec = Record(ask, time.monotonic())
            recs.append(rec)
            await stream(port, payload(model_id, ask), rec)

    tasks = [asyncio.ensure_future(client()) for _ in range(sched.clients)]
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    await _finish(tasks, t0 + seconds + grace)
    return recs
