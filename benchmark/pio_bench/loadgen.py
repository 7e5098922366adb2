#!/usr/bin/env python3
"""The load generator: a process of its own, standard library + NumPy.

    python3 loadgen.py <spec.json> <out.jsonl>

Reads a spec (host, port, path, the schedule, the client timeout, whether
to send ``X-Request-Id``), opens keep-alive connections, prints ``ready``,
waits for a line on stdin, then sends every request at its due instant on
one asyncio loop — open loop: a request is sent when it is due whether or
not earlier ones have been answered.  Per request it records the due, sent
and done instants (``time.monotonic``, seconds from the window's start),
the HTTP status (0 with ``error`` for transport failures and timeouts) and
the answer body, one JSON object per line; the first line is a header.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


class Pool:
    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.idle: list = []
        self.opened = 0

    async def open(self):
        self.opened += 1
        return await asyncio.open_connection(self.host, self.port)

    async def get(self):
        while self.idle:
            r, w = self.idle.pop()
            if not w.is_closing() and not r.at_eof():
                return r, w
        return await self.open()

    def put(self, conn) -> None:
        self.idle.append(conn)

    def close(self) -> None:
        for _, w in self.idle:
            w.close()


async def exchange(pool: Pool, head: bytes, body: bytes, stamp):
    """One POST on a pooled keep-alive connection; ``stamp()`` is called
    just before the bytes go out.  Returns (status, body bytes)."""
    r, w = await pool.get()
    try:
        stamp()
        w.write(head + body)
        await w.drain()
        raw = await r.readuntil(b"\r\n\r\n")
        lines = raw.split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        length, close = 0, False
        for ln in lines[1:]:
            k, _, v = ln.partition(b":")
            k = k.strip().lower()
            if k == b"content-length":
                length = int(v)
            elif k == b"connection" and v.strip().lower() == b"close":
                close = True
        data = await r.readexactly(length) if length else b""
    except BaseException:
        w.close()
        raise
    if close:
        w.close()
    else:
        pool.put((r, w))
    return status, data


async def run(spec: dict, out_path: str) -> None:
    pool = Pool(spec["host"], spec["port"])
    for conn in [await pool.open() for _ in range(spec["warm_connections"])]:
        pool.put(conn)
    due = spec["due_s"]
    n = len(due)
    timeout = spec["client_timeout_s"]
    path = spec["path"]
    base_head = (f"POST {path} HTTP/1.1\r\nHost: {spec['host']}\r\n"
                 "Content-Type: application/json\r\n")
    records: list = [None] * n
    print("ready", flush=True)
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, sys.stdin.readline)
    t0 = time.monotonic() + 0.05

    async def one(i: int) -> None:
        body = json.dumps(
            {"user": spec["user"][i], "num": spec["num"][i]}).encode()
        head = base_head + f"Content-Length: {len(body)}\r\n"
        if spec["request_ids"]:
            head += f"X-Request-Id: bench-{i}\r\n"
        sent = [None]

        def stamp():
            sent[0] = time.monotonic() - t0

        rec = {"i": i, "due": due[i]}
        try:
            status, data = await asyncio.wait_for(
                exchange(pool, (head + "\r\n").encode(), body, stamp), timeout)
            rec.update(status=status, body=data.decode("utf-8", "replace"))
        except asyncio.TimeoutError:
            rec.update(status=0, error="timeout")
        except (OSError, asyncio.IncompleteReadError, ValueError,
                IndexError) as e:
            rec.update(status=0, error=f"{type(e).__name__}: {e}"[:200])
        rec["done"] = time.monotonic() - t0
        rec["sent"] = sent[0]
        records[i] = rec

    tasks = []
    for i in range(n):
        # always yields, so that requests already sent make progress
        await asyncio.sleep(max(0.0, t0 + due[i] - time.monotonic()))
        tasks.append(asyncio.create_task(one(i)))
    await asyncio.gather(*tasks)
    pool.close()
    with open(out_path, "w") as f:
        f.write(json.dumps({"header": True, "requests": n,
                            "connections_opened": pool.opened,
                            "window_s": spec["window_s"]}) + "\n")
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    asyncio.run(run(spec, sys.argv[2]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
