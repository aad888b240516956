"""The load generator of the serve cells: a process of its own that never
imports jax (the chip belongs to the server's process).

Open loop: Poisson arrivals from the seed at the fixed rate the traffic file
names, every request on a new connection (independent users), one thread
(asyncio). Each request is timed from the moment it was DUE, so a stall
counts for every request it delays; how late the generator itself ran is
reported beside it.

Protocol with the parent, one word a line: prints ``ready`` when the
requests are made; reads ``go <port>``; sends the warm-up requests one after
another and prints ``warm``; reads ``start``; runs the schedule, waits for the
stragglers, writes the result file and prints ``done``.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from perfbench.harness import textgen  # noqa: E402


def schedule(seed: int, rate: float, seconds: float) -> list:
    """Due times (seconds from the start) of a Poisson process."""
    rng = np.random.default_rng([seed, 0xA221])
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / rate))
        if t >= seconds:
            return out
        out.append(t)


async def post(host: str, port: int, body: bytes, timeout: float) -> tuple:
    """(status, n_chunks); status -1 when the exchange failed."""
    try:
        reader, writer = await asyncio.open_connection(host, port)
        try:
            writer.write(
                b"POST /v1/qa HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\nConnection: close\r\n"
                b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
                + body)
            await writer.drain()
            data = await asyncio.wait_for(reader.read(), timeout)
        finally:
            writer.close()
        head, _, payload = data.partition(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        chunks = json.loads(payload).get("n_chunks", 0) if status == 200 else 0
        return status, int(chunks)
    except (OSError, ValueError, IndexError, asyncio.TimeoutError):
        return -1, 0


async def drive(host, port, requests, due, timeout) -> list:
    t0 = time.perf_counter()
    rows = [None] * len(due)

    async def one(i):
        sent = time.perf_counter() - t0
        status, chunks = await post(host, port, requests[i]["body"], timeout)
        rows[i] = {"due": due[i], "sent": sent,
                   "done": time.perf_counter() - t0,
                   "status": status, "chunks": chunks}

    tasks = []
    for i, t in enumerate(due):
        delay = t0 + t - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(one(i)))
    if tasks:
        await asyncio.wait(tasks)
    return rows


def main(spec_path: str) -> int:
    spec = json.loads(Path(spec_path).read_text())
    words = Path(spec["vocab_file"]).read_text().split()[len(textgen.SPECIALS):]
    due = schedule(spec["seed"], spec["rate"], spec["seconds"])
    requests = textgen.serve_requests(
        spec["seed"], words, spec["mix"], len(due) + spec["warmup_requests"])
    print("ready", flush=True)

    port = int(sys.stdin.readline().split()[1])
    warm = requests[len(due):]
    for r in warm:
        asyncio.run(post(spec["host"], port, r["body"], spec["timeout_s"]))
    print("warm", flush=True)

    sys.stdin.readline()                       # start
    rows = asyncio.run(drive(spec["host"], port, requests, due,
                             spec["timeout_s"]))
    Path(spec["out"]).write_text(json.dumps({
        "rows": rows, "aimed_chunks": [r["chunks"] for r in requests[:len(due)]],
    }))
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
