"""The load generator: a process of its own that never imports JAX.

    python benchmark/lib/loadgen.py --schedule S.json --url http://host:port/app --log L.json

It replays a schedule (`traffic.generate`) against the Serve HTTP proxy with
`?stream=1`, from one thread (asyncio), and logs for every request its due
time, its send time, the arrival time of every token and the token ids. All
times are `time.monotonic()`, which on Linux is one clock for every process
of the machine, so the runner that started this process can place its own
marks on the same axis.

Order: the schedule's `prime` requests, four at a time (they put each
conversation's start into the prefix cache); then one line on standard
output, `{"event": "clock", "start": t, "open": t + lead_in, "close": ...}`;
then the run's requests from `start`. An open-loop request is sent when it is
due, whatever has come back. A closed-loop caller sends its next request when
its last one has completed; the log says how many requests each caller had
not yet begun at the close (the closed loop's margin). At `close` every request still in flight is
cancelled by closing its connection, the log is written and the process
exits.

Derived from `ray_tpu/loadgen/driver.py`, which times from dispatch and runs
a thread per request inside the engine's process (PERF.md, section 7).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from urllib.parse import urlparse

PRIME_CONCURRENCY = 4
CLOSED_LOOP_STAGGER_S = 0.04


async def stream_request(host: str, port: int, path: str, request: dict,
                         record: dict) -> None:
    """POST one request and record each token as it arrives. Raises on a
    transport error or an error line; the caller writes the status."""
    body = json.dumps(
        {
            "prompt_ids": request["prompt_ids"],
            "max_new_tokens": request["max_new_tokens"],
            "stream": True,
            "request_id": record["request_id"],
        }
    ).encode()
    head = (
        f"POST {path}?stream=1 HTTP/1.1\r\nHost: {host}:{port}\r\n"
        "Content-Type: application/json\r\nX-Serve-Timeout-S: 600\r\n"
        f"Content-Length: {len(body)}\r\n\r\n"
    ).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(head + body)
        await writer.drain()
        record["sent"] = time.monotonic()
        status = await reader.readline()
        if b" 200 " not in status:
            raise RuntimeError(f"HTTP status {status!r}")
        while (await reader.readline()).strip():
            pass  # response headers
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                return
            line = await reader.readexactly(size)
            await reader.readexactly(2)  # the chunk's closing CRLF
            now = time.monotonic()
            item = json.loads(line)
            if "error" in item:
                raise RuntimeError(item["error"])
            record["token_times"].append(now)
            record["token_ids"].append(item["result"]["token_id"])
    finally:
        writer.close()


async def one(target, request: dict, due: float, records: list, run: str) -> dict:
    record = {
        "id": request["id"], "request_id": f"{run}-{request['id']}",
        "phase": request["phase"], "due": due, "sent": None,
        "token_times": [], "token_ids": [], "status": "in_flight",
        "prompt_tokens": len(request["prompt_ids"]),
        "max_new_tokens": request["max_new_tokens"], "done": None,
    }
    records.append(record)
    try:
        await stream_request(*target, request, record)
        complete = len(record["token_ids"]) == request["max_new_tokens"]
        record["status"] = "ok" if complete else "error: stream ended early"
    except asyncio.CancelledError:
        record["status"] = "cancelled"
        raise
    except Exception as exc:  # noqa: BLE001 — the status carries it
        record["status"] = f"error: {exc!r}"
    finally:
        record["done"] = time.monotonic()
    return record


async def replay(schedule: dict, url: str, run: str) -> dict:
    parsed = urlparse(url)
    target = (parsed.hostname, parsed.port, parsed.path)
    records: list = []

    gate = asyncio.Semaphore(PRIME_CONCURRENCY)

    async def primed(request):
        async with gate:
            await one(target, request, time.monotonic(), records, run)

    await asyncio.gather(*(primed(r) for r in schedule["prime"]))

    start = time.monotonic() + 0.25
    open_at = start + schedule["lead_in_s"]
    close_at = open_at + schedule["seconds"]
    print(json.dumps({"event": "clock", "start": start, "open": open_at,
                      "close": close_at}), flush=True)

    async def sleep_until(when: float) -> None:
        delay = when - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)

    tasks = []
    ran_dry = []
    left: dict = {}  # closed loop: requests each caller has not yet begun
    if schedule["loop"] == "open":
        async def fire():
            for request in schedule["requests"]:
                due = start + request["due_s"]
                await sleep_until(due)
                tasks.append(asyncio.ensure_future(
                    one(target, request, due, records, run)))
        tasks.append(asyncio.ensure_future(fire()))
    else:
        queues: dict = {}
        for request in schedule["requests"]:
            queues.setdefault(request["client"], []).append(request)

        async def caller(client: int, queue: list):
            await sleep_until(start + client * CLOSED_LOOP_STAGGER_S)
            for request in queue:
                left[client] -= 1
                await one(target, request, time.monotonic(), records, run)
            ran_dry.append(client)

        for client, queue in sorted(queues.items()):
            left[client] = len(queue)
            tasks.append(asyncio.ensure_future(caller(client, queue)))

    await sleep_until(close_at)
    closed = time.monotonic()
    for task in tasks:
        task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return {
        "start": start, "open": open_at, "close": close_at, "closed": closed,
        "loop": schedule["loop"], "callers_that_ran_dry": sorted(ran_dry),
        "requests_left_by_caller": [left[c] for c in sorted(left)],
        "records": records,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--schedule", required=True)
    parser.add_argument("--url", required=True)
    parser.add_argument("--log", required=True)
    parser.add_argument("--run", default="run")
    args = parser.parse_args(argv)
    if "jax" in sys.modules:
        raise RuntimeError("the load generator must never import JAX")
    with open(args.schedule) as f:
        schedule = json.load(f)
    log = asyncio.run(replay(schedule, args.url, args.run))
    with open(args.log, "w") as f:
        json.dump(log, f)
    print(json.dumps({"event": "done", "records": len(log["records"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
