"""The load generator: a process of its own that imports no JAX.

    python3 benchmark/loadgen.py <plan.json> <results.json>

``plan.json``: ``{"url", "mode": "open"|"closed", "requests"|"clients",
"window": [open_s, close_s], "stop_s", "measure": "due"|"done"}`` with
times in seconds after the start instant. After loading the plan it
prints ``ready`` and reads one line from stdin: the start instant on
``time.perf_counter()``'s clock (CLOCK_MONOTONIC, shared by every process
of the host). One thread, one asyncio loop, one connection per request.

Open loop: every request is posted at its due instant whether or not
earlier ones have returned; the generator keeps posting after the window
closes until every request DUE inside the window has returned or
``stop_s`` passes, so no measured request sees a draining system.
Closed loop: each client posts its next request when the last returns,
until the window closes; the request then in flight runs to its end (or to
``stop_s``), so that every request that overlaps the window has both its
instants.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from urllib.parse import urlparse


async def post(host: str, port: int, path: str, body: bytes) -> tuple[int, bytes]:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST " + path.encode() + b" HTTP/1.1\r\nHost: "
                     + host.encode() + b"\r\nContent-Type: application/json"
                     b"\r\nConnection: close\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        raw = await reader.read()           # Connection: close -> to EOF
    finally:
        writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1]) if head else 0
    return status, payload


def body_of(req: dict) -> bytes:
    return json.dumps({"inputs": {"input_ids": [req["prompt"]]},
                       "max_new": req["max_new"]}).encode()


async def one(plan, t0, req, results, due_s=None):
    u = plan["_url"]
    rec = {"idx": req["idx"], "due_s": due_s, "max_new": req["max_new"],
           "prompt_len": len(req["prompt"])}
    rec["sent_s"] = time.perf_counter() - t0
    try:
        status, payload = await post(u.hostname, u.port, u.path,
                                     req["_body"])
        rec["done_s"] = time.perf_counter() - t0
        rec["status"] = status
        if status == 200:
            ans = json.loads(payload)
            rec["tokens"] = ans["generations"][0]
            rec["timings"] = ans["timings"][0]
        else:
            rec["error"] = payload[:200].decode("replace")
    except Exception as e:                  # a failed request is a result
        rec["done_s"] = time.perf_counter() - t0
        rec["status"] = 0
        rec["error"] = f"{type(e).__name__}: {e}"
    except asyncio.CancelledError:          # not back when the cap passed
        rec["status"] = -1
        results.append(rec)
        raise
    results.append(rec)


async def open_loop(plan, t0, results):
    lo, hi = plan["window"]
    pending = set()
    measured = set()
    for req in plan["requests"]:
        due = req["due_s"]
        if due >= plan["stop_s"]:
            break
        if due >= hi and measured and all(t.done() for t in measured):
            break                           # every measured request is back
        delay = t0 + due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        task = asyncio.ensure_future(one(plan, t0, req, results, due))
        pending.add(task)
        if lo <= due < hi:
            measured.add(task)
    left = t0 + plan["stop_s"] - time.perf_counter()
    if measured and left > 0:
        await asyncio.wait(measured, timeout=left)
    for t in pending:
        if not t.done():
            t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)


async def closed_loop(plan, t0, results):
    _, hi = plan["window"]

    async def client(queue):
        for req in queue:
            if time.perf_counter() - t0 >= hi:
                return
            await one(plan, t0, req, results)

    delay = t0 - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    tasks = [asyncio.ensure_future(client(q)) for q in plan["clients"]]
    left = t0 + plan["stop_s"] - time.perf_counter()
    _, late = await asyncio.wait(tasks, timeout=max(0.0, left))
    for t in late:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    plan["_url"] = urlparse(plan["url"])
    every = (plan["requests"] if plan["mode"] == "open"
             else [r for q in plan["clients"] for r in q])
    for req in every:
        req["_body"] = body_of(req)         # nothing is encoded in the loop
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    results: list = []
    loop = open_loop if plan["mode"] == "open" else closed_loop
    asyncio.run(loop(plan, t0, results))
    with open(out_path, "w") as f:
        json.dump({"results": results,
                   "ended_s": time.perf_counter() - t0}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
