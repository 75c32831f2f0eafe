"""Open-loop load generator: runs in a child process and never imports JAX.

It reads a job (server address, the window's opening time on the shared
monotonic clock, the schedule) from the JSON file named on the command line,
sends each request at its due time as a streaming ``POST /v1/completions``
(asyncio, one thread), and writes one record per request to the result file:
when it was due and sent, the time each token arrived, the tokens, and how
it ended. A request still open at the job's deadline is closed, which
cancels it in the server.

    python3 client.py JOB.json RESULT.json
"""
from __future__ import annotations

import asyncio
import json
import sys
import time


async def _request(job: dict, req: dict, rec: dict) -> None:
    t_due = job["t0"] + req["t_due"]
    delay = t_due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["t_sent"] = time.perf_counter()
    body = json.dumps({"prompt": req["prompt"], "max_tokens": req["max_tokens"],
                       "stream": True, "temperature": 0.0}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {job['host']}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(job["host"], job["port"])
        writer.write(head + body)
        await writer.drain()
        status = (await reader.readline()).split()
        if len(status) < 2 or status[1] != b"200":
            rec["status"] = "http_" + (status[1].decode() if len(status) > 1
                                       else "none")
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass                                  # response headers
        while True:
            line = await reader.readline()
            if not line:
                rec["status"] = "eof"
                return
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                rec["status"] = "ok"
                return
            now = time.perf_counter()
            choice = json.loads(payload)["choices"][0]
            for tok in choice["token_ids"]:
                rec["t_tokens"].append(now)
                rec["tokens"].append(tok)
            if choice.get("finish_reason"):
                rec["finish_reason"] = choice["finish_reason"]
    except OSError as e:
        rec["status"] = f"error: {e!r}"
    finally:
        if writer is not None:
            writer.close()


async def run(job: dict) -> list:
    records = [{"index": r["index"], "t_due": job["t0"] + r["t_due"],
                "t_sent": None, "t_tokens": [], "tokens": [],
                "status": "open", "finish_reason": None}
               for r in job["requests"]]
    tasks = [asyncio.ensure_future(_request(job, r, rec))
             for r, rec in zip(job["requests"], records)]
    timeout = job["t0"] + job["deadline"] - time.perf_counter()
    done, pending = await asyncio.wait(tasks, timeout=max(timeout, 0.0))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        t.result()
    return records


def main(argv) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    records = asyncio.run(run(job))
    with open(argv[2], "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
