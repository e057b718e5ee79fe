"""The load generator: a child process that imports no JAX, so that the
threads reading its SSE streams never share the engine's interpreter
lock. One asyncio loop, one thread.

Protocol (all on this process's stdin/stdout):
  stdin  line 1: the schedule (benchmark/harness/traffic.py::build_schedule)
  stdout "ready"
  stdin  line 2: "go <t_open>", t_open on time.monotonic()'s clock, which
         Linux shares between processes
  stdout one JSON line: {"records": [...]}, times relative to t_open.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import aiohttp

REQUEST_TIMEOUT_S = 180.0


def _body(endpoint: str, model: str, req: dict) -> dict:
    if endpoint == "completions":
        return {"model": model, "prompt": req["prompt_ids"],
                "max_tokens": req["max_tokens"], "temperature": 0.0,
                "stream": True}
    if endpoint == "chain_generate":
        from benchmark.harness.traffic import words

        return {"messages": [{"role": "user",
                              "content": words(req["prompt_ids"])}],
                "use_knowledge_base": True, "temperature": 0.0,
                "top_p": 1.0, "max_tokens": req["max_tokens"]}
    raise ValueError(f"unknown endpoint {endpoint!r}")


def _token_text(endpoint: str, payload: str):
    """(text of this frame, is it the end). Raises on a malformed frame."""
    if payload.strip() == "[DONE]":
        return "", True
    choice = json.loads(payload)["choices"][0]
    if endpoint == "completions":
        return choice.get("text") or "", False
    return (choice["message"].get("content") or "",
            choice.get("finish_reason") == "[DONE]")


async def _one(session, url, endpoint, model, req, t_open, due_s):
    """Send one request now; read its stream to the end."""
    rec = {"phase": req["phase"], "due_s": due_s, "asked": req["max_tokens"],
           "prompt_tokens": len(req["prompt_ids"]), "token_s": [],
           "ok": False, "error": None}
    rec["sent_s"] = time.monotonic() - t_open
    try:
        async with session.post(url, json=_body(endpoint, model, req)) as resp:
            if resp.status != 200:
                rec["error"] = f"http {resp.status}"
                return rec
            async for raw in resp.content:
                now = time.monotonic() - t_open
                line = raw.decode(errors="replace").strip()
                if not line.startswith("data: "):
                    continue
                text, end = _token_text(endpoint, line[6:])
                if text:
                    if text.startswith("Error from chain server") or \
                            text.startswith("No response generated"):
                        rec["error"] = text[:120]
                        return rec
                    rec["token_s"].append(now)
                if end:
                    break
        rec["ok"] = len(rec["token_s"]) == rec["asked"]
        if not rec["ok"]:
            rec["error"] = (f"asked {rec['asked']} tokens, "
                            f"got {len(rec['token_s'])}")
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    return rec


async def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def _open_loop(session, url, args, sched, t_open):
    async def fire(req):
        await _sleep_until(t_open + req["due_s"])
        return await _one(session, url, args.endpoint, args.model, req,
                          t_open, req["due_s"])

    return list(await asyncio.gather(*(fire(r) for r in sched["requests"])))


async def _closed_loop(session, url, args, sched, t_open):
    reqs, records, turn = sched["requests"], [], [0]
    t_close = t_open + sched["seconds"]

    async def client():
        await _sleep_until(t_open - sched["ramp_s"])
        while time.monotonic() < t_close:
            req = reqs[turn[0] % len(reqs)]
            turn[0] += 1
            now = time.monotonic() - t_open
            records.append(await _one(session, url, args.endpoint,
                                      args.model, req, t_open, now))

    await asyncio.gather(*(client() for _ in range(sched["clients"])))
    return records


async def _main(args) -> int:
    sched = json.loads(sys.stdin.readline())
    url = args.base_url.rstrip("/") + (
        "/v1/completions" if args.endpoint == "completions" else "/generate")
    timeout = aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)
    async with aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0), timeout=timeout) as s:
        print("ready", flush=True)
        loop = asyncio.get_running_loop()
        go = (await loop.run_in_executor(None, sys.stdin.readline)).split()
        if len(go) != 2 or go[0] != "go":
            return 2
        t_open = float(go[1])
        run = _closed_loop if sched["kind"] == "closed" else _open_loop
        records = await run(s, url, args, sched, t_open)
    print(json.dumps({"records": records}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base-url", required=True)
    ap.add_argument("--endpoint", required=True,
                    choices=("completions", "chain_generate"))
    ap.add_argument("--model", default="bench")
    return asyncio.run(_main(ap.parse_args()))


if __name__ == "__main__":
    sys.exit(main())
