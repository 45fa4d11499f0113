"""The load generator's sender: one asyncio loop in the harness's own
process (no threads), one streamed request per call, every token stamped on
arrival. Latencies are client-side, through the gateway, on one clock.
"""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp


class Client:
    def __init__(self, url: str, token: str, endpoint: str):
        self.url = f"{url}/endpoint/{endpoint}"
        self.headers = {"Authorization": f"Bearer {token}",
                        "Content-Type": "application/json",
                        "Accept": "text/event-stream"}
        self.session: aiohttp.ClientSession | None = None
        self.records: list = []
        self.t0 = time.perf_counter()

    async def __aenter__(self):
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=600))
        return self

    async def __aexit__(self, *exc):
        await self.session.close()

    def open_window(self) -> None:
        """Records made from now on are stamped relative to this instant."""
        self.t0 = time.perf_counter()
        self.records = []

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    async def send(self, spec: dict, due_s) -> dict:
        """POST one streamed request; ``due_s`` is when it was due on the
        window's clock (None: set-up traffic, outside any window)."""
        rec = {"class": spec.get("class", ""), "judged": bool(spec.get("judged")),
               "prompt_len": len(spec["prompt"]),
               "want_tokens": spec["max_new_tokens"], "due_s": due_s,
               "sent_s": self.clock(), "token_s": [], "tokens": [],
               "ok": False, "error": ""}
        self.records.append(rec)
        body = json.dumps({"tokens": spec["prompt"], "stream": True,
                           "max_new_tokens": spec["max_new_tokens"]})
        try:
            async with self.session.post(self.url, data=body,
                                         headers=self.headers) as resp:
                if resp.status != 200:
                    rec["error"] = f"HTTP {resp.status}: " \
                        f"{(await resp.text())[:200]}"
                    return rec
                done = False
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    now = self.clock()
                    event = json.loads(raw[5:])
                    if "token" in event:
                        rec["token_s"].append(now)
                        rec["tokens"].append(event["token"])
                    elif event.get("done"):
                        done = True
                    elif "error" in event:
                        rec["error"] = str(event["error"])[:200]
                rec["end_s"] = self.clock()
                if not rec["error"] and not done:
                    rec["error"] = "stream ended without a done event"
                elif not rec["error"] and \
                        len(rec["tokens"]) != spec["max_new_tokens"]:
                    rec["error"] = f"{len(rec['tokens'])} tokens, not " \
                        f"{spec['max_new_tokens']}"
                rec["ok"] = not rec["error"]
        except asyncio.CancelledError:
            rec["error"] = "cut at the window's end"
            rec["cut"] = True
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                ValueError) as exc:
            rec["error"] = f"{type(exc).__name__}: {exc}"[:200]
        return rec
