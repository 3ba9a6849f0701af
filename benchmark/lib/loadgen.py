"""The load generator: a process of its own, off jax, one event loop.

Started by the harness with a plan file; then takes one JSON command per
line on stdin and answers each with JSON lines on stdout (the last line
of an answer carries `"done": true`). Commands:

  {"cmd": "batch", "steps": [[{"delay_s", "prompt_tokens",
      "output_tokens", "logprobs"}...]...]}
      — each step's requests are launched together (after their delays)
        and awaited before the next step: warm-up probes and the
        requests `correct` is judged on. Answers every request's record
        (with text and logprobs where asked).
  {"cmd": "load", "rate_rps" | "clients", "seconds", "seed"}
      — one measured window of the plan's traffic mix; prints
        {"event": "window_open"/"window_close", "t": monotonic} as they
        happen and then the request log.
  {"cmd": "quit"}

Times are `time.monotonic()`: CLOCK_MONOTONIC is one clock for every
process of the machine, so the harness compares them with its own.

Open loop: arrivals follow the mix's fixed cycle (traffic.open_cycle);
a request's clock starts when it was DUE, not when it was sent, and
`launched - due` is logged as the generator's lateness. Closed loop:
`clients` callers, each sending its next request when the last one
ended, ramped in over `ramp_s` and measured after `warmup_s`.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402


class Client:
    def __init__(self, plan: dict):
        self.base = plan["base_url"]
        self.model = plan["model"]
        self.mix = plan["mix"]
        self.template_tokens = plan["template_tokens"]
        with open(plan["words_file"]) as f:
            self.words = json.load(f)
        self.session = None

    async def __aenter__(self):
        import aiohttp

        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=None, sock_connect=30),
            connector=aiohttp.TCPConnector(limit=0),
        )
        return self

    async def __aexit__(self, *exc):
        await self.session.close()

    def body(self, content: str, max_tokens: int, logprobs: bool) -> bytes:
        body = {
            "model": self.model,
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens,
            "temperature": 0.0,
            "stream": True,
            "stream_options": {"include_usage": True},
            # exact output length: random weights may sample <|eot_id|>
            "nvext": {"ignore_eos": True},
        }
        if logprobs:
            body["logprobs"] = True
        return json.dumps(body).encode()

    async def request(self, rec: dict, body: bytes, window=None,
                      keep_text: bool = False) -> dict:
        """One streamed chat completion. Fills `rec` in place: launched,
        t_first, t_last, tokens, tokens_in_window, status."""
        rec["launched"] = time.monotonic()
        text, lps = [], []
        n = in_win = 0
        try:
            async with self.session.post(
                f"{self.base}/v1/chat/completions", data=body,
                headers={"content-type": "application/json",
                         "x-request-id": rec["id"]},
            ) as r:
                if r.status != 200:
                    rec["status"] = f"http_{r.status}"
                    rec["error"] = (await r.text())[:300]
                    return rec
                async for raw in r.content:
                    if not raw.startswith(b"data:"):
                        continue
                    data = raw[5:].strip()
                    if data == b"[DONE]":
                        break
                    now = time.monotonic()
                    obj = json.loads(data)
                    if obj.get("usage"):
                        rec["usage"] = obj["usage"]
                    for ch in obj.get("choices") or ():
                        piece = (ch.get("delta") or {}).get("content")
                        if piece:
                            k = len(piece.split())
                            if not n:
                                rec["t_first"] = now
                            n += k
                            rec["t_last"] = now
                            if window and window[0] <= now < window[1]:
                                in_win += k
                            if keep_text:
                                text.append(piece)
                        if keep_text and ch.get("logprobs"):
                            lps += [e["logprob"]
                                    for e in ch["logprobs"]["content"]]
            rec["status"] = "ok" if n else "no_tokens"
        except asyncio.CancelledError:
            rec["status"] = "cut"  # the run ended while it streamed
            raise
        except Exception as e:  # noqa: BLE001 — a failed request is data
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            rec["tokens"] = n
            rec["tokens_in_window"] = in_win
            if keep_text:
                rec["text"] = "".join(text)
                rec["logprobs"] = lps
        return rec

    # ------------------------------------------------------------- batch

    async def batch(self, cmd: dict) -> list[dict]:
        out = []
        for s, step in enumerate(cmd["steps"]):
            tasks = []
            for j, spec in enumerate(step):
                shape = traffic.Shape(spec["prompt_tokens"],
                                      spec["output_tokens"])
                tag = f"{cmd.get('tag', 'batch')}:{s}:{j}"
                content = traffic.content_for(
                    self.words, cmd.get("seed", 0), tag, shape,
                    self.template_tokens)
                rec = {"id": f"{tag}", "prompt_tokens": spec["prompt_tokens"],
                       "max_tokens": spec["output_tokens"]}
                if spec.get("logprobs"):
                    rec["content"] = content
                body = self.body(content, spec["output_tokens"],
                                 bool(spec.get("logprobs")))
                tasks.append(asyncio.create_task(self._delayed(
                    spec.get("delay_s", 0.0), rec, body,
                    bool(spec.get("logprobs")))))
            out += await asyncio.gather(*tasks)
        return out

    async def _delayed(self, delay, rec, body, keep):
        if delay:
            await asyncio.sleep(delay)
        return await self.request(rec, body, keep_text=keep)

    # -------------------------------------------------------------- load

    async def load(self, cmd: dict, say) -> dict:
        if self.mix["loop"] == "open":
            return await self._open(cmd, say)
        if self.mix["loop"] == "closed":
            return await self._closed(cmd, say)
        raise ValueError(f"unknown loop kind {self.mix['loop']!r}")

    async def _open(self, cmd, say):
        seconds, seed = float(cmd["seconds"]), int(cmd["seed"])
        warm = float(self.mix.get("warmup_s", 10.0))
        cutoff = float(self.mix.get("cutoff_s", 5.0))
        times, shapes = traffic.open_cycle(self.mix, cmd["rate_rps"], seconds)
        n = len(times)
        r0 = traffic.rotation(seed, n)
        # (offset from window open, cycle index, lap): the window holds
        # the whole cycle from arrival r0 on; the warm-up is the stretch
        # of the cycle that precedes it
        plan = []
        for i in range(n):
            off = (times[i] - times[r0]) % seconds
            plan.append((off, i, "m"))
            if off - seconds >= -warm:
                plan.append((off - seconds, i, "w"))
        plan.sort()
        recs, bodies = [], []
        for off, i, lap in plan:
            tag = f"{lap}{i}"
            rec = {"id": f"b{seed}-{tag}", "due_off": off,
                   "prompt_tokens": shapes[i].prompt_tokens,
                   "max_tokens": shapes[i].output_tokens,
                   "in_window": lap == "m"}
            recs.append(rec)
            bodies.append(self.body(
                traffic.content_for(self.words, seed, tag, shapes[i],
                                    self.template_tokens),
                shapes[i].output_tokens, False))
        t_open = time.monotonic() + warm + 0.05
        window = (t_open, t_open + seconds)
        crier = asyncio.create_task(self._announce(say, window))
        tasks = []
        for rec, body in zip(recs, bodies):
            rec["due"] = t_open + rec["due_off"]
            await self._sleep_until(rec["due"])
            tasks.append(asyncio.create_task(
                self.request(rec, body, window)))
        await crier
        # a due request gets `cutoff` seconds past the close to show its
        # first token; then everything still streaming is cut
        await self._sleep_until(window[1] + cutoff)
        return await self._finish(tasks, recs, window, cutoff)

    async def _closed(self, cmd, say):
        seconds, seed = float(cmd["seconds"]), int(cmd["seed"])
        clients = int(cmd["clients"])
        warm = float(self.mix.get("warmup_s", 20.0))
        ramp = float(self.mix.get("ramp_s", 10.0))
        cutoff = float(self.mix.get("cutoff_s", 5.0))
        per = int(self.mix.get("requests_per_client", 16))
        lists = traffic.closed_lists(self.mix, clients, per)
        r0 = traffic.rotation(seed, clients)
        t0 = time.monotonic()
        t_open = t0 + warm
        window = (t_open, t_open + seconds)
        recs: list[dict] = []

        async def caller(c: int):
            await self._sleep_until(t0 + ramp * c / clients)
            shapes = lists[(c + r0) % clients]
            for k, shape in enumerate(shapes):
                tag = f"c{c}-{k}"
                rec = {"id": f"b{seed}-{tag}", "client": c,
                       "prompt_tokens": shape.prompt_tokens,
                       "max_tokens": shape.output_tokens}
                body = self.body(
                    traffic.content_for(self.words, seed, tag, shape,
                                        self.template_tokens),
                    shape.output_tokens, False)
                rec["due"] = time.monotonic()
                rec["in_window"] = window[0] <= rec["due"] < window[1]
                if rec["due"] >= window[1]:
                    return
                recs.append(rec)
                await self.request(rec, body, window)

        tasks = [asyncio.create_task(caller(c)) for c in range(clients)]
        await self._announce(say, window)
        await self._sleep_until(window[1] + cutoff)
        return await self._finish(tasks, recs, window, cutoff)

    @staticmethod
    async def _sleep_until(t: float):
        d = t - time.monotonic()
        if d > 0:
            await asyncio.sleep(d)

    async def _announce(self, say, window) -> None:
        await self._sleep_until(window[0])
        say({"event": "window_open", "t": window[0]})
        await self._sleep_until(window[1])
        say({"event": "window_close", "t": window[1]})

    @staticmethod
    async def _finish(tasks, recs, window, cutoff) -> dict:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return {"window": list(window), "cutoff_s": cutoff,
                "t_end": time.monotonic(), "requests": recs}


async def main(plan_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)

    def say(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    async with Client(plan) as client:
        say({"event": "ready", "done": True})
        while True:
            line = await reader.readline()
            if not line:
                return 0
            cmd = json.loads(line)
            if cmd["cmd"] == "quit":
                return 0
            try:
                if cmd["cmd"] == "batch":
                    res = {"requests": await client.batch(cmd)}
                elif cmd["cmd"] == "load":
                    res = await client.load(cmd, say)
                else:
                    raise ValueError(f"unknown command {cmd['cmd']!r}")
            except Exception as e:  # noqa: BLE001 — report, stay alive
                say({"error": f"{type(e).__name__}: {e}", "done": True})
                continue
            with open(cmd["out"], "w") as f:
                json.dump(res, f)
            say({"out": cmd["out"], "done": True})


if __name__ == "__main__":
    sys.exit(asyncio.run(main(sys.argv[1])))
