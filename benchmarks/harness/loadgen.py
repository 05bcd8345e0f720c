"""The open-loop load generator.

``schedule`` is a pure function of the traffic parameters and the seed.  The
*set* of request sizes and of gaps between arrivals is drawn once from the
mix's own ``mix_seed``, so every ``--seed`` offers the same work at the same
mean rate; the seed puts sizes and gaps in another order and draws the
prompts' tokens.  ``python harness/loadgen.py <plan> <out>`` is the client:
a child that never imports JAX (standard library only), so that client and
engine do not share an interpreter lock.  It sends each request when it is
due, whatever happened to the ones before, over HTTP with ``"stream":
true``, one thread per request in flight, and writes the times of due, sent,
first token and every token event to a file.
"""

import json
import math
import os
import random
import sys
import threading
import time


def lognormal_lengths(rng, n, median, sigma, low, high):
    return [min(high, max(low, round(median * math.exp(
        sigma * rng.gauss(0.0, 1.0))))) for _ in range(n)]


def phase(traffic: dict, tag: int, seconds: float, seed: int):
    """``[(offset, prompt_len, max_new_tokens)]`` for one phase (ramp or
    window) of ``seconds``: ``round(rate * seconds)`` requests whose gaps
    are exponential (Poisson arrivals), scaled to fill the phase exactly."""
    n = round(traffic["rate_rps"] * seconds)
    if n <= 0:
        return []
    fixed = random.Random(traffic["mix_seed"] * 1000003 + tag)
    prompts = lognormal_lengths(fixed, n, **traffic["prompt_tokens"])
    outputs = lognormal_lengths(fixed, n, **traffic["output_tokens"])
    limit = traffic["max_total_tokens"]
    sizes = [(p, min(o, limit - p)) for p, o in zip(prompts, outputs)]
    gaps = [fixed.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    order = random.Random(seed * 1000003 + tag)
    order.shuffle(sizes)
    order.shuffle(gaps)
    offsets, t = [], 0.0
    for gap in gaps:
        offsets.append(t)   # the first request is due as the phase starts
        t += gap * scale
    return [(off, p, o) for off, (p, o) in zip(offsets, sizes)]


def schedule(traffic: dict, seed: int, seconds: float) -> list:
    """Requests due in the ramp (``due`` < 0, not counted) and in the
    window (0 <= ``due`` < ``seconds``), each with its own seeded prompt of
    distinct random tokens."""
    ramp = traffic["ramp_seconds"]
    tokens = random.Random(seed * 1000003 + 7)
    vocab = traffic["vocab_size"]
    requests = []
    for tag, start, length in ((1, -ramp, ramp), (2, 0.0, seconds)):
        for off, p, o in phase(traffic, tag, length, seed):
            requests.append({
                "due": start + off, "counted": tag == 2,
                "max_new_tokens": o,
                "tokens": [tokens.randrange(vocab) for _ in range(p)]})
    return requests


# -- the client --------------------------------------------------------------

def send(port: int, t0: float, request: dict, record: dict, timeout: float):
    """POST one streamed ``/generate`` and record when each token event
    arrived (seconds after ``t0``, the start of the window)."""
    import http.client
    body = json.dumps({"tokens": request["tokens"],
                       "max_new_tokens": request["max_new_tokens"],
                       "stream": True}).encode()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        record["sent"] = time.monotonic() - t0
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        record["status"] = resp.status
        if resp.status != 200:
            resp.read()
            return
        buf = b""
        while True:
            data = resp.read1(65536)
            now = time.monotonic() - t0
            if not data:
                break
            buf += data
            done = buf.rfind(b"\n\n")
            if done < 0:
                continue
            blocks, buf = buf[:done], buf[done + 2:]
            for block in blocks.split(b"\n\n"):
                event, _, payload = block.partition(b"\ndata: ")
                if event == b"event: token":
                    record["events"].append(
                        [now, len(json.loads(payload)["tokens"])])
                elif event == b"event: done":
                    record["finished"] = True
                elif event == b"event: error":
                    record["error"] = payload.decode()
        conn.close()
    except OSError as e:  # transport errors count as failures
        record["error"] = f"{type(e).__name__}: {e}"


def client(plan_path: str, out_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    t0, port = plan["t0_monotonic"], plan["port"]
    requests = sorted(plan["requests"], key=lambda r: r["due"])
    records, threads = [], []
    for i, request in enumerate(requests):
        wait = t0 + request["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        record = {"due": request["due"], "counted": request["counted"],
                  "prompt_tokens": len(request["tokens"]),
                  "max_new_tokens": request["max_new_tokens"],
                  "events": [], "finished": False}
        records.append(record)
        thread = threading.Thread(
            target=send, args=(port, t0, request, record,
                               plan["request_timeout_s"]), daemon=True)
        thread.start()
        threads.append(thread)
    deadline = t0 + plan["seconds"] + plan["drain_seconds"]
    for thread in threads:
        thread.join(max(0.0, deadline - time.monotonic()))
    # Threads past the deadline may still be appending: write a copy, and
    # put the file in place whole.
    snapshot = [dict(r, events=list(r["events"])) for r in records]
    with open(out_path + ".tmp", "w") as f:
        json.dump({"records": snapshot,
                   "unfinished_threads": sum(t.is_alive() for t in threads)},
                  f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    sys.exit(client(sys.argv[1], sys.argv[2]))
