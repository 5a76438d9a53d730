"""Open-loop HTTP load from a child process that never imports jax.

    python3 -m benchmark.loadgen <plan.json>

The plan (written by the serving driver) names the port, the sample's text
file, the traffic mix, the seed and the window.  The child builds its
requests, prints ``READY``, waits for a line on stdin, sends for
``seconds`` seconds on ``connections`` keep-alive connections, and writes
every request's due/sent/done times to ``out``.  A request still queued
``DRAIN_S`` after the window's end is not sent and counts as failed, so an
overloaded server cannot stretch a run.

One general generator reads the mix: ``sizes`` is a list of
``{"share", "lo", "hi", "spacing": "uniform"|"log"}`` bands of records per
request; ``arrivals`` is ``poisson`` or ``bursts`` (``on_ms``/``off_ms``,
same mean rate).  Every seed gets **the same multiset of sizes and of
gaps** (the quantiles of the mix), in another order, so the work of a
window does not depend on the seed.
"""

from __future__ import annotations

import http.client
import json
import math
import queue
import sys
import threading
import time
from typing import List

from .gen import fold_seed, json_records


def size_multiset(sizes: List[dict], n: int) -> List[int]:
    """n request sizes at the mix's quantiles (deterministic)."""
    out: List[int] = []
    total = sum(b["share"] for b in sizes)
    for i, band in enumerate(sizes):
        k = round(n * band["share"] / total) if i < len(sizes) - 1 else n - len(out)
        lo, hi = band["lo"], band["hi"]
        for j in range(max(k, 0)):
            u = (j + 0.5) / max(k, 1)
            if band.get("spacing") == "log":
                out.append(int(round(math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo))))))
            else:
                out.append(int(lo + math.floor(u * (hi - lo + 1))))
    return out


def arrival_times(traffic: dict, n: int, rng) -> List[float]:
    """n due times from t=0: exponential gaps at their quantiles, shuffled
    (Poisson arrivals with a fixed multiset of gaps); with ``bursts`` the
    same times are squeezed into the on-phases of an on/off cycle."""
    rate = float(traffic["rate_per_s"])
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g
        out.append(t)
    arr = traffic.get("arrivals", {"kind": "poisson"})
    if arr.get("kind") == "bursts":
        on, off = arr["on_ms"] / 1000.0, arr["off_ms"] / 1000.0
        out = [(x * on / (on + off)) // on * (on + off) + (x * on / (on + off)) % on for x in out]
    return out


def build_requests(traffic: dict, seconds: float, seed: int, n_pool: int) -> List[dict]:
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    rng = fold_seed(seed, 21)
    sizes = size_multiset(traffic["sizes"], n)
    rng.shuffle(sizes)
    due = arrival_times(traffic, n, rng)
    starts = rng.integers(0, n_pool, n)
    return [{"due": due[i], "records": sizes[i], "start": int(starts[i])} for i in range(n)
            if due[i] < seconds]


def body_of(texts: List[str], start: int, k: int) -> bytes:
    n = len(texts)
    return ('{"records":[' + ",".join(texts[(start + j) % n] for j in range(k)) + "]}").encode()


DRAIN_S = 5.0       # past the window's end + this, what is still queued is not sent: it failed


def worker(port: int, q: "queue.Queue", t0: float, texts: List[str], results: List[dict],
           give_up: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    while True:
        req = q.get()
        if req is None:
            break
        body = body_of(texts, req["start"], req["records"])
        wait = t0 + req["due"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = {"due": req["due"], "records": req["records"], "status": 0, "scores_ok": False}
        rec["sent"] = time.perf_counter() - t0
        if rec["sent"] > give_up:          # a backlog that outlives the window: refused here
            rec["done"] = rec["sent"]
            rec["error"] = "not sent: the window and its drain time were over"
            results.append(rec)
            continue
        try:
            conn.request("POST", "/score", body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            rec["done"] = time.perf_counter() - t0
            rec["status"] = resp.status
            if resp.status == 200:
                scores = json.loads(raw).get("scores") or []
                rec["scores_ok"] = len(scores) == req["records"] and all(
                    isinstance(s, (int, float)) and math.isfinite(s) for s in scores)
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["done"] = time.perf_counter() - t0
            rec["error"] = f"{type(e).__name__}: {e}"
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        results.append(rec)
    conn.close()


def main(plan_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    traffic, seconds = plan["traffic"], float(plan["seconds"])
    _, texts = json_records(plan["text"], int(traffic["record_pool"]), skip=int(plan.get("skip", 0)))
    requests = build_requests(traffic, seconds, int(plan["seed"]), len(texts))
    results: List[dict] = []
    q: "queue.Queue" = queue.Queue()
    print(f"READY {len(requests)} requests, {sum(r['records'] for r in requests)} records",
          flush=True)
    sys.stdin.readline()                       # the parent says go
    t0 = time.perf_counter() + 0.05
    threads = [threading.Thread(target=worker, args=(plan["port"], q, t0, texts, results, seconds + DRAIN_S),
                                daemon=True) for _ in range(int(traffic["connections"]))]
    for t in threads:
        t.start()
    for r in requests:                         # in due order: a free connection takes the next
        q.put(r)
    for _ in threads:
        q.put(None)
    for t in threads:
        t.join()
    results.sort(key=lambda r: r["due"])
    with open(plan["out"], "w") as f:
        json.dump({"requests": results, "seconds": seconds,
                   "wall": time.perf_counter() - t0}, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
