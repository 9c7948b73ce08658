"""Closed-loop offline batches: ``clients`` clients, each sending its next
image as soon as its previous one is answered, for the whole window.

The inputs are ``images`` network-size uint8 images drawn from the seed
(pre-cropped, as a pipeline that stores crops sends them).  Client ``c``
walks them from its own offset, in an order drawn from the seed.  A
request's due time is when it was sent.
"""

from __future__ import annotations

import time

import numpy as np

# seconds past the window's end to wait for the answers still due
GRACE_S = 60.0


def make_inputs(params: dict, config: dict, seed: int) -> dict:
    rng = np.random.default_rng([seed, 0x5eed])
    h, w = config["input_hw"]
    n = params["images"]
    payloads = list(rng.integers(0, 256, (n, h, w, 3), dtype=np.uint8))
    return {"payloads": payloads, "order": rng.permutation(n)}


def warm(server, inputs: dict, params: dict) -> None:
    """Every bucket once through the whole serving path."""
    for b in server.scheduler.buckets:
        for p in inputs["payloads"][:b]:
            server.submit(p)
        server.drain()


def drive(server, inputs: dict, params: dict, seconds: float) -> dict:
    payloads, order = inputs["payloads"], inputs["order"]
    n, clients = len(order), params["clients"]
    requests: list[dict] = []
    live: dict[int, tuple[int, dict]] = {}
    sent_by = [0] * clients

    def send(c: int) -> None:
        i = int(order[(c * n // clients + sent_by[c]) % n])
        sent_by[c] += 1
        rec = {"input": i}
        r = server.submit(payloads[i])
        rec["due"] = rec["sent"] = time.perf_counter()
        requests.append(rec)
        if r.done:
            rec.update(done=rec["sent"], outcome=r.outcome, result=r.result)
        else:
            live[r.id] = (c, rec)

    t0 = time.perf_counter()
    t1 = t0 + seconds
    for c in range(clients):
        send(c)
    while server.queue_depth:
        done = server.step()
        t = time.perf_counter()
        for r in done:
            c, rec = live.pop(r.id)
            rec.update(done=t, outcome=r.outcome, result=r.result)
            if t < t1:
                send(c)
        if t > t1 + GRACE_S:
            break
    return {"t0": t0, "t1": t1, "requests": requests}
