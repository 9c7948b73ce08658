"""Open-loop camera streams: ``streams`` independent streams, each sending
one raw frame every ``1 / fps`` seconds whether or not earlier frames are
done.

Stream ``s`` has the resolution ``resolutions[s % len(resolutions)]`` and
cycles through that resolution's ``frames_per_resolution`` frames, which
are uint8 noise drawn from the seed before the window; frames are drawn,
and warmed up, only for the resolutions that some stream sends.  The streams'
phases are spread evenly over one frame interval and dealt to the streams
in an order drawn from the seed, so every seed offers the same arrivals in
another order.

Each request is timed from when it was due, so a stalled loop shows in its
latency; ``sent - due`` is how late the generator ran.
"""

from __future__ import annotations

import time

import numpy as np

# seconds past the window's end to wait for the answers still due
GRACE_S = 60.0


def make_inputs(params: dict, config: dict, seed: int) -> dict:
    rng = np.random.default_rng([seed, 0x5eed])
    n, k = params["frames_per_resolution"], params["streams"]
    payloads, first = [], []
    for h, w in params["resolutions"][:k]:
        first.append(len(payloads))
        payloads += [rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
                     for _ in range(n)]
    phases = rng.permutation(k) / (k * params["fps"])
    return {"payloads": payloads, "first": first, "phases": phases}


def warm(server, inputs: dict, params: dict) -> None:
    """Every frame size the streams send through the preprocess hook,
    then every bucket once through the whole serving path with them."""
    payloads, first = inputs["payloads"], inputs["first"]
    for i in first:
        server.preprocess(payloads[i])
    mix = [payloads[first[j % len(first)] + j // len(first)]
           for j in range(max(server.scheduler.buckets))]
    for b in server.scheduler.buckets:
        for p in mix[:b]:
            server.submit(p)
        server.drain()


def schedule(inputs: dict, params: dict, seconds: float):
    """(due offset, input index) of every request in the window, by due
    time."""
    period = 1.0 / params["fps"]
    n, first = params["frames_per_resolution"], inputs["first"]
    out = []
    for s, phase in enumerate(inputs["phases"]):
        base = first[s % len(first)]
        for k in range(int(np.ceil((seconds - phase) / period))):
            t = float(phase) + k * period
            if t < seconds:
                out.append((t, base + (s + k) % n))
    out.sort()
    return out


def drive(server, inputs: dict, params: dict, seconds: float) -> dict:
    import jax

    plan = schedule(inputs, params, seconds)
    payloads = inputs["payloads"]
    requests = [{"input": i, "due": 0.0} for _, i in plan]
    live: dict[int, dict] = {}
    t0 = time.perf_counter()
    for rec, (off, _) in zip(requests, plan):
        rec["due"] = t0 + off
    nxt = 0
    while True:
        now = time.perf_counter()
        while nxt < len(requests) and requests[nxt]["due"] <= now:
            rec = requests[nxt]
            r = server.submit(payloads[rec["input"]])
            rec["sent"] = time.perf_counter()
            if r.done:
                rec.update(done=rec["sent"], outcome=r.outcome,
                           result=r.result)
            else:
                live[r.id] = rec
            nxt += 1
            now = rec["sent"]
        if server.queue_depth:
            done = server.step()
            t = time.perf_counter()
            for r in done:
                rec = live.pop(r.id)
                rec.update(done=t, outcome=r.outcome, result=r.result)
        elif nxt < len(requests):
            with jax.profiler.TraceAnnotation("chipbench.wait_arrival"):
                time.sleep(max(0.0, requests[nxt]["due"] - time.perf_counter()))
        else:
            break
        if time.perf_counter() > t0 + seconds + GRACE_S:
            break
    return {"t0": t0, "t1": t0 + seconds, "requests": requests}
