"""The generators that drive a cell's entry through the measured window.

``closed``: one caller runs step after step, keeping ``inflight`` calls
enqueued on the device, as a sweep or a training job does; each call runs
the traffic's ``steps_per_call`` steps.  The window ends when the last call
dispatched before ``seconds`` had passed is done.
"""
from __future__ import annotations

import collections
import time

from bench.lib.harness import Window


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def closed_loop(entry, traffic: dict, seed: int, seconds: float) -> Window:
    import jax
    depth = int(traffic.get("inflight", 2))
    pending = collections.deque()
    k = 0
    with _annotate("bench.window"):
        t0 = time.perf_counter()
        while True:
            with _annotate("bench.dispatch"):
                pending.append(entry.step(k))
            k += 1
            if len(pending) >= depth:
                with _annotate("bench.wait"):
                    jax.block_until_ready(pending.popleft())
            if time.perf_counter() - t0 >= seconds:
                break
        with _annotate("bench.wait"):
            jax.block_until_ready(list(pending))
        t1 = time.perf_counter()
    steps = k * int(traffic.get("steps_per_call", 1))
    return Window(seconds=t1 - t0, completed=steps, attempted=steps, failed=0)


LOOPS = {"closed": closed_loop}


def run(kind: str, entry, traffic: dict, seed: int, seconds: float) -> Window:
    if kind not in LOOPS:
        raise ValueError(f"unknown loop {kind!r}; known: {sorted(LOOPS)}")
    return LOOPS[kind](entry, traffic, seed, seconds)
