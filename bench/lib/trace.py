"""Reduce a profiler trace of the measured window to device metrics.

The JAX profiler writes, under ``<dir>/plugins/profile/<time>/``, a
``*.trace.json.gz`` in the Chrome trace format.  On a TPU, each chip is a
process named ``/device:TPU:<n>``; the ops it ran are the complete events
of its thread ``XLA Ops``, each with ``args.tf_op``, the op's name path
(``jit(step)/jvp(spd_solve)/pallas_call:``), in which a
``jax.named_scope`` shows.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) are complete events
on the host's threads, on the same clock.  Times are in microseconds.

* busy: the union of a device's op intervals inside the window, averaged
  over the devices; the idle share is 1 - busy / window;
* per-op and per-scope device time: sums of the durations of the ops that
  enclose no other op.  A loop (``while``, as ``lax.scan`` compiles) shows
  as an op that spans its body's ops; counting it too would count the
  body twice;
* idle gaps: the stretches of the window in which no device op ran, each
  named by the ``bench.*`` host span that covers most of it.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import json
import pathlib

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def merge(intervals) -> list:
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def leaves(ops) -> list:
    """The ops of one device line that enclose no other op of it."""
    ops = sorted(ops, key=lambda op: (op[0], -op[1]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[0] < op[1] and nxt[1] <= op[1])]


def gaps(busy, lo: float, hi: float) -> list:
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Device ops and host spans of one traced window."""

    def __init__(self, events: list):
        procs, threads = {}, {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                procs[e["pid"]] = e["args"]["name"]
            elif e.get("ph") == "M" and e.get("name") == "thread_name":
                threads[(e["pid"], e["tid"])] = e["args"]["name"]
        self.ops = collections.defaultdict(list)   # device -> [(s, e, name, scope)]
        self.spans = []                             # [(s, e, name)]
        for e in events:
            if e.get("ph") != "X":
                continue
            proc = procs.get(e["pid"], "")
            s, d = float(e["ts"]), float(e.get("dur", 0.0))
            if proc.startswith(DEVICE_PREFIX):
                if threads.get((e["pid"], e["tid"])) == OPS_LINE:
                    scope = e.get("args", {}).get("tf_op", "")
                    self.ops[proc].append((s, s + d, e["name"], scope))
            elif e["name"].startswith(SPAN_PREFIX):
                self.spans.append((s, s + d, e["name"]))
        windows = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if windows:
            self.lo, self.hi = windows[0][0], windows[-1][1]
        else:
            ends = [iv for ops in self.ops.values() for iv in ops]
            self.lo = min((s for s, *_ in ends), default=0.0)
            self.hi = max((e for _, e, *_ in ends), default=0.0)

    @classmethod
    def from_file(cls, path) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls(json.load(f)["traceEvents"])

    @classmethod
    def from_dir(cls, root) -> "Trace":
        files = sorted(pathlib.Path(root).glob("**/*.trace.json.gz"))
        if not files:
            raise FileNotFoundError(f"no *.trace.json.gz under {root}")
        return cls.from_file(files[-1])

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-6

    def _busy(self, device) -> list:
        return clip(merge((s, e) for s, e, *_ in self.ops[device]),
                    self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the traced devices."""
        if not self.ops:
            return 0.0
        total = sum(e - s for dev in self.ops for s, e in self._busy(dev))
        return total * 1e-6 / len(self.ops)

    def _window_ops(self):
        for ops in self.ops.values():
            for s, e, name, scope in leaves(ops):
                s, e = max(s, self.lo), min(e, self.hi)
                if e > s:
                    yield e - s, name, scope

    def scope_s(self, scope: str) -> float:
        """Device seconds of the ops whose name path holds ``scope`` as a
        whole scope (``.../spd_solve/...`` or ``...(spd_solve)...``)."""
        marks = (f"/{scope}/", f"({scope})", f"/{scope}:")
        return 1e-6 * sum(d for d, _, path in self._window_ops()
                          if any(m in "/" + path for m in marks))

    def top_ops(self, n: int = 10) -> list:
        """``[[op, seconds], ...]``: the ops that took most device time,
        each named by its HLO name and its name path."""
        tot = collections.Counter()
        for d, name, path in self._window_ops():
            tot[f"{name} {path}".strip()] += d
        return [[k, v * 1e-6] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """``[[host span, seconds], ...]``: the idle time of the devices,
        summed by the ``bench.*`` span that covers most of each gap
        (``none`` where no span does), longest first.  The spans are the
        loop's own, one after another on one thread."""
        spans = sorted((s, e, name) for s, e, name in self.spans
                       if name != WINDOW_SPAN)
        starts = [s for s, _, _ in spans]
        tot = collections.Counter()
        for dev in self.ops:
            for gs, ge in gaps(self._busy(dev), self.lo, self.hi):
                best, cover = "none", 0.0
                i = bisect.bisect_left(starts, ge) - 1
                while i >= 0 and spans[i][1] > gs:
                    s, e, name = spans[i]
                    c = min(e, ge) - max(s, gs)
                    if c > cover:
                        best, cover = name, c
                    i -= 1
                tot[best] += (ge - gs) / len(self.ops)
        return [[k, v * 1e-6] for k, v in tot.most_common(n)]
