"""From a profiler trace to numbers: busy time, idle gaps, kernel time.

``load(log_dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps what the reduction needs in a small JSON-able form::

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

Device planes are ``/device:TPU:<n>``.  Their op line holds one event per
executed HLO op, nested (a layer-scan ``while`` contains its body's ops);
an op keeps its stable base name, so ``%flash_attention_fwd.14 = ...``
becomes ``flash_attention_fwd`` (a Pallas kernel is named by its
``pallas_call``).  The host list keeps only spans named ``bench.*``: the
benchmark's ``TraceAnnotation``s around its calls into the program, on
the same clock as the device events; ``bench.window`` bounds the window.

``Reduction`` computes from that form; the per-layer readers only call its
methods.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
_SUFFIX = re.compile(r"\.\d+$")


def base_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    return _SUFFIX.sub("", hlo.split(" = ", 1)[0].strip().lstrip("%"))


def load(log_dir) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(files, key=lambda f: Path(f).stat()
                                   .st_mtime))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[base_name(e.name), int(e.start_ns),
                                   int(e.duration_ns)] for e in line.events]
            if dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["host"].append([e.name, int(e.start_ns),
                                            int(e.duration_ns)])
    return out


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(ops):
    """[(name, seconds of the op not covered by ops nested in it)]: a
    parent such as a layer-scan ``while`` keeps only its own time."""
    out = []
    stack = []      # [name, end, self_ns]
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    out.extend(stack)
    return [(n, max(ns, 0) / 1e9) for n, _, ns in out]


def is_collective(name: str) -> bool:
    return any(c in name.lower() for c in COLLECTIVES)


class Reduction:
    """Numbers of one traced window, averaged over the devices in it."""

    def __init__(self, trace: dict, window=None):
        self.trace = trace
        self.devices = sorted(trace["devices"])
        if window is None:
            spans = [s for s in trace["host"] if s[0] == "bench.window"]
            if spans:
                window = (spans[0][1], spans[0][1] + spans[0][2])
            else:
                ev = [e for d in self.devices
                      for e in trace["devices"][d]["ops"]]
                window = (min(e[1] for e in ev),
                          max(e[1] + e[2] for e in ev)) if ev else (0, 0)
        self.lo, self.hi = window

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _events(self, dev):
        return [e for e in self.trace["devices"][dev]["ops"]
                if e[1] + e[2] > self.lo and e[1] < self.hi]

    def _busy(self, dev):
        return union(clip([[s, s + d] for _, s, d in self._events(dev)],
                          self.lo, self.hi))

    def _mean(self, per_device) -> float:
        return sum(per_device) / len(self.devices) if self.devices else 0.0

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        return self._mean([total(self._busy(d)) / 1e9 for d in self.devices])

    def idle_share(self):
        if not self.devices or self.hi <= self.lo:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_s(self, match) -> float:
        """Summed device seconds of the ops whose base name ``match``
        accepts (kernels are leaves: nothing nests in them)."""
        return self._mean([sum(d for n, _, d in self._events(dev)
                               if match(n)) / 1e9 for dev in self.devices])

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective ran on a device and no other op
        did, averaged over the devices."""
        out = []
        for d in self.devices:
            ops = self._events(d)
            coll = union([[s, s + t] for n, s, t in ops if is_collective(n)])
            comp = union([[s, s + t] for n, s, t in ops
                          if not is_collective(n) and n != "while"])
            out.append(total(clip(subtract(coll, comp), self.lo,
                                  self.hi)) / 1e9)
        return self._mean(out)

    def top_ops(self, n=10):
        """[[op base name, seconds]] of the ``n`` names with the most self
        time on the device, averaged over the devices."""
        acc = {}
        for d in self.devices:
            for name, t in self_times(self._events(d)):
                acc[name] = acc.get(name, 0.0) + t
        k = max(len(self.devices), 1)
        return [[name, t / k] for name, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n=10):
        """[[what the host was doing, seconds]] of the ``n`` longest gaps
        in which the first device ran nothing, named by the benchmark span
        that overlaps the gap most (``unattributed`` where none does)."""
        if not self.devices:
            return []
        gaps = subtract([[self.lo, self.hi]], self._busy(self.devices[0]))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        spans = [s for s in self.trace["host"] if s[0] != "bench.window"]
        out = []
        for s, e in gaps:
            best, name = 0, "unattributed"
            for sn, ss, sd in spans:
                ov = min(e, ss + sd) - max(s, ss)
                if ov > best:
                    best, name = ov, sn[len(SPAN_PREFIX):]
            out.append([name, (e - s) / 1e9])
        return out
