"""From a profiler trace to the numbers per-layer metrics read.

The JAX profiler writes an ``.xplane.pb`` file. Its device planes
(``/device:TPU:<n>``) hold a line of XLA modules, one event per program
run (``jit__segment(...)``), and a line of XLA ops, one event per
operation that ran. The host plane holds the client's own spans
(``client.submit``, ``engine.step``, ``client.harvest``, ``client.idle``)
on the clock the device events are placed on.

Busy time is the union of the op intervals; idle time is the rest of
the traced window. Each idle gap is labelled by the client span that
was open at its midpoint.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]           # (start_ns, end_ns)

_SUFFIX = re.compile(r"[(.].*$")     # "jit__segment(123)" -> "jit__segment"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int


@dataclasses.dataclass
class DeviceTrace:
    """Events of one device plane, clipped to nothing yet."""
    plane: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class TraceSummary:
    window_ns: Interval
    devices: List[DeviceTrace]
    spans: List[Event]               # the client's spans, time-sorted

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        total = sum(union_ns(clip((e.start_ns, e.end_ns) for e in d.ops),
                             self.window_ns)
                    for d in self.devices)
        return total * 1e-9 / len(self.devices)

    def module_s(self, prefixes: Sequence[str]) -> float:
        """Device seconds of the programs whose name (without its id
        suffix) is one of ``prefixes``, summed over devices."""
        want = set(prefixes)
        return sum(_overlap((e.start_ns, e.end_ns), self.window_ns)
                   for d in self.devices for e in d.modules
                   if base_name(e.name) in want) * 1e-9

    def ops_matching(self, pattern: str,
                     modules: Optional[Sequence[str]] = None) -> float:
        """Device seconds of ops whose name matches ``pattern``, and
        that start inside a run of one of ``modules`` where given."""
        rx = re.compile(pattern)
        total = 0
        for d in self.devices:
            spans = sorted((m.start_ns, m.end_ns) for m in d.modules
                           if modules is None
                           or base_name(m.name) in modules)
            starts = [s for s, _ in spans]
            for e in d.ops:
                if not rx.search(e.name):
                    continue
                if modules is not None:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    if i < 0 or e.start_ns >= spans[i][1]:
                        continue
                total += _overlap((e.start_ns, e.end_ns), self.window_ns)
        return total * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` innermost ops that took most device time, in
        seconds: an op that holds others (a loop) is left out, its body
        counts."""
        tot: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            ops = sorted(d.ops, key=lambda e: (e.start_ns, -e.end_ns))
            for i, e in enumerate(ops):
                if i + 1 < len(ops) and ops[i + 1].start_ns < e.end_ns:
                    continue
                tot[op_label(e.name)] += _overlap((e.start_ns, e.end_ns),
                                                  self.window_ns)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first device, each with
        the client span open at its midpoint."""
        if not self.devices:
            return []
        busy = merge(clip((e.start_ns, e.end_ns)
                          for e in self.devices[0].ops), self.window_ns)
        gaps, cur = [], self.window_ns[0]
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.window_ns[1]:
            gaps.append((cur, self.window_ns[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]

    def span_at(self, t_ns: int) -> str:
        """The innermost client span open at ``t_ns``."""
        best = None
        for sp in self.spans:
            if sp.start_ns > t_ns:
                break
            if sp.end_ns >= t_ns and (best is None
                                      or sp.start_ns >= best.start_ns):
                best = sp
        return best.name if best is not None else "outside client spans"


def base_name(name: str) -> str:
    return _SUFFIX.sub("", name)


_OPCODE = re.compile(r"\s([a-z][a-z-]*)\(")


def op_label(name: str) -> str:
    """A short label for an op whose trace name is its HLO text:
    ``%fusion.201 = bf16[...] fusion(...), ...`` -> ``%fusion.201 fusion``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:160]
    m = _OPCODE.search(" " + rest)
    return f"{head} {m.group(1)}" if m else head


def clip(intervals: Iterable[Interval]) -> List[Interval]:
    return [iv for iv in intervals if iv[1] > iv[0]]


def merge(intervals: Iterable[Interval], window: Interval
          ) -> List[Interval]:
    """Sorted, disjoint union of the intervals, clipped to ``window``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals: Iterable[Interval], window: Interval) -> int:
    return sum(e - s for s, e in merge(intervals, window))


def _overlap(iv: Interval, window: Interval) -> int:
    return max(0, min(iv[1], window[1]) - max(iv[0], window[0]))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def summarize(planes, span_names: Sequence[str],
              window_ns: Optional[Interval] = None) -> TraceSummary:
    """Reduce profiler planes (``ProfileData(...).planes``, or any
    objects with ``name``, ``lines`` of ``name`` and ``events``) to a
    summary. Without ``window_ns`` the window runs from the first
    client span's start to the last one's end."""
    devices, spans = [], []
    wanted = set(span_names)
    for plane in planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += _events(line)
                elif line.name == "XLA Ops":
                    ops += _events(line)
            devices.append(DeviceTrace(plane.name, mods, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e.name in wanted]
    spans.sort(key=lambda e: e.start_ns)
    if window_ns is None:
        if not spans:
            raise ValueError("no client span in the trace")
        window_ns = (spans[0].start_ns, max(e.end_ns for e in spans))
    return TraceSummary(window_ns=window_ns, devices=devices, spans=spans)
