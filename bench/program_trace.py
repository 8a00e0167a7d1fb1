"""The program's own spans and named scopes in a profiler trace.

``bench/trace.py`` reduces a trace to what the accepted per-layer metrics
read: the client's spans, program runs, op intervals. This module reads
what the program itself adds to the same trace:

- the spans ``DecodeEngine.step`` opens (``repro.serving.tracing``): one
  per engine phase, and ``engine.wait`` around each host read that waits
  for the device, on the host plane and the device's clock;
- the ``jax.named_scope`` path of each device op (``decode.layers``,
  ``decode.attention``, ``decode.mlp``, ``decode.head``): the op's
  ``op_name`` metadata, which the profiler keeps in the ``SCOPE_STAT``
  stat of the op's event metadata. ``ProfileData`` does not expose
  event metadata, so ``op_scopes`` reads it from the ``.xplane.pb``
  file itself (the ``XSpace`` protobuf of ``tsl/profiler/protobuf/
  xplane.proto``).

From a trace kept by ``bench/run.py --trace 1 --trace-dir <dir>``:

    python3 bench/program_trace.py <dir> --segment-len 8

prints the host self-time per segment and per phase, decode attention's
device time per step, the share of ``jit__segment`` time under each
scope, the ops above 1% of it with their scope, and the idle gaps
labelled by the innermost span, client's or program's. ``--slice <out>``
also writes two decode steps of the trace (``write_slice``), for tests.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import gzip
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    _root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_root / "src"), str(_root)]

from repro.serving.tracing import PHASES, SEGMENT, SPANS, WAIT  # noqa: E402

from bench import trace  # noqa: E402

SCOPE_STAT = "tf_op"      # the metadata stat of an XLA op: its op_name
SEGMENT_PROGRAM = "jit__segment"
ATTENTION = r"(^|/)decode\.attention(/|$)"
SCOPES = ("decode.layers", "decode.attention", "decode.mlp", "decode.head")


@dataclasses.dataclass
class Op:
    name: str
    start_ns: int
    end_ns: int
    scope: str                       # op_name metadata; "" if none


@dataclasses.dataclass
class ProgramTrace:
    window_ns: trace.Interval
    spans: List[trace.Event]         # the program's spans, time-sorted
    modules: List[List[trace.Event]]  # per device: program runs
    ops: List[List[Op]]              # per device: ops with their scope

    # -- spans ---------------------------------------------------------

    def span_s(self, names: Sequence[str]) -> float:
        """Seconds of the spans named ``names`` inside the window."""
        want = set(names)
        return sum(trace._overlap((e.start_ns, e.end_ns), self.window_ns)
                   for e in self.spans if e.name in want) * 1e-9

    def span_count(self, name: str) -> int:
        """Spans named ``name`` that start inside the window."""
        w0, w1 = self.window_ns
        return sum(1 for e in self.spans
                   if e.name == name and w0 <= e.start_ns < w1)

    def engine_host_ms(self) -> Optional[float]:
        """Host self-time of the engine per decode segment, in ms: the
        phase spans less the waits nested in them, over the segment
        spans that start in the window."""
        n = self.span_count(SEGMENT)
        if n == 0:
            return None
        return 1e3 * (self.span_s(PHASES) - self.span_s([WAIT])) / n

    def phase_self_ms(self) -> Dict[str, float]:
        """Each phase's self-time per decode segment, in ms: its spans
        less the waits that start inside them."""
        n = self.span_count(SEGMENT)
        if n == 0:
            return {}
        waits = [e for e in self.spans if e.name == WAIT]
        out = {}
        for phase in PHASES:
            total = 0
            for e in self.spans:
                if e.name != phase:
                    continue
                total += trace._overlap((e.start_ns, e.end_ns),
                                        self.window_ns)
                total -= sum(trace._overlap((w.start_ns, w.end_ns),
                                            self.window_ns)
                             for w in waits
                             if e.start_ns <= w.start_ns < e.end_ns)
            out[phase] = 1e3 * total * 1e-9 / n
        out[WAIT] = 1e3 * self.span_s([WAIT]) / n
        return out

    # -- device ops by scope --------------------------------------------

    def _in_runs(self, device: int, prefixes: Optional[Sequence[str]]):
        """The ops of one device, those that start inside a run of one
        of ``prefixes`` where given."""
        ops = self.ops[device]
        if prefixes is None:
            return ops
        runs = sorted((m.start_ns, m.end_ns) for m in self.modules[device]
                      if trace.base_name(m.name) in prefixes)
        starts = [s for s, _ in runs]
        out = []
        for e in ops:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < runs[i][1]:
                out.append(e)
        return out

    def scope_s(self, pattern: str,
                modules: Optional[Sequence[str]] = None) -> float:
        """Device seconds in which an op whose scope matches ``pattern``
        ran (the union of their intervals, so a nested op counts once),
        of ops that start in a run of ``modules`` where given; summed
        over devices."""
        rx = re.compile(pattern)
        return sum(trace.union_ns(((e.start_ns, e.end_ns)
                                   for e in self._in_runs(d, modules)
                                   if rx.search(e.scope)), self.window_ns)
                   for d in range(len(self.ops))) * 1e-9

    def module_runs(self, prefixes: Sequence[str]) -> int:
        """Runs of the programs ``prefixes`` that start inside the
        window, on the first device."""
        if not self.modules:
            return 0
        w0, w1 = self.window_ns
        return sum(1 for e in self.modules[0]
                   if trace.base_name(e.name) in prefixes
                   and w0 <= e.start_ns < w1)

    def attention_ms_per_step(self, segment_len: int) -> Optional[float]:
        """Device time of the segment program's ops under
        ``decode.attention``, per decode step of its runs, in ms."""
        secs = self.scope_s(ATTENTION, [SEGMENT_PROGRAM])
        steps = self.module_runs([SEGMENT_PROGRAM]) * segment_len
        if secs <= 0 or steps == 0:
            return None
        return 1e3 * secs / steps

    def scope_shares(self, modules: Sequence[str] = (SEGMENT_PROGRAM,)
                     ) -> Dict[str, float]:
        """Share of the innermost ops' device time in ``modules`` under
        each of ``SCOPES`` (nested scopes count in each), and under
        none, in %."""
        tot: Dict[str, int] = defaultdict(int)
        for name, scope, ns in self._innermost(modules):
            tot[""] += ns
            hit = [s for s in SCOPES if re.search(rf"(^|/){re.escape(s)}"
                                                  r"(/|$)", scope)]
            for s in hit:
                tot[s] += ns
            if not hit:
                tot["(none)"] += ns
        whole = tot.pop("", 0)
        return {k: 100.0 * v / whole for k, v in tot.items()} if whole \
            else {}

    def top_ops(self, modules: Sequence[str] = (SEGMENT_PROGRAM,),
                min_share: float = 1.0) -> List[List]:
        """Innermost ops (by label) of ``modules`` that take at least
        ``min_share`` % of their device time: [label, %, scope]."""
        tot: Dict[str, int] = defaultdict(int)
        scope_of: Dict[str, str] = {}
        for name, scope, ns in self._innermost(modules):
            label = trace.op_label(name)
            tot[label] += ns
            scope_of[label] = scope
        whole = sum(tot.values())
        rows = sorted(tot.items(), key=lambda kv: -kv[1])
        return [[k, 100.0 * v / whole, scope_of[k]] for k, v in rows
                if whole and 100.0 * v / whole >= min_share]

    def _innermost(self, modules):
        """(name, scope, ns inside the window) of each op that holds no
        other op, of the runs of ``modules``."""
        for d in range(len(self.ops)):
            ops = sorted(self._in_runs(d, modules),
                         key=lambda e: (e.start_ns, -e.end_ns))
            for i, e in enumerate(ops):
                if i + 1 < len(ops) and ops[i + 1].start_ns < e.end_ns:
                    continue
                yield e.name, e.scope, trace._overlap(
                    (e.start_ns, e.end_ns), self.window_ns)


# -- the scope of each op, from the xplane protobuf ----------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for a varint, a memoryview for the rest."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def op_scopes(xplane_path: str, stat: str = SCOPE_STAT) -> Dict[str, str]:
    """The value of the ``stat`` stat of each TPU event metadata, keyed
    by the metadata's name (an XLA op's HLO text, the name
    ``ProfileData`` gives its events). XSpace: planes = 1; XPlane:
    name = 2, event_metadata = 4, stat_metadata = 5 (map entries:
    key = 1, value = 2); XEventMetadata: name = 2, stats = 5;
    XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
    str_value = 5, ref_value = 7 (the id of a stat metadata that holds
    the string)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, names = "", [], {}
        for pnum, value in _fields(plane):
            if pnum == 2:
                name = _text(value)
            elif pnum == 4:
                events.append(value)
            elif pnum == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not re.fullmatch(r"/device:TPU:\d+", name):
            continue
        want = next((k for k, v in names.items() if v == stat), None)
        if want is None:
            continue
        for entry in events:
            key = value = None
            for mnum, mval in _fields(dict(_fields(entry)).get(2, b"")):
                if mnum == 2:
                    key = _text(mval)
                elif mnum == 5:
                    st = dict(_fields(mval))
                    if st.get(1) == want:
                        value = (_text(st[5]) if 5 in st
                                 else names.get(st.get(7), ""))
            if key is not None and value is not None:
                out[key] = value
    return out


def summarize(planes, window_ns: trace.Interval,
              scopes: Optional[Dict[str, str]] = None) -> ProgramTrace:
    """The program's spans and device ops of profiler planes
    (``ProfileData(...).planes``, or objects shaped alike) over
    ``window_ns``; each op takes its scope from ``scopes`` (by event
    name, see ``op_scopes``)."""
    scopes = scopes or {}
    spans, modules, ops = [], [], []
    own = set(SPANS)
    for plane in planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += trace._events(line)
                elif line.name == "XLA Ops":
                    dev_ops += [Op(e.name, int(e.start_ns),
                                   int(e.start_ns + e.duration_ns),
                                   scopes.get(e.name, ""))
                                for e in line.events]
            modules.append(mods)
            ops.append(dev_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in trace._events(line) if e.name in own]
    spans.sort(key=lambda e: e.start_ns)
    return ProgramTrace(window_ns, spans, modules, ops)


def labelled_gaps(summary: trace.TraceSummary, program: ProgramTrace,
                  n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of ``summary``, each labelled by the
    innermost span, client's or program's, open at its midpoint; a wait
    by its phase too (``engine.segment/engine.wait``)."""
    phases = [e for e in program.spans if e.name != WAIT]
    spans = list(phases)
    for w in program.spans:
        if w.name == WAIT:
            outer = [p for p in phases
                     if p.start_ns <= w.start_ns and w.end_ns <= p.end_ns]
            label = f"{outer[-1].name}/{WAIT}" if outer else WAIT
            spans.append(dataclasses.replace(w, name=label))
    both = sorted(summary.spans + spans, key=lambda e: e.start_ns)
    return dataclasses.replace(summary, spans=both).idle_gaps(n)


def write_slice(planes, scopes: Dict[str, str], t0: int, t1: int,
                out: Path, counters: Optional[Dict[str, int]] = None
                ) -> None:
    """The events of ``planes`` that overlap [t0, t1) as gzip JSON: the
    devices' modules and ops (each op by its short label, with its
    scope), and the client's and program's spans. Each line lists its
    event names once (``names``), and ops their scopes once
    (``scopes``); an event is [name index, start less the previous
    event's start, duration] and, for an op, its scope index.
    ``counters`` (the run's window deltas) ride along."""
    from bench.client import SPANS as CLIENT_SPANS
    keep = set(CLIENT_SPANS) | set(SPANS)
    res = []
    for plane in planes:
        device = re.fullmatch(r"/device:TPU:\d+", plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            ops = bool(device) and line.name == "XLA Ops"
            names: Dict[str, int] = {}
            scope_ids: Dict[str, int] = {}
            ev, prev = [], 0
            for e in sorted(line.events, key=lambda e: e.start_ns):
                s, d = int(e.start_ns), int(e.duration_ns)
                if s + d <= t0 or s >= t1 or not (device
                                                  or e.name in keep):
                    continue
                label = trace.op_label(e.name) if ops else e.name
                row = [names.setdefault(label, len(names)), s - prev, d]
                if ops:
                    row.append(scope_ids.setdefault(
                        scopes.get(e.name, ""), len(scope_ids)))
                ev.append(row)
                prev = s
            if ev:
                lines.append({"name": line.name, "names": list(names),
                              "events": ev})
                if ops:
                    lines[-1]["scopes"] = list(scope_ids)
        res.append({"name": plane.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": res, "counters": counters or {}}, f,
                  separators=(",", ":"))


def read_slice(path: Path):
    """A slice ``write_slice`` wrote, as (planes shaped like
    ``ProfileData``'s, the scope of each op by name, counters)."""
    with gzip.open(path, "rt") as f:
        obj = json.load(f)
    scopes: Dict[str, str] = {}
    planes = []
    for p in obj["planes"]:
        lines = []
        for ln in p["lines"]:
            events, start = [], 0
            for row in ln["events"]:
                start += row[1]
                name = ln["names"][row[0]]
                events.append(_SliceEvent(name, start, row[2]))
                if len(row) > 3:
                    scopes[name] = ln["scopes"][row[3]]
            lines.append(_SliceLine(ln["name"], events))
        planes.append(_SlicePlane(p["name"], lines))
    return planes, scopes, obj.get("counters", {})


def load(trace_dir) -> Tuple[trace.TraceSummary, ProgramTrace, list,
                             Dict[str, str]]:
    """The profile under ``trace_dir`` (a traced run's) reduced over the
    window ``bench/run.py`` measures: from the observation that opened
    it to the one that closed it, each of which starts a
    ``client.harvest`` span. Returns the client's reduction, the
    program's, the planes and the scope of each op."""
    from bench.client import SPANS as CLIENT_SPANS
    from jax.profiler import ProfileData
    path = trace.find_xplane(str(trace_dir))
    planes = list(ProfileData.from_file(path).planes)
    scopes = op_scopes(path)
    summary = trace.summarize(planes, CLIENT_SPANS)
    harvest = [s for s in summary.spans if s.name == "client.harvest"]
    if len(harvest) >= 2:
        summary.window_ns = (harvest[0].start_ns, harvest[-1].start_ns)
    return summary, summarize(planes, summary.window_ns, scopes), planes, \
        scopes


@dataclasses.dataclass
class _SliceEvent:
    name: str
    start_ns: int
    duration_ns: int


@dataclasses.dataclass
class _SliceLine:
    name: str
    events: List[_SliceEvent]


@dataclasses.dataclass
class _SlicePlane:
    name: str
    lines: List[_SliceLine]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--segment-len", type=int, required=True)
    ap.add_argument("--slice", type=Path, default=None,
                    help="also write two steps' events here (gzip JSON)")
    args = ap.parse_args(argv)
    summary, program, planes, scopes = load(args.trace_dir)
    print(json.dumps({
        "window_s": summary.window_s,
        "engine_host_ms": program.engine_host_ms(),
        "phase_self_ms": program.phase_self_ms(),
        "attention_ms_per_step":
            program.attention_ms_per_step(args.segment_len),
        "segment_runs": program.module_runs([SEGMENT_PROGRAM]),
        "segment_scope_share": program.scope_shares(),
        "segment_ops_over_1pct": program.top_ops(),
        "idle_gaps": labelled_gaps(summary, program, 10),
    }, indent=1))
    if args.slice is not None:
        steps = [s for s in summary.spans if s.name == "engine.step"
                 and any(e.name == SEGMENT and s.start_ns <= e.start_ns
                         < s.end_ns for e in program.spans)]
        mid = len(steps) // 2
        picked = steps[mid:mid + 2]
        if picked:
            after = [s for s in summary.spans
                     if s.start_ns >= picked[-1].end_ns]
            t1 = after[0].end_ns if after else picked[-1].end_ns
            write_slice(planes, scopes, picked[0].start_ns, t1, args.slice)
    return 0


if __name__ == "__main__":
    sys.exit(main())
