"""From a profiler trace to numbers: the one reduction every PR uses.

A trace is read into plain events (plane, line, name, start, duration in
ns, string stats), so the arithmetic below runs the same on a recorded
trace, a hand-built one in the tests, and a live run:

* busy time: the union of the intervals in which an operation ran on a
  device (its op line), clipped to the traced window, averaged over the
  devices;
* kernel time: the summed device durations of one kernel's events;
* idle gaps: the stretches of the window with no device operation, each
  named by what the host was doing in it (the innermost host event that
  covers most of the gap).

The traced window is the benchmark's own ``chipbench.traced`` host span.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Iterable, Optional

#: the host span that marks the traced window
WINDOW_SPAN = "chipbench.traced"
#: the device line whose events are single operations
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start: float               # ns
    dur: float                 # ns
    stats: tuple = ()          # ((key, value), ...) string-valued stats

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def op(self) -> str:
        """The HLO instruction name of a device op event, whose full
        name is the instruction's text (``%fusion.7 = u32[...] ...``)."""
        return self.name.split(" = ", 1)[0]

    def short(self) -> str:
        """Instruction name, result type without layout, and opcode:
        ``%fusion.7 u32[262144] fusion``."""
        head, _, rest = self.name.partition(" = ")
        if not rest:
            return head
        rest = re.sub(r"\{[^{}]*\}", "", rest)
        if rest.startswith("("):           # a tuple result
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            result, tail = rest[:i + 1], rest[i + 1:]
        else:
            result, _, tail = rest.partition(" ")
        opcode = re.match(r"\s*([\w-]+)\(", tail)
        return " ".join([head, result] + ([opcode.group(1)] if opcode
                                          else []))

    def result_dims(self) -> tuple[int, ...]:
        """The dims of the instruction's (first) result type."""
        m = re.search(r"\[([\d,]*)\]", self.name.partition(" = ")[2])
        return tuple(int(x) for x in m.group(1).split(",") if x) if m \
            else ()


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "host" not in plane.lower()


def read_trace(logdir: str) -> list[Ev]:
    """Every event of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                stats = tuple((str(k), str(v)) for k, v in e.stats
                              if isinstance(v, str))
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns),
                              stats))
    return out


def union_ns(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def window(events: list[Ev]) -> tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if spans:
        return spans[0].start, spans[0].end
    ops = [e for e in events if is_device(e.plane) and e.line in OP_LINES]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(e.start for e in ops), max(e.end for e in ops)


def _clip(e: Ev, lo: float, hi: float) -> Optional[tuple[float, float]]:
    s, t = max(e.start, lo), min(e.end, hi)
    return (s, t) if t > s else None


def host_activity(events: list[Ev],
                  spans: list[tuple[float, float]]) -> list[str]:
    """What the host was doing in each of ``spans`` (``(s, t)`` pairs,
    sorted and disjoint, as ``gaps`` gives them): the shortest host event
    covering at least half of it, else the one covering most; of equals,
    the first in ``events``. One sweep over the host events in order of
    start: those that start before a span ends are taken in, and those
    that end before it starts are let go, since they end before every
    later span starts too."""
    host = sorted(((e.start, i, e) for i, e in enumerate(events)
                   if not is_device(e.plane) and e.name != WINDOW_SPAN),
                  key=lambda x: (x[0], x[1]))
    names, live, nxt = [], [], 0
    for s, t in spans:
        while nxt < len(host) and host[nxt][0] < t:
            live.append(host[nxt][1:])
            nxt += 1
        live = sorted((ie for ie in live if ie[1].end > s),
                      key=lambda ie: ie[0])
        best, best_key = "no host event", None
        for _, e in live:
            c = _clip(e, s, t)
            if c is None:
                continue
            cover = (c[1] - c[0]) / (t - s)
            key = (cover >= 0.5, -e.dur if cover >= 0.5 else cover)
            if best_key is None or key > best_key:
                best, best_key = e.name, key
        names.append(best)
    return names


def self_times(events: list[Ev]) -> list[tuple[Ev, float]]:
    """Each event with its self time: its duration less that of the
    events nested in it on the same device line (a while loop holds
    the ops of its body)."""
    out: list[list] = []
    stacks: dict[tuple, list] = defaultdict(list)
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        stack = stacks[(e.plane, e.line)]
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        if stack:
            stack[-1][1] -= min(e.end, stack[-1][0].end) - e.start
        item = [e, e.dur]
        out.append(item)
        stack.append(item)
    return [(e, own) for e, own in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over devices
    n_devices: int
    op_seconds: dict                    # op -> self device s (avg/device)
    gap_list: list                      # [(host activity, seconds)]
    events: list                        # device op events inside it

    def kernel(self, trace_name: str) -> list[Ev]:
        """The events of the custom call ``trace_name`` (instruction
        ``%<trace_name>`` or ``%<trace_name>.<n>``), over every device."""
        pat = re.compile(r"^%" + re.escape(trace_name) + r"(\.\d+)?$")
        return [e for e in self.events if pat.match(e.op)]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gap_list[:top]]}


def summarize(events: list[Ev]) -> Summary:
    lo, hi = window(events)
    by_dev: dict[str, list] = defaultdict(list)
    clipped, inside = [], []
    for e in events:
        if is_device(e.plane) and e.line in OP_LINES:
            c = _clip(e, lo, hi)
            if c is not None:
                by_dev[e.plane].append(c)
                clipped.append(dataclasses.replace(e, start=c[0],
                                                   dur=c[1] - c[0]))
                if c == (e.start, e.end):
                    inside.append(e)
    if not by_dev:
        raise ValueError("no device operation ran in the traced window")
    n_dev = len(by_dev)
    busy = sum(union_ns(iv) for iv in by_dev.values()) / n_dev
    ops: dict[str, float] = defaultdict(float)
    for e, own in self_times(clipped):
        ops[e.short()] += own * 1e-9 / n_dev
    # idle gaps of the first device, named by the host's activity
    first = sorted(by_dev)[0]
    idle = gaps(by_dev[first], lo, hi)
    named = [(name, (t - s) * 1e-9)
             for name, (s, t) in zip(host_activity(events, idle), idle)]
    named.sort(key=lambda kv: -kv[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   n_devices=n_dev, op_seconds=dict(ops), gap_list=named,
                   events=inside)
