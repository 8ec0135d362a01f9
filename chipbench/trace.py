"""Reduction of a profiler trace (`.xplane.pb`) to what the per-layer
metrics read.

The trace is first read into `Trace`: for each chip its device
operations (the plane `/device:TPU:<n>`, line "XLA Ops") and its XLA
module executions (line "XLA Modules"), and the host thread that holds
the benchmark's own annotations. Everything after that is plain
interval arithmetic on `Event`s, so the tests build a `Trace` by hand.

  busy        union of a chip's operation intervals inside the window
  idle share  1 - busy / window, averaged over the chips
  module time summed durations of a chip's module executions whose name
              holds a given part ("chunk_step", "eval_acc")
  op time     summed self time of operations matching a predicate
              (self: minus the operations nested in them on that line)
  collectives op time of all-reduce, all-gather, reduce-scatter,
              collective-permute and all-to-all operations
  idle gaps   the window minus chip 0's busy union, each gap labelled by
              the innermost host event that covers its middle
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
CALL = "chipbench.call"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter|psum", re.IGNORECASE)


@dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    dur: int  # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    @property
    def window(self) -> Tuple[int, int]:
        w = [e for e in self.host if e.name == WINDOW]
        if not w:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        return w[0].start, w[0].end

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def from_xspace(path: str) -> Trace:
    """Read one `.xplane.pb` into a Trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [Event(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            if m and line.name == OPS_LINE:
                tr.ops[int(m.group(1))] = evs
            elif m and line.name == MODULES_LINE:
                tr.modules[int(m.group(1))] = evs
            elif not m and any(e.name == WINDOW for e in evs):
                tr.host = evs
    return tr


def find_xspace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(trace: Trace, chip: int) -> int:
    lo, hi = trace.window
    return sum(b - a for a, b in merge(clip(trace.ops.get(chip, []), lo, hi)))


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the chips."""
    chips = sorted(trace.ops)
    if not chips:
        return 0.0
    return sum(busy_ns(trace, c) for c in chips) / len(chips) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.ops:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def self_times(events: Sequence[Event]) -> List[Tuple[Event, int]]:
    """Each event with its duration minus its directly nested events'."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    own = [e.dur for e in evs]
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= evs[stack[-1]].end:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return list(zip(evs, own))


def in_window(trace: Trace, events: Iterable[Event]) -> List[Event]:
    lo, hi = trace.window
    return [e for e in events if e.start >= lo and e.end <= hi]


def module_s(trace: Trace, part: str, chip: Optional[int] = None) -> float:
    """Seconds of module executions whose name holds `part`, on `chip`
    or averaged over the chips."""
    chips = [chip] if chip is not None else sorted(trace.modules)
    if not chips:
        return 0.0
    total = sum(e.dur for c in chips
                for e in in_window(trace, trace.modules.get(c, []))
                if part in e.name)
    return total / len(chips) * 1e-9


def op_s(trace: Trace, pred: Callable[[str], bool], chip: int) -> float:
    """Self seconds of chip `chip`'s operations whose name passes."""
    evs = in_window(trace, trace.ops.get(chip, []))
    return sum(own for e, own in self_times(evs) if pred(e.name)) * 1e-9


def collective_s(trace: Trace) -> Dict[int, float]:
    return {c: op_s(trace, lambda n: bool(COLLECTIVE.search(n)), c)
            for c in sorted(trace.ops)}


def op_kind(name: str) -> str:
    """An operation's kind: its HLO name ("%fusion.179 = f32[...] ...")
    without the "%", the instruction text and the numeric suffix."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Chip-averaged self seconds by operation kind, largest first."""
    tot: Dict[str, float] = defaultdict(float)
    chips = sorted(trace.ops)
    for c in chips:
        for e, own in self_times(in_window(trace, trace.ops[c])):
            tot[op_kind(e.name)] += own * 1e-9 / len(chips)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def gaps(trace: Trace, chip: int = 0) -> List[Tuple[int, int]]:
    lo, hi = trace.window
    busy = merge(clip(trace.ops.get(chip, []), lo, hi))
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def label(trace: Trace, t: int) -> str:
    """The innermost host event that covers time t."""
    cover = [e for e in trace.host if e.start <= t < e.end]
    if not cover:
        return "no host event"
    return min(cover, key=lambda e: e.dur).name


def idle_gaps(trace: Trace, n: int = 10, chip: int = 0,
              ) -> List[Tuple[str, float]]:
    """Idle seconds of chip `chip` by what the host was doing, largest
    first."""
    tot: Dict[str, float] = defaultdict(float)
    for a, b in gaps(trace, chip):
        tot[label(trace, (a + b) // 2)] += (b - a) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
