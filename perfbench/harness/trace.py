"""From a profiler trace to device busy time, kernel time and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. On a TPU each
chip is a ``/device:TPU:<n>`` plane: its ``XLA Modules`` line holds one event
per run of a program (``jit_<name>(<hash>)``), its ``XLA Ops`` line one event
per operation, named by the HLO instruction, and operations nest (a
``while`` holds its body's operations). The harness's own host spans
(``harness.spans``) are events of the same names on the host plane, on the
same clock.

- The window is the interval from the first host span's start to the last
  one's end.
- Busy time is the union of the operations' intervals inside the window,
  per chip, averaged over the chips; the idle share is 1 - busy / window.
- An operation's stable name is its program's name without the hash and its
  instruction's name without numbers (``jit_train_step/fusion``); kernel
  time by name sums the operations' self time (without the operations
  nested in them), so that nothing is counted twice.
- A program's device time is the union of its operations' intervals.
- Each idle gap on the first chip is put down to the host span that
  overlaps it most ("no span" where none does)."""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Op:
    device: int
    name: str            # stable name: program/instruction, no numbers
    module: str          # the program (HLO module) it ran in
    start: int           # ns
    end: int
    self_ns: int = -1    # time not covered by operations nested in it


@dataclass
class Span:
    name: str
    start: int
    end: int


def stable_name(module: str, instruction: str) -> str:
    """``jit_train_step(123)`` and ``%fusion.480 = ...`` give
    ``jit_train_step/fusion``."""
    prog = re.sub(r"\(\d+\)$", "", module) or "?"
    instr = instruction.split(" = ", 1)[0].strip().lstrip("%")
    return f"{prog}/{re.sub(r'[.]?\d+', '', instr)}"


def _device_ops(device: int, modules, ops) -> List[Op]:
    """``modules`` and ``ops`` as (name, start, end); each op is given the
    module run that holds its start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, start, end in ops:
        i = bisect.bisect_right(starts, start) - 1
        module = modules[i][0] if i >= 0 and start < modules[i][2] else ""
        out.append(Op(device, stable_name(module, name), module, start, end))
    return out


def load(path: str, host_spans: Iterable[str]) -> Tuple[List[Op], List[Span]]:
    """Device operations and the named host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    wanted = set(host_spans)
    ops: List[Op] = []
    spans: List[Span] = []

    def events(line):
        for ev in line.events:
            start = int(ev.start_ns)
            yield ev.name, start, start + int(ev.duration_ns)

    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(events(line)) for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            ops += _device_ops(int(m.group(1)), lines.get(MODULES_LINE, []),
                               lines.get(OPS_LINE, []))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans += [Span(*e) for e in events(line) if e[0] in wanted]
    return ops, spans


def self_times(ops: List[Op]) -> None:
    """Sets each op's ``self_ns``: its time less that of the ops nested
    directly in it (ops of one chip)."""
    stack: List[Op] = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        op.self_ns = op.end - op.start
        while stack and op.start >= stack[-1].end:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Tuple[int, int], b: Tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclass
class Reduction:
    window: Tuple[int, int]
    devices: int
    busy_ns: List[int]
    ops: List[Op] = field(default_factory=list)      # clipped to the window
    idle: List[Tuple[str, int]] = field(default_factory=list)   # (span, ns)
    traced: List[Op] = field(default_factory=list)   # every op in the trace

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns) / 1e9

    def op_seconds(self) -> Dict[str, float]:
        """Device self seconds by stable operation name, over every chip."""
        out: Dict[str, float] = {}
        for op in self.ops:
            out[op.name] = out.get(op.name, 0.0) + op.self_ns / 1e9
        return out

    def module_seconds(self, parts: Sequence[str]) -> float:
        """Device seconds of the programs whose name holds one of ``parts``,
        over the whole trace: the union of their operations' intervals, per
        chip, summed. (The device clock may lead the host's by a fraction of
        a millisecond, so an operation dispatched early in the first span can
        show before the window opens.)"""
        total = 0
        for d in range(self.devices):
            total += sum(e - s for s, e in union(
                [(o.start, o.end) for o in self.traced if o.device == d
                 and any(p in o.module for p in parts)]))
        return total / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        idle = sorted(self.idle, key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, ns / 1e9] for n, ns in idle[:top]]}


def reduce(ops: List[Op], spans: List[Span], devices: int) -> Reduction:
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    lo = min(s.start for s in spans)
    hi = max(s.end for s in spans)
    inside = [op for op in ops
              if op.end > lo and op.start < hi and op.device < devices]
    busy_ns, first = [], []
    for d in range(devices):
        u = clip(union([(o.start, o.end) for o in inside if o.device == d]),
                 lo, hi)
        busy_ns.append(sum(e - s for s, e in u))
        if d == 0:
            first = u
    idle = []
    for g in gaps(first, lo, hi):
        best, most = "no span", 0
        for s in spans:
            ov = overlap(g, (s.start, s.end))
            if ov > most:
                best, most = s.name, ov
        idle.append((best, g[1] - g[0]))
    clipped = [Op(o.device, o.name, o.module, max(o.start, lo),
                  min(o.end, hi)) for o in inside]
    for d in range(devices):
        self_times([o for o in clipped if o.device == d])
    return Reduction(window=(lo, hi), devices=devices, busy_ns=busy_ns,
                     ops=clipped, idle=idle, traced=list(ops))


def reduce_dir(trace_dir: str, *, devices: int,
               host_spans: Iterable[str]):
    """The reduction of the newest trace written under ``trace_dir``, or
    None where it holds no TPU operation at all (a run on the CPU)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, spans = load(paths[-1], host_spans)
    return reduce(ops, spans, devices) if ops else None
