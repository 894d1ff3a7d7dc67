"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is kept as plain lists (``Trace``): the device operations of each
chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, whose event
names are the HLO instruction text, shapes included), the harness's host
spans (``chipbench.*`` annotations on the host threads) and the traced
window (the ``chipbench.traced`` span). ``to_json``/``from_json`` keep a
trace small enough to commit as a test fixture.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

# ops that only contain other ops: they count towards busy time (the
# device is running the program) but never as a kernel of their own
CONTAINERS = ("while", "conditional", "call")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES)
                    + r")\[([0-9,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_HEAD = re.compile(r"^%(\S+) = ")
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")


@dataclasses.dataclass
class Op:
    text: str          # the HLO instruction, as the trace names the event
    start: float       # ns
    end: float         # ns

    def parts(self) -> Tuple[str, str, str, str]:
        """(instruction name, result text, opcode, operand text)."""
        m = _HEAD.match(self.text)
        if not m:
            return self.text, "", "", ""
        rest = self.text[m.end():]
        if rest.startswith("("):
            depth = 0
            for i, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            result, rest = rest[:i + 1], rest[i + 1:].lstrip()
        else:
            result, _, rest = rest.partition(" ")
        opcode, _, operands = rest.partition("(")
        end = re.search(r"\)(, |$)", operands)
        if end:
            operands = operands[:end.start()]
        return _SUFFIX.sub("", m.group(1)), result, opcode, operands

    @property
    def kernel(self) -> str:
        """``tile_swiglu`` for ``%tile_swiglu.3 = ...``: the instruction's
        name without its numeric and ``.clone`` suffixes."""
        return self.parts()[0]

    @property
    def opcode(self) -> str:
        return self.parts()[2]

    def shapes(self) -> Tuple[List["Shape"], List["Shape"]]:
        """(operands, results) as (dtype, dims, memory space) triples;
        space 0 is HBM, 1 is VMEM (the layout's ``S(1)``)."""
        _, result, _, operands = self.parts()
        return _shapes(operands), _shapes(result)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


Shape = Tuple[str, Tuple[int, ...], int]


def _shapes(text: str) -> List[Shape]:
    out = []
    for m in _SHAPE.finditer(text):
        space = _SPACE.search(m.group(3) or "")
        out.append((m.group(1),
                    tuple(int(x) for x in m.group(2).split(",") if x),
                    int(space.group(1)) if space else 0))
    return out


def nbytes(shapes: List[Shape], space: Optional[int] = None) -> int:
    """Bytes of the arrays, or of those in one memory space."""
    total = 0
    for dtype, dims, where in shapes:
        if space is not None and where != space:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict[str, object]


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]             # device plane name -> its ops
    spans: List[Span]                    # chipbench.* host spans
    window: Tuple[float, float]          # ns, the chipbench.traced span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops_in_window(self, device: Optional[str] = None) -> List[Op]:
        lo, hi = self.window
        devs = [device] if device else sorted(self.ops)
        return [o for d in devs for o in self.ops[d]
                if o.start >= lo and o.end <= hi]

    def spans_in_window(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.spans
                if s.name == name and lo <= s.start < hi]


def load_xplane(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [Op(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        spans.append(Span(e.name, e.start_ns, e.end_ns,
                                          dict(e.stats)))
    windows = [s for s in spans if s.name == "chipbench.traced"]
    if not windows:
        raise ValueError("the trace holds no chipbench.traced span")
    w = windows[0]
    return Trace(ops, spans, (w.start, w.end))


def to_json(trace: Trace) -> str:
    return json.dumps({
        "window": list(trace.window),
        "ops": {d: [[o.text, o.start, o.end] for o in ops]
                for d, ops in trace.ops.items()},
        "spans": [[s.name, s.start, s.end, s.stats] for s in trace.spans],
    })


def from_json(text: str) -> Trace:
    d = json.loads(text)
    return Trace({k: [Op(*o) for o in v] for k, v in d["ops"].items()},
                 [Span(*s) for s in d["spans"]], tuple(d["window"]))


# -- reductions --------------------------------------------------------------------
def union(intervals: List[Tuple[float, float]],
          window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to the window."""
    lo, hi = window
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    per_chip = []
    for dev, ops in trace.ops.items():
        busy = union([(o.start, o.end) for o in ops], trace.window)
        per_chip.append(sum(e - s for s, e in busy) * 1e-9)
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def idle_gaps(trace: Trace, device: str) -> List[Tuple[float, float]]:
    lo, hi = trace.window
    busy = union([(o.start, o.end) for o in trace.ops[device]],
                 trace.window)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_activity(trace: Trace, t: float) -> str:
    """The innermost harness span that was open at time ``t``."""
    inner = None
    for s in trace.spans:
        if s.name != "chipbench.traced" and s.start <= t < s.end:
            if inner is None or s.start >= inner.start:
                inner = s
    return inner.name if inner else "outside harness spans"


def kernel_ops(trace: Trace) -> List[Op]:
    """Device ops inside the window that are not containers."""
    return [o for o in trace.ops_in_window() if o.opcode not in CONTAINERS]


def top(pairs: List[Tuple[str, float]], n: int = 10) -> List[List]:
    acc: Dict[str, float] = {}
    for k, v in pairs:
        acc[k] = acc.get(k, 0.0) + v
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace) -> Dict[str, List[List]]:
    """The device ops that took most time, and the longest idle gaps by
    what the host was doing, summed over the chips."""
    ops = top([(o.kernel, o.seconds) for o in kernel_ops(trace)])
    gaps = []
    for dev in trace.ops:
        for s, e in idle_gaps(trace, dev):
            gaps.append((host_activity(trace, (s + e) / 2), (e - s) * 1e-9))
    return {"device_ops": ops, "idle_gaps": top(gaps)}
