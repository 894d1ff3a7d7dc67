"""The program's own host spans and layer scopes, read from the profile of
a traced run.

``chipbench.trace`` keeps the harness's spans (``chipbench.*``) and the
HLO text of each device op. The program records spans of its own
(``repro.serve.prefill`` and ``repro.serve.readback`` in
``launch/serve.py``) and names its attention layers with
``jax.named_scope("attn")`` (``models/layers.py``). XLA keeps a scope in
each instruction's ``op_name`` metadata, and the profiler keeps that as
the ``tf_op`` stat of the device op's event metadata, which
``ProfileData`` does not give. This module reads both from the profile
the harness's trace was reduced from and adds them to that trace. A
program without them has no such spans or scopes, and the readers of the
serving loop and model step layers then return nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from chipbench import bench
from chipbench import trace as T

SPAN_PREFIX = "repro."
# the stat of a TPU op's event metadata that holds the instruction's
# op_name (``jit(decode_step)/while/body/closed_call/attn/dot_general:``)
SCOPE_STAT = "tf_op"
# a path component names a scope bare (``attn``) or inside the
# transforms that traced it (``transpose(jvp(attn))``)
_COMPONENT = re.compile(r"^(?:[\w.]+\()*([\w.]*)\)*$")
_TPU = re.compile(r"/device:TPU:\d+")


@dataclasses.dataclass
class ScopedOp(T.Op):
    scope: str = ""    # the instruction's op_name, "" when not recorded

    def in_scope(self, name: str) -> bool:
        for part in self.scope.split("/"):
            m = _COMPONENT.match(part)
            if m and m.group(1) == name:
                return True
        return False


class ProgramTrace(T.Trace):
    """A ``chipbench.trace.Trace`` whose ops are ``ScopedOp``s and whose
    spans include the program's own."""


def scoped(trace: T.Trace, scopes: Dict[str, List[str]],
           spans: List[T.Span] = ()) -> ProgramTrace:
    """``trace`` with the op_name of each op of each TPU plane (in the
    order of its ``XLA Ops`` line) and the program's ``spans`` added."""
    ops = {}
    for dev, dev_ops in trace.ops.items():
        names = scopes.get(dev, [])
        if len(names) != len(dev_ops):
            raise ValueError(
                f"{dev}: {len(dev_ops)} events on its XLA Ops line but "
                f"{len(names)} in the op metadata read for it")
        ops[dev] = [ScopedOp(o.text, o.start, o.end, s)
                    for o, s in zip(dev_ops, names)]
    return ProgramTrace(ops, list(trace.spans) + list(spans), trace.window)


# the few fields of the profiler's XSpace proto (tsl/profiler/protobuf/
# xplane.proto) that hold an op's metadata stats, which ProfileData does
# not give; a map is read as the repeated entries it is on the wire
_XSPACE_FIELDS = {
    "XStat": [("metadata_id", 1, "INT64"), ("str_value", 5, "STRING"),
              ("ref_value", 7, "UINT64")],
    "XEvent": [("metadata_id", 1, "INT64")],
    "XLine": [("name", 2, "STRING"), ("events", 4, "*XEvent")],
    "XEventMetadata": [("stats", 5, "*XStat")],
    "XStatMetadata": [("name", 2, "STRING")],
    "EventMetadataEntry": [("key", 1, "INT64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "INT64"), ("value", 2, "XStatMetadata")],
    "XPlane": [("name", 2, "STRING"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry")],
    "XSpace": [("planes", 1, "*XPlane")],
}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xspace.proto", package="chipbench_xspace",
        syntax="proto3")
    for name, fields in _XSPACE_FIELDS.items():
        m = f.message_type.add(name=name)
        for field, number, kind in fields:
            fd = m.field.add(name=field, number=number)
            if kind[0] == "*":
                fd.label, kind = F.LABEL_REPEATED, kind[1:]
            if kind.isupper():
                fd.type = getattr(F, "TYPE_" + kind)
            else:
                fd.type, fd.type_name = F.TYPE_MESSAGE, \
                    ".chipbench_xspace." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xspace.XSpace"))


def op_scopes(path: str) -> Dict[str, List[str]]:
    """For each TPU plane, the op_name of every event of its ``XLA Ops``
    line, in the line's order ("" where the op's metadata has none)."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    out: Dict[str, List[str]] = {}
    for plane in space.planes:
        if not _TPU.fullmatch(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope = {}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    # a string stat, or a reference to an interned one
                    scope[entry.key] = st.str_value or stat_names.get(
                        st.ref_value, "")
        for line in plane.lines:
            if line.name == "XLA Ops":
                out[plane.name] = [scope.get(e.metadata_id, "")
                                   for e in line.events]
    return out


@functools.lru_cache(maxsize=2)
def _program_part(path: str, mtime: float):
    """(window of the ``chipbench.traced`` span, the program's spans, the
    op scopes) of the profile at ``path``."""
    from jax.profiler import ProfileData
    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "chipbench.traced" and window is None:
                    window = (e.start_ns, e.end_ns)
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append(T.Span(e.name, e.start_ns, e.end_ns,
                                        dict(e.stats)))
    return window, spans, op_scopes(path)


def _newest(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _with_program(trace: T.Trace, path: str) -> Optional[ProgramTrace]:
    """The harness's ``trace`` of the profile at ``path`` with the program's
    part added; None where that profile's window is another."""
    try:
        window, spans, scopes = _program_part(path, os.path.getmtime(path))
        if window is None or tuple(window) != tuple(trace.window):
            return None
        return scoped(trace, scopes, spans)
    except Exception as e:
        raise RuntimeError(f"cannot read the program's spans and scopes "
                           f"from {path}: {e}") from e


def load_xplane(trace_dir: str) -> ProgramTrace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    path = _newest(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return _with_program(T.load_xplane(trace_dir), path)


def for_view(view) -> Optional[ProgramTrace]:
    """The program's trace of the window the view was reduced from: the
    view's own trace where it is one, else the view's trace with the
    program's part of the newest profile under the harness's trace
    directory (``run.py`` writes each traced run's profile there), if
    that profile's window is the view's."""
    if isinstance(view.trace, ProgramTrace):
        return view.trace
    path = _newest(str(bench.CACHE_DIR / "trace"))
    return None if path is None else _with_program(view.trace, path)


# -- reductions ------------------------------------------------------------------
def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_inside(trace: T.Trace, name: str) -> Optional[float]:
    """Percent of the window in which the device was idle while the host
    was inside a span called ``name`` (the union of those spans, so their
    nesting does not count), averaged over the chips; None without such
    spans or device ops."""
    spans = [(s.start, s.end) for s in trace.spans if s.name == name]
    if not spans or not trace.ops or trace.window_s <= 0:
        return None
    inside = T.union(spans, trace.window)
    idle = [_overlap(T.idle_gaps(trace, dev), inside) for dev in trace.ops]
    return 100.0 * sum(idle) / len(idle) * 1e-9 / trace.window_s


def scope_share(trace: ProgramTrace, name: str) -> Optional[float]:
    """Percent of the device's busy time spent in kernel ops of the scope
    ``name``; None where no op carries it (a program without the scope)."""
    inside = sum(o.seconds for o in T.kernel_ops(trace) if o.in_scope(name))
    busy = T.busy_s(trace) * len(trace.ops)
    if inside <= 0 or busy <= 0:
        return None
    return 100.0 * inside / busy
