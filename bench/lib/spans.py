"""Read the program's own spans and named scopes out of a trace summary.

The engine writes host spans into the profiler's trace on every tick:
``engine.step`` around the tick and, inside it, one span per phase:
``engine.admit`` (``engine.prefill`` nested in it for each request it
prefills) and ``engine.decode.upload``, ``.dispatch``, ``.wait``,
``.readback`` and ``.emit``. Its programs carry named scopes in each
operation's op_name metadata, ``jit(_decode_impl)/while/body/.../attend/
...``: ``kv_write``, ``attend``, ``lm_head`` and ``sample``. XLA gives a
fusion the metadata of its root operation, so a fusion is counted under
the scope of its root.

The host spans are read from a ``trace.Summary``. The op_name metadata is
not in it (``hlo_op_names`` says why): it is read from the trace file, so
only a tool that keeps the file (``bench/tools/phases.py``) attributes
device time to scopes.

Everything here is clipped to the window (``bench.window``) and, for the
device, averaged over devices, as in ``bench/lib/trace.py``; a run of a
program without the spans or scopes reads nothing, never raises.
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench.lib.trace import Event, Plane, Summary, op_name, self_times

STEP_SPAN = "engine.step"
METADATA_PLANE = "/host:metadata"


def is_phase(name: str) -> bool:
    """An engine phase span: ``engine.*`` other than the step itself."""
    return name.startswith("engine.") and name != STEP_SPAN


def host_spans(s: Summary, name: str) -> List[float]:
    """Seconds of each host-line span named ``name``, clipped to the
    window (spans wholly outside it are left out)."""
    return [s._clip(e) * 1e-9 for e in s.host_line.events
            if e.name == name and s._inside(e)]


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one protobuf message:
    varints as int, length-delimited fields as bytes, fixed-width fields
    as their raw bytes."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return out

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, varint()
        elif wire == 2:
            size = varint()
            yield field, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield field, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _sub(buf: bytes, field: int) -> List[bytes]:
    return [v for f, v in _fields(buf) if f == field]


def _str(buf: bytes, field: int) -> str:
    vals = _sub(buf, field)
    return vals[0].decode() if vals else ""


def hlo_op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """Each compiled module's instruction names mapped to their op_name
    metadata (the path of named scopes), read from the HLO protos the
    profiler keeps in the trace's ``/host:metadata`` plane; keyed by module
    name without its ``(<id>)``. A fusion's metadata is its root's.

    ``jax.profiler.ProfileData`` (so ``trace.load``) does not expose that
    plane's event metadata, and a v5e's ``XLA Ops`` events carry no
    op_name stat, so this reads the file itself. Wire format: XSpace.planes
    1; XPlane.name 2, .event_metadata 4 and .stat_metadata 5 (map entries:
    key 1, value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
    XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):
        if _str(plane, 2) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in _sub(plane, 5):
            key = dict(_fields(entry))
            stat_names[key.get(1)] = _str(key.get(2, b""), 2)
        for entry in _sub(plane, 4):
            md = dict(_fields(entry)).get(2, b"")
            module = _str(md, 2).split("(")[0]
            names = out.setdefault(module, {})
            for stat in _sub(md, 5):
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) != "Hlo Proto" or 6 not in st:
                    continue
                for hmod in _sub(st[6], 1):
                    for comp in _sub(hmod, 3):
                        for ins in _sub(comp, 2):
                            meta = _sub(ins, 7)
                            path = _str(meta[0], 2) if meta else ""
                            if path:
                                names[_str(ins, 1)] = path
    return out


def scope_path(ev: Event, op_names: Dict[str, str]) -> str:
    """The op_name metadata of a device operation, by its instruction
    name; '' where ``op_names`` holds none."""
    return op_names.get(op_name(ev.name).split(" ")[0], "")


def scope_of(path: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` that is a component of ``path``."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return None


def program_ops(s: Summary, plane: Plane, programs: Sequence[str]
                ) -> Iterator[Tuple[Event, float]]:
    """(operation, own ns) of each operation that starts inside an
    execution of the named programs on ``plane``."""
    runs = sorted((e.start_ns, e.end_ns)
                  for e in s._executions(plane, programs))
    if not runs:
        return
    starts = [a for a, _ in runs]
    for ev, own in self_times(s._ops(plane), s.t0, s.t1):
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i >= 0 and ev.start_ns < runs[i][1]:
            yield ev, own


def label(ev: Event, op_names: Dict[str, str], scopes: Sequence[str],
          kernels: Sequence[str] = ()) -> Optional[str]:
    """What a device operation's time counts under: one of ``kernels``
    for the kernel's custom call (named after it), ``"<kernel> wrapper"``
    for the layout copies of its jitted wrapper, else the innermost of
    ``scopes`` in its op_name path (from ``op_names``), else None."""
    name, path = op_name(ev.name), scope_path(ev, op_names)
    parts = path.split("/")
    for k in kernels:
        if name.startswith(k):
            return k
        if f"jit({k})" in parts:
            return f"{k} wrapper"
    return scope_of(path, scopes)


def scope_times(s: Summary, scopes: Sequence[str], programs: Sequence[str],
                op_names: Dict[str, str], kernels: Sequence[str] = ()
                ) -> Dict[Optional[str], float]:
    """Own device seconds inside the named programs' executions, per
    device, by ``label``."""
    tot: Dict[Optional[str], float] = collections.Counter()
    for p in s.devices:
        for ev, own in program_ops(s, p, programs):
            tot[label(ev, op_names, scopes, kernels)] += own
    return {k: v / len(s.devices) * 1e-9 for k, v in tot.items()}


def _innermost(spans: List[Event], t0: float, t1: float
               ) -> List[Tuple[float, float, Optional[str]]]:
    """Cut [t0, t1] into pieces, each labelled with the innermost of the
    (properly nested) ``spans`` covering it, or None."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Event] = []
    t = t0

    def emit(until: float) -> None:
        nonlocal t
        until = min(max(until, t0), t1)
        if until > t:
            out.append((t, until, stack[-1].name if stack else None))
            t = until

    for sp in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= sp.start_ns:
            emit(stack[-1].end_ns)
            stack.pop()
        emit(sp.start_ns)
        stack.append(sp)
    while stack:
        emit(stack[-1].end_ns)
        stack.pop()
    emit(t1)
    return out


def idle_by_phase(s: Summary) -> Dict[Optional[str], float]:
    """Device idle seconds in the window, per device, by the innermost
    engine phase span on the host line at the time (None: no phase span;
    ``engine.step`` counts as none)."""
    pieces = _innermost([e for e in s.host_line.events if is_phase(e.name)],
                        s.t0, s.t1)
    tot: Dict[Optional[str], float] = collections.Counter()
    for p in s.devices:
        idle, t = [], s.t0
        for a, b in s._busy(p) + [(s.t1, s.t1)]:
            if a > t:
                idle.append((t, a))
            t = max(t, b)
        i = 0
        for a, b in idle:
            while i < len(pieces) and pieces[i][1] <= a:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < b:
                lo, hi, label = pieces[j]
                tot[label] += min(b, hi) - max(a, lo)
                j += 1
    return {k: v / len(s.devices) * 1e-9 for k, v in tot.items()}
