"""The program's named spans, read from a ``--trace 1`` run's profiler trace.

    python3 -m bench.spans .bench_traces/<cell>-<seed> [--workload <cell>]

prints device seconds by span, idle seconds by host span and what is left
without a span.  With ``--workload`` the vocabulary is the cell's (see
:func:`vocabulary`), as the harness reads it in a traced run.

The program names its phases with ``jax.named_scope`` (device spans) and
``jax.profiler.TraceAnnotation`` (host spans).  The vocabulary is copied
here, not imported, so that a change to the program cannot move the
yardstick.  ``SPANS`` is the base vocabulary; a cell adds the scopes of
its own program (a client model's layers, say) as a ``spans`` list in its
configuration or traffic file, so that their time is not booked under
the span that calls them.

* A device span is a path component of a compiled instruction's
  ``op_name`` metadata (a transform wraps the scopes inside it:
  ``vmap(vmap(world.init))``).  A fusion keeps its root's ``op_name``.
  An instruction XLA inserted (a copy, a layout fusion) carries no
  ``op_name``: it takes that of the instruction calling its computation
  (the ``while`` whose body holds it), and so on outwards.  Each device
  operation's self time (``bench.trace``'s) goes to the innermost span of
  its instruction, or to ``(no span)``.  The instructions' ``op_name``
  come from the trace itself: the profiler writes each program's
  optimized HLO (``/host:metadata``, one HloProto a program), and the
  ``XLA Modules`` line says which program each operation ran in.
* A host span is a host event of the host vocabulary.  Each idle gap of
  the device (as ``bench.trace`` finds them: from its first operation to
  the profile's end) is split by the innermost host span that covers each
  part of it.
"""
from __future__ import annotations

import bisect
import re
import sys
from dataclasses import dataclass, field

from bench import trace

SPANS = ("world.init", "round.mobility", "round.channel", "round.select",
         "dagsa.select", "dagsa.eq11", "round.sgd", "round.aggregate",
         "round.compress", "round.eval")
HOST_SPANS = ("fl.call", "fl.dispatch", "fl.fetch", "sweep.call",
              "sweep.params", "sweep.dispatch", "sweep.fetch")
NO_SPAN = "(no span)"

_WRAP = re.compile(r"^(?:\w+\()*|\)*$")
_PROGRAM = re.compile(r"\((\d+)\)$")


def vocabulary(*files: dict) -> tuple:
    """``SPANS`` and the names the ``spans`` lists of ``files`` (a cell's
    configuration and traffic) add to it."""
    out = list(SPANS)
    for f in files:
        out += [n for n in f.get("spans", ()) if n not in out]
    return tuple(out)


def innermost(op_name: str, spans=SPANS) -> str:
    """The innermost span of the vocabulary ``spans`` in an ``op_name``
    path."""
    for part in reversed(op_name.split("/")):
        name = _WRAP.sub("", part)
        if name in spans:
            return name
    return NO_SPAN


# -------------------------------------------------------- protobuf wire --
def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one message: ints for varint and fixed
    fields, memoryview slices for length-delimited ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(b, i)
        elif wire == 1:
            val, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            val, i = b[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield num, val


def _first(b, num: int, default=None):
    return next((v for f, v in _fields(b) if f == num), default)


def _ints(val) -> list:
    """A repeated int field's value: one varint, or a packed run."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        v, i = _varint(val, i)
        out.append(v)
    return out


def hlo_op_names(hlo_proto) -> dict:
    """{instruction name: (op_name, bare)} of every instruction of every
    computation (fused ones too) of a serialized ``xla.HloProto``.  An
    instruction with no ``op_name`` of its own (``bare``) takes that of
    the nearest instruction calling its computation that has one, or ""."""
    own, comp_of, caller = {}, {}, {}
    module = _first(hlo_proto, 1, b"")                 # HloProto.hlo_module
    for f, comp in _fields(module):
        if f != 3:                                     # .computations
            continue
        cid = _first(comp, 5)                          # .id
        for g, inst in _fields(comp):
            if g != 2:                                 # .instructions
                continue
            name, op_name, called = "", "", []
            for h, v in _fields(inst):
                if h == 1:                             # .name
                    name = bytes(v).decode()
                elif h == 7:                           # .metadata.op_name
                    op_name = bytes(_first(v, 2, b"")).decode()
                elif h == 38:                          # .called_computation_ids
                    called += _ints(v)
            own[name], comp_of[name] = op_name, cid
            for c in called:
                caller.setdefault(c, name)

    def named(inst: str, seen: frozenset) -> str:
        up = caller.get(comp_of[inst])
        if own[inst] or up is None or up in seen:
            return own[inst]
        return named(up, seen | {up})

    return {i: (own[i] or named(i, frozenset({i})), not own[i])
            for i in own}


def program_op_names(xspace: bytes) -> dict:
    """{program id: {instruction: (op_name, bare)}} from the
    ``/host:metadata`` plane of a serialized ``XSpace``."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or bytes(_first(plane, 2, b"")) != b"/host:metadata":
            continue
        for g, entry in _fields(plane):
            if g != 4:                                 # .event_metadata
                continue
            meta = _first(entry, 2, b"")               # map value
            for h, stat in _fields(meta):
                if h != 5:                             # .stats
                    continue
                proto = _first(stat, 6)                # .bytes_value
                if proto is not None:
                    out[_first(meta, 1)] = hlo_op_names(proto)
    return out


# ------------------------------------------------------------ reduction --
@dataclass
class Spans:
    """What one trace says of the program's spans."""

    busy_s: float
    unresolved_s: float = 0.0       # ops no traced program names
    unnamed_s: float = 0.0          # ops with no op_name of their own
    device_s: dict = field(default_factory=dict)    # span -> self seconds
    ops: dict = field(default_factory=dict)   # span -> {instruction: s}
    bare: set = field(default_factory=set)    # instructions with no op_name
    idle_s: dict = field(default_factory=dict)      # host span -> seconds
    present: set = field(default_factory=set)       # spans in the programs


def _module_of(modules, starts, t_ns: int):
    """The program id of the ``XLA Modules`` event covering ``t_ns``."""
    i = bisect.bisect_right(starts, t_ns) - 1
    if i >= 0 and t_ns < modules[i][1]:
        return modules[i][2]
    return None


def _split_gap(g0: int, g1: int, spans) -> dict:
    """A gap's nanoseconds by the innermost (shortest) host span covering
    each part of it."""
    inside = [(max(s, g0), min(e, g1), e - s, n) for s, e, n in spans
              if s < g1 and e > g0]
    cuts = sorted({g0, g1, *(s for s, _, _, _ in inside),
                   *(e for _, e, _, _ in inside)})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [(d, n) for s, e, d, n in inside if s <= a and b <= e]
        name = min(cover)[1] if cover else NO_SPAN
        out[name] = out.get(name, 0) + (b - a)
    return out


def reduce(path: str, spans=SPANS) -> Spans:
    """Device seconds by span of the vocabulary ``spans``, idle seconds by
    host span and the spans the traced programs hold, from the trace at
    ``path``.  ``python3 -m bench.spans`` and the harness's traced runs
    both read a trace with this."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    op_names = program_op_names(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    planes = list(pd.planes)
    device_s, ops, bare_ops, busy, ran = {}, {}, set(), [], set()
    first_device, unresolved, unnamed = None, 0.0, 0.0
    for plane in planes:
        if not trace._DEVICE.match(plane.name):
            continue
        events = trace._op_events(plane)
        if not events:
            continue
        modules = sorted(
            (e.start_ns, e.end_ns, int(m.group(1)))
            for ln in plane.lines if ln.name == "XLA Modules"
            for e in ln.events for m in [_PROGRAM.search(e.name)] if m)
        starts = [m[0] for m in modules]
        leaves, self_ns = trace._leaves_and_self(events)
        merged = trace._union((e.start_ns, e.end_ns) for e in leaves)
        busy.append(sum(e - s for s, e in merged))
        if first_device is None:
            first_device = merged
        for e in events:
            pid = _module_of(modules, starts, e.start_ns)
            ran.add(pid)
            names = op_names.get(pid, {})
            inst = trace.instruction(e.name)
            secs = self_ns[id(e)] / 1e9
            op_name, bare = names.get(inst, ("", False))
            if inst not in names:
                unresolved += secs
            elif bare:
                unnamed += secs
                bare_ops.add(inst)
            span = innermost(op_name, spans)
            device_s[span] = device_s.get(span, 0.0) + secs
            mine = ops.setdefault(span, {})
            mine[inst] = mine.get(inst, 0.0) + secs
    if not busy:
        raise ValueError(f"no device operation in {path}")
    present = {innermost(o, spans) for pid in ran
               for o, _ in op_names.get(pid, {}).values()} - {NO_SPAN}
    intervals = [(e.start_ns, e.end_ns, e.name) for p in planes
                 if p.name.startswith("/host:") for ln in p.lines
                 for e in ln.events if e.name in HOST_SPANS]
    length = None
    for p in planes:
        stats = dict(p.stats)
        if "profile_start_time" in stats and "profile_stop_time" in stats:
            length = stats["profile_stop_time"] - stats["profile_start_time"]
    gaps, prev = [], first_device[0][0]
    for s, e in first_device:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if length is not None and length > prev:
        gaps.append((prev, length))
    idle = {}
    for g0, g1 in gaps:
        for name, ns in _split_gap(g0, g1, intervals).items():
            idle[name] = idle.get(name, 0.0) + ns / 1e9
    return Spans(busy_s=sum(busy) / len(busy) / 1e9, unresolved_s=unresolved,
                 unnamed_s=unnamed, device_s=device_s, ops=ops,
                 bare=bare_ops, idle_s=idle, present=present)


def report(s: Spans, top: int = 5) -> list[str]:
    """The lines ``python3 -m bench.spans`` prints for one trace: the
    longest operations of each span are marked ``*`` where they carry no
    ``op_name`` of their own."""
    order = sorted(s.device_s.items(), key=lambda kv: -kv[1])
    longest = [f"  {span}: " + ", ".join(
        f"{i}{'*' if i in s.bare else ''} {v!r}"
        for i, v in sorted(s.ops[span].items(), key=lambda kv: -kv[1])[:top])
        for span, _ in order]
    return [
        f"busy seconds {s.busy_s!r}",
        "device seconds by span (share of busy time): " + ", ".join(
            f"{k} {v!r} ({100 * v / s.busy_s:.2f}%)" for k, v in order),
        "idle seconds by host span: " + ", ".join(
            f"{k} {v!r}" for k, v in sorted(s.idle_s.items(),
                                            key=lambda kv: -kv[1])),
        f"spans in the traced programs: {sorted(s.present)}",
        f"device seconds of operations with no op_name of their own "
        f"(XLA-inserted; each under the span of its nearest named caller): "
        f"{s.unnamed_s!r}; of operations no traced program names: "
        f"{s.unresolved_s!r}",
        "longest operations by span (self seconds):",
        *longest,
    ]


def main(argv: list[str]) -> int:
    if len(argv) not in (1, 3) or argv[1:2] not in ([], ["--workload"]):
        print("usage: python3 -m bench.spans <trace directory or "
              ".xplane.pb> [--workload <cell>]", file=sys.stderr)
        return 2
    names = SPANS
    if len(argv) == 3:
        from bench import harness
        cell = harness.find_cell(argv[2])
        names = vocabulary(cell.config, cell.traffic)
    path = argv[0]
    if not path.endswith(".xplane.pb"):
        path = trace.find_xplane(path)
    print(path)
    for line in report(reduce(path, names)):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
