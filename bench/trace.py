"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the device's busy time, the time of each Pallas kernel, the device
operations that took longest and the longest idle gaps with what the host
was doing in them.

* Busy time is the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any.
* A kernel's time is the sum of the durations of its calls' events.  A
  call is found by its instruction name in the compiled program (see
  ``bench.counting.kernel_calls``) or by the kernel's name in the event.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Summary:
    busy_s: float
    window_s: float
    op_seconds: dict = field(default_factory=dict)
    kernel_seconds: dict = field(default_factory=dict)
    call_counts: dict = field(default_factory=dict)    # instruction -> n
    call_seconds: dict = field(default_factory=dict)   # instruction -> s
    gaps: list = field(default_factory=list)           # [(host, seconds)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def find_xplane(tdir) -> str:
    files = glob.glob(os.path.join(str(tdir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return max(files, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_events(plane):
    """The events of a device's op line (``XLA Ops``; asynchronous copies
    and module spans live on lines of their own)."""
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" in lines:
        return list(lines["XLA Ops"].events)
    return [e for name, ln in lines.items()
            if "Module" not in name and "Step" not in name
            and "Async" not in name for e in ln.events]


def instruction(event_name: str) -> str:
    """The HLO instruction name of an op event: the trace names an op by
    its instruction's text, ``%name = shape opcode(...)``."""
    m = re.match(r"\s*%?([\w.\-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def _leaves_and_self(events):
    """Events that contain no other event (a ``while`` op spans its body),
    and each event's self time (its duration less its children's)."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    self_ns = {id(e): e.duration_ns for e in order}
    parent_of, stack = {}, []
    for e in order:
        while stack and e.end_ns > stack[-1].end_ns:   # not inside the top
            stack.pop()
        if stack:
            parent = stack[-1]
            parent_of[id(parent)] = True
            self_ns[id(parent)] -= e.duration_ns
        stack.append(e)
    leaves = [e for e in order if id(e) not in parent_of]
    return leaves, self_ns


def reduce(path: str, kernels=(), calls: dict | None = None,
           window_s: float | None = None) -> Summary:
    """Summarise the trace at ``path``.

    ``calls`` maps the compiled program's kernel-call instruction names to
    their kernel; ``window_s`` is the traced window's length (else the
    trace's own start and stop).  Busy time is the union of the leaf ops'
    intervals, so idle time inside a device loop counts as idle.
    """
    from jax.profiler import ProfileData
    calls = calls or {}
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    devices = [p for p in planes if _DEVICE.match(p.name)]
    busy, op_s, ker_s, n_call, s_call = [], {}, {}, {}, {}
    all_busy = []
    for plane in devices:
        events = _op_events(plane)
        if not events:
            continue
        leaves, self_ns = _leaves_and_self(events)
        merged = _union((e.start_ns, e.end_ns) for e in leaves)
        all_busy.append(merged)
        busy.append(sum(e - s for s, e in merged))
        for e in events:
            inst = instruction(e.name)
            op_s[inst] = op_s.get(inst, 0.0) + self_ns[id(e)] / 1e9
            secs = e.duration_ns / 1e9
            kernel = calls[inst]["kernel"] if inst in calls else next(
                (k for k in kernels if k in inst), None)
            if kernel is not None:
                ker_s[kernel] = ker_s.get(kernel, 0.0) + secs
            if inst in calls:
                n_call[inst] = n_call.get(inst, 0) + 1
                s_call[inst] = s_call.get(inst, 0.0) + secs
    if not busy:
        raise ValueError(f"no device operation in {path}")
    start = stop = None
    for p in planes:
        for k, v in p.stats:
            if k == "profile_start_time":
                start = int(v)
            elif k == "profile_stop_time":
                stop = int(v)
    # event times count from the profile's start
    length_ns = None if start is None or stop is None else stop - start
    if window_s is None:
        if length_ns is None:
            lo = min(m[0][0] for m in all_busy)
            hi = max(m[-1][1] for m in all_busy)
            window_s = (hi - lo) / 1e9
        else:
            window_s = length_ns / 1e9
    gaps = _idle_gaps(all_busy[0], planes, length_ns)
    return Summary(busy_s=sum(busy) / len(busy) / 1e9, window_s=window_s,
                   op_seconds=op_s, kernel_seconds=ker_s,
                   call_counts=n_call, call_seconds=s_call, gaps=gaps)


def _idle_gaps(merged, planes, length_ns, top: int = 10):
    """The longest idle gaps of one device between its first and last op
    (and to the profile's end), each named by the host event that covers
    most of it (the shortest such, so the most specific)."""
    gaps = []
    prev_end = merged[0][0]
    for s, e in merged:
        if s > prev_end:
            gaps.append((prev_end, s))
        prev_end = max(prev_end, e)
    if length_ns is not None and length_ns > prev_end:
        gaps.append((prev_end, length_ns))
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:top]
    host = [e for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    out = []
    for g0, g1 in gaps:
        best, best_key = "no host event", None
        for e in host:
            ov = min(e.end_ns, g1) - max(e.start_ns, g0)
            if ov <= 0:
                continue
            covers = ov >= 0.5 * (g1 - g0)
            key = (covers, -e.duration_ns if covers else ov)
            if best_key is None or key > best_key:
                best, best_key = e.name, key
        out.append((best, (g1 - g0) / 1e9))
    return out
