"""Every number the benchmark takes from a profiler trace.

``summarize`` reads one ``.xplane.pb`` and reduces it, for a window
given by a host span, to:

* per device, the busy time: the union of the intervals in which an
  operation ran on it (line ``XLA Ops`` of each ``/device:TPU:<n>``
  plane), clipped to the window;
* the time by operation, named ``<program>/<op>``, summed over devices;
* the time in each kind of collective (``collective-permute``,
  ``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all``),
  per device;
* the idle gaps of device 0 inside the window, each charged to the host
  span of the benchmark (``bench.*``) that covers most of it.

Nothing here imports the program or JAX beyond the trace reader.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all")


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


@dataclass
class Summary:
    window_ns: float
    busy_ns: dict[str, float]                  # device plane -> busy
    op_ns: dict[str, float]                    # op name -> time, all devices
    collective_ns: dict[str, dict[str, float]]  # device -> kind -> time
    idle_by_host: dict[str, float]             # host span -> idle ns, dev 0
    host_span_counts: dict[str, int] = field(default_factory=dict)

    @property
    def devices(self) -> list[str]:
        return sorted(self.busy_ns)

    @property
    def busy_s_mean(self) -> float:
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def top_ops(self, k: int = 10) -> list[list]:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def top_idle(self, k: int = 10) -> list[list]:
        top = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the op
    events of a TPU trace are named by their whole HLO instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def is_collective(name: str, kind: str) -> bool:
    """Whether op ``name`` (as ``op_name`` gives it) is of ``kind``."""
    return name.startswith(kind)


def _label(modules, start: float, name: str) -> str:
    """``<program>/<op>``: the program is the XLA module whose run on the
    device covers the op's start."""
    j = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if j >= 0 and modules[j][0] <= start < modules[j][1]:
        return f"{modules[j][2]}/{name}"
    return name


def _cover(spans, a: float, b: float) -> str:
    """Name of the span in ``spans`` (sorted ``(start, end, name)``) that
    covers most of ``[a, b]``."""
    longest = max((e - s for s, e, _ in spans), default=0.0)
    j = bisect.bisect_left(spans, (b,))
    best, cover = "no bench span", 0.0
    while j > 0:
        j -= 1
        s, e, n = spans[j]
        if s + longest < a:
            break
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = n, c
    return best


def summarize(path: str) -> Summary:
    """Reduce the trace at ``path`` over the window of its
    ``bench.traced`` host span (the whole trace if it has none)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans: list[tuple[str, float, float]] = []
    dev_ops: dict[str, list[tuple[str, float, float]]] = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans.extend(ev for ev in _events(line)
                                  if ev[0].startswith(SPAN_PREFIX))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(n), s, e) for n, s, e in _events(line))
                elif line.name == MODULES_LINE:
                    modules.extend((s, e, n) for n, s, e in _events(line))
            modules.sort()
            dev_ops[plane.name] = [(_label(modules, s, n), n, s, e)
                                   for n, s, e in ops]
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        every = [ev for ops in dev_ops.values() for ev in ops]
        lo = min((s for _, _, s, _ in every), default=0.0)
        hi = max((e for _, _, _, e in every), default=0.0)
    busy, coll, op_ns = {}, {}, {}
    for dev, ops in dev_ops.items():
        busy[dev] = union_ns([(s, e) for _, _, s, e in ops], lo, hi)
        coll[dev] = {c: union_ns([(s, e) for _, n, s, e in ops
                                  if is_collective(n, c)], lo, hi)
                     for c in COLLECTIVES}
        for label, _, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[label] = op_ns.get(label, 0.0) + d
    spans = sorted((s, e, n) for n, s, e in host_spans if n != WINDOW_SPAN)
    counts: dict[str, int] = {}
    for s, e, n in spans:
        if lo <= s < hi:
            counts[n] = counts.get(n, 0) + 1
    idle: dict[str, float] = {}
    if dev_ops:
        first = sorted(dev_ops)[0]
        for a, b in gaps_ns([(s, e) for _, _, s, e in dev_ops[first]],
                            lo, hi):
            name = _cover(spans, a, b)
            idle[name] = idle.get(name, 0.0) + (b - a)
    return Summary(window_ns=hi - lo, busy_ns=busy, op_ns=op_ns,
                   collective_ns=coll, idle_by_host=idle,
                   host_span_counts=counts)
