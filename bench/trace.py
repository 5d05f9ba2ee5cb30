"""Reduce a profiler trace (`.xplane.pb`) to the numbers the metrics read.

`load()` reads the trace with `jax.profiler.ProfileData` and keeps three
kinds of intervals, all in nanoseconds on the profiler's one clock:

- device ops: the events of each device plane's "XLA Ops" line, one HLO
  op or Pallas kernel each, named by its HLO text (`%hash_insert.10 =
  (s32[...]) custom-call(...)`); the ops that only hold others (`while`,
  `conditional`, `call`) are left out, so time is leaf ops' time;
- device modules: the events of the "XLA Modules" line (one executable
  run each, named after the jitted function);
- host spans: the harness's own `TraceAnnotation`s, found on any host
  line by the names in `SPANS`.

`reduce()` turns those into a `Summary`: the window (the harness's
`window` span), busy time as the union of op intervals per device, sums
per op name and per module name, the ops that took the most time, and
idle time summed by the host span it fell in. Nothing here knows a cell, a config or a metric.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

# The harness's spans (bench/workload.py); `window` bounds the traced window.
SPANS = ("window", "job", "update", "finalize",
         "submit", "flush", "wait", "record")

Interval = Tuple[int, int]                     # [start_ns, end_ns)


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Tuple[str, int, int]]            # (op name, start, end)
    modules: List[Tuple[str, int, int]]        # (module name, start, end)


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Tuple[str, int, int]]          # harness host spans


@dataclasses.dataclass
class Summary:
    n_devices: int
    window_s: float
    busy_s: float                              # mean over devices
    idle_share: float                          # 1 - busy / window
    op_s: Dict[str, float]                     # op base name -> seconds
    op_calls: Dict[str, int]
    op_bytes: Dict[str, int]                   # custom calls: HLO I/O bytes
    module_s: Dict[str, float]                 # module base name -> s
    module_calls: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_by_span: List[Tuple[str, float]]

    def kernel_s(self, kernel: str) -> float:
        """Summed device seconds of every op named after `kernel`, per
        device (a kernel runs once on each device of a sharded call)."""
        return sum(s for n, s in self.op_s.items() if _is_named(n, kernel))

    def kernel_calls(self, kernel: str) -> int:
        return sum(c for n, c in self.op_calls.items()
                   if _is_named(n, kernel))

    def kernel_io_bytes(self, kernel: str) -> int:
        """Bytes of every operand and result of the kernel's calls, from
        the shapes in their HLO text (padding included)."""
        return sum(b for n, b in self.op_bytes.items()
                   if _is_named(n, kernel))

    def module_time_s(self, fragment: str) -> float:
        return sum(s for n, s in self.module_s.items() if fragment in n)

    def module_count(self, fragment: str) -> int:
        return sum(c for n, c in self.module_calls.items() if fragment in n)


def _is_named(op: str, kernel: str) -> bool:
    return op == kernel or op.startswith(kernel + ".")


_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
CONTAINERS = ("while", "conditional", "call")


def base_name(name: str) -> str:
    """An op or module name without its HLO text and XLA's numeric suffix
    ('%fusion.12 = u32[8] fusion(...)' -> 'fusion', 'jit_f(3)' -> 'jit_f')."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    return _SUFFIX.sub("", name)


def hlo_io_bytes(text: str) -> int:
    """Bytes of the result and operand shapes in one op's HLO text."""
    text = text.split("custom_call_target=", 1)[0]
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES.get(dtype, 4)
    return total


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: Path) -> Trace:
    """Read one `.xplane.pb` into plain intervals."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    wanted = set(SPANS)
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append((e.name, int(e.start_ns), int(e.end_ns)))
            if ops:
                devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
    return Trace(sorted(devices, key=lambda d: d.name), spans)


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of `intervals`."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted `a` that the disjoint sorted `b` leaves
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(trace: Trace) -> Interval:
    """The harness's traced window: its `window` span, else the extent of
    the device ops."""
    wins = [(s, e) for n, s, e in trace.spans if n == "window"]
    if wins:
        return min(s for s, _ in wins), max(e for _, e in wins)
    ops = [(s, e) for d in trace.devices for _, s, e in d.ops]
    return min(s for s, _ in ops), max(e for _, e in ops)


def flatten(spans: Sequence[Tuple[str, int, int]]
            ) -> List[Tuple[int, int, str]]:
    """Disjoint sorted segments, each named by the innermost of the nested
    host spans covering it."""
    segs: List[Tuple[int, int, str]] = []
    stack: List[Tuple[str, int, int]] = []
    pos = 0

    def emit(a: int, b: int, name: str) -> None:
        if b > a:
            segs.append((a, b, name))

    for n, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            emit(pos, top[2], top[0])
            pos = top[2]
        if stack:
            emit(pos, s, stack[-1][0])
        pos = s
        stack.append((n, s, e))
    while stack:
        top = stack.pop()
        emit(pos, top[2], top[0])
        pos = max(pos, top[2])
    return segs


def attribute(gaps: Sequence[Interval], segs: Sequence[Tuple[int, int, str]]
              ) -> Dict[str, int]:
    """Nanoseconds of the sorted disjoint `gaps` that fall in each named
    segment; what no segment covers goes under 'untracked'."""
    out: Dict[str, int] = {}
    j = 0
    for s, e in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b = max(s, segs[k][0]), min(e, segs[k][1])
            if b > a:
                out[segs[k][2]] = out.get(segs[k][2], 0) + (b - a)
                covered += b - a
            k += 1
        if e - s > covered:
            out["untracked"] = out.get("untracked", 0) + (e - s - covered)
    return out


def reduce(trace: Trace, top: int = 10) -> Summary:
    if not trace.devices:
        raise ValueError("the trace holds no device ops")
    lo, hi = window_of(trace)
    n_dev = len(trace.devices)
    op_ns: Dict[str, int] = {}
    op_calls: Dict[str, int] = {}
    op_bytes: Dict[str, int] = {}
    mod_ns: Dict[str, int] = {}
    mod_calls: Dict[str, int] = {}
    busy = 0
    segs = flatten(trace.spans)
    idle: Dict[str, int] = {}
    for dev in trace.devices:
        ops = [(base_name(n), n, s, e) for n, s, e in dev.ops
               if e > lo and s < hi]
        ops = [(b, n, s, e) for b, n, s, e in ops if b not in CONTAINERS]
        for b, n, s, e in ops:
            op_ns[b] = op_ns.get(b, 0) + min(e, hi) - max(s, lo)
            op_calls[b] = op_calls.get(b, 0) + 1
            if "custom-call(" in n:
                op_bytes[b] = op_bytes.get(b, 0) + hlo_io_bytes(n)
        ops = [(b, s, e) for b, _, s, e in ops]
        for n, s, e in dev.modules:
            if e > lo and s < hi:
                b = base_name(n)
                mod_ns[b] = mod_ns.get(b, 0) + min(e, hi) - max(s, lo)
                mod_calls[b] = mod_calls.get(b, 0) + 1
        cover = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy += length(cover)
        for name, ns in attribute(subtract([(lo, hi)], cover), segs).items():
            idle[name] = idle.get(name, 0) + ns
    window_ns = hi - lo
    busy_mean = busy / n_dev
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        n_devices=n_dev,
        window_s=window_ns * 1e-9,
        busy_s=busy_mean * 1e-9,
        idle_share=1.0 - busy_mean / window_ns,
        op_s={k: v * 1e-9 for k, v in op_ns.items()},
        op_calls=op_calls,
        op_bytes=op_bytes,
        module_s={k: v * 1e-9 for k, v in mod_ns.items()},
        module_calls=mod_calls,
        top_ops=[(k, v * 1e-9 / n_dev) for k, v in top_ops],
        idle_by_span=[(k, v * 1e-9 / n_dev) for k, v in top_idle])


def breakdown(summary: Summary) -> Optional[dict]:
    """The result line's `breakdown`: top device ops and idle time by host
    span, seconds per device."""
    return {"device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_by_span]}
