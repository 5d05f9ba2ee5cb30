"""The program's own layers in a profiler trace: device time by layer scope,
host time by program span.

The program marks its layers itself (src/repro). Each jitted body wraps
its layers in `jax.named_scope`: `extract`, `l3`, `route` and `insert` in
`local_update`, `route` and `lookup` in `local_query`, `finalize` in
`local_finalize`. XLA keeps the scope path in each op's metadata, and the
profiler writes it as the `tf_op` stat of the op's event metadata on the
device plane (`jit(local_query)/lookup/jit(hash_lookup)/pallas_call:`).
The host side of `KmerCounter` and `QueryService` opens
`jax.profiler.TraceAnnotation` spans (`kc.*`, `serve.*`, `query.*`) on the
same clock as the device events.

`jax.profiler.ProfileData`, which bench/trace.py reads, exposes only an
event's own stats, not its metadata's, so this module reads the raw
`.xplane.pb` through the protobuf runtime with a schema of the few fields
it needs (no TensorFlow). It adds to bench/trace.py's reduction and
changes none of it: the window, the ops left out and the interval
arithmetic are that module's. In a trace of a program without scopes or
spans every op is outside a scope and no program span is found, and the
metric readers find nothing to read.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import statistics
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as xt

# device layers (`jax.named_scope` in the jitted bodies)
SCOPES = ("extract", "l3", "route", "insert", "lookup", "finalize")
# host spans (`TraceAnnotation` in KmerCounter and QueryService)
PROGRAM_SPANS = ("kc.update", "kc.plan", "kc.grow", "kc.run", "kc.sync",
                 "kc.commit", "kc.finalize", "serve.flush", "serve.coalesce",
                 "query.pack", "query.put", "query.run", "query.fetch",
                 "serve.split")
# where bench/run.py's traced run leaves its trace while metrics are read
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"


@functools.lru_cache(maxsize=None)
def _space_class():
    """The XSpace message class for the fields read here, numbered as in
    tsl's xplane.proto; every other field is skipped as unknown."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto2")
    i64, s, msg = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_MESSAGE

    def message(name, fields, into=None):
        m = (fdp.message_type if into is None else into).add(name=name)
        for fname, num, kind, ref in fields:
            many = ref is not None and ref.startswith("*")
            f = m.field.add(name=fname, number=num, type=kind,
                            label=F.LABEL_REPEATED if many
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_xplane." + ref.lstrip("*")
        return m

    message("XStat", [("metadata_id", 1, i64, None),
                      ("str_value", 5, s, None)])
    message("XEvent", [("metadata_id", 1, i64, None),
                       ("offset_ps", 2, i64, None),
                       ("duration_ps", 3, i64, None)])
    message("XLine", [("name", 2, s, None), ("timestamp_ns", 3, i64, None),
                      ("events", 4, msg, "*XEvent")])
    message("XEventMetadata", [("name", 2, s, None),
                               ("stats", 5, msg, "*XStat")])
    message("XStatMetadata", [("name", 2, s, None)])
    plane = message("XPlane", [
        ("name", 2, s, None), ("lines", 3, msg, "*XLine"),
        ("event_metadata", 4, msg, "*XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, "*XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, i64, None), ("value", 2, msg, value)],
                    into=plane.nested_type)
        e.options.map_entry = True
    message("XSpace", [("planes", 1, msg, "*XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_space(path: Path):
    """The XSpace of one `.xplane.pb`, gzipped or not."""
    raw = Path(path).read_bytes()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    space = _space_class()()
    space.ParseFromString(raw)
    return space


@dataclasses.dataclass
class Layers:
    """A trace's device time by (module, tf_op path) and its host spans,
    all within the harness's window."""
    window: xt.Interval
    n_devices: int
    op_ns: Dict[Tuple[str, str], int]   # (module, tf_op) -> ns, all devices
    spans: List[Tuple[str, int, int]]   # program and harness spans
    idle_gaps: List[List[xt.Interval]]  # per device, sorted and disjoint

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def path_s(self, component: str, module: Optional[str] = None) -> float:
        """Device seconds (per device) of the ops whose `tf_op` path holds
        `component` (a layer scope or a `jit(...)` name), in `module` (an
        executable's name, with or without its `jit_`) if given."""
        return 1e-9 * sum(
            ns for (mod, op), ns in self.op_ns.items()
            if (module is None or mod in (module, "jit_" + module))
            and component in op.split("/")) / self.n_devices

    def module_s(self, module: Optional[str] = None) -> float:
        """Device op seconds (per device) in `module`, or in all."""
        return 1e-9 * sum(ns for (mod, _), ns in self.op_ns.items()
                          if module is None
                          or mod in (module, "jit_" + module)
                          ) / self.n_devices

    def span_s(self, name: str) -> List[float]:
        """Durations in seconds of the spans named `name`, in time order."""
        return [(e - s) * 1e-9 for n, s, e in self.spans if n == name]

    def idle_within_s(self, name: str) -> float:
        """Device idle seconds (per device) inside the spans named `name`,
        at any depth."""
        cover = xt.union([(s, e) for n, s, e in self.spans if n == name])
        return 1e-9 * sum(xt.length(_intersect(g, cover))
                          for g in self.idle_gaps) / self.n_devices

    def idle_by_span(self) -> Dict[str, float]:
        """Device idle seconds (per device) by the innermost program or
        harness span it fell in ('untracked' where none)."""
        segs = xt.flatten(self.spans)
        out: Dict[str, float] = {}
        for gaps in self.idle_gaps:
            for name, ns in xt.attribute(gaps, segs).items():
                out[name] = out.get(name, 0.0) + 1e-9 * ns / self.n_devices
        return out


def _intersect(a: Sequence[xt.Interval], b: Sequence[xt.Interval]
               ) -> List[xt.Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _events(line, names, tf_ops=None):
    """(name, tf_op, start_ns, end_ns) of each event on `line`."""
    t0 = line.timestamp_ns
    for ev in line.events:
        yield (names.get(ev.metadata_id, ""),
               "" if tf_ops is None else tf_ops.get(ev.metadata_id, ""),
               int(t0 + ev.offset_ps / 1000),
               int(t0 + (ev.offset_ps + ev.duration_ps) / 1000))


def load(path: Path) -> Layers:
    """Read one `.xplane.pb` (or `.xplane.pb.gz`) into `Layers`."""
    wanted = set(xt.SPANS) | set(PROGRAM_SPANS)
    devices, spans = [], []
    for plane in read_space(path).planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        if xt._is_device_plane(plane.name):
            tf_op = {k for k, v in plane.stat_metadata.items()
                     if v.name == "tf_op"}
            tf_ops = {k: next((st.str_value for st in v.stats
                               if st.metadata_id in tf_op), "")
                      for k, v in plane.event_metadata.items()}
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                mods = lines.get("XLA Modules")
                devices.append((
                    plane.name,
                    list(_events(lines["XLA Ops"], names, tf_ops)),
                    [] if mods is None else list(_events(mods, names))))
        elif plane.name.startswith("/host:"):
            spans += [(n, s, e) for line in plane.lines
                      for n, _, s, e in _events(line, names)
                      if n in wanted]
    if not devices:
        raise ValueError("the trace holds no device ops")
    devices.sort(key=lambda d: d[0])
    lo, hi = xt.window_of(xt.Trace(
        [xt.Device(n, [(o, s, e) for o, _, s, e in ops], [])
         for n, ops, _ in devices],
        [sp for sp in spans if sp[0] in xt.SPANS]))
    op_ns: Dict[Tuple[str, str], int] = {}
    gaps = []
    for _, ops, mods in devices:
        mods = sorted((s, e, xt.base_name(n)) for n, _, s, e in mods)
        starts = [m[0] for m in mods]
        kept = []
        for name, tf_op, s, e in ops:
            if e <= lo or s >= hi or xt.base_name(name) in xt.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            key = (mods[i][2] if i >= 0 and s < mods[i][1] else "", tf_op)
            op_ns[key] = op_ns.get(key, 0) + min(e, hi) - max(s, lo)
            kept.append((s, e))
        gaps.append(xt.subtract([(lo, hi)], xt.union(xt.clip(kept, lo, hi))))
    spans = sorted((sp for sp in spans if sp[2] > lo and sp[1] < hi),
                   key=lambda sp: (sp[1], -sp[2]))
    return Layers((lo, hi), len(devices), op_ns, spans, gaps)


@functools.lru_cache(maxsize=2)
def _load_cached(path: str, mtime_ns: int, size: int) -> Layers:
    return load(Path(path))


def of(ctx) -> Optional[Layers]:
    """The `Layers` of the traced run a metric reader is reading: the trace
    bench/run.py left in `TRACE_DIR`, if its window is the one the run's
    `ctx.trace` summary reduced (else None, as without a trace)."""
    if getattr(ctx, "trace", None) is None:
        return None
    try:
        path = xt.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    st = path.stat()
    layers = _load_cached(str(path), st.st_mtime_ns, st.st_size)
    if abs(layers.window_s - ctx.trace.window_s) > 1e-6:
        return None
    return layers


def per_job_scope_ms(ctx, scope: str, module: str = "local_update"
                     ) -> Optional[float]:
    """Device milliseconds (per chip) of layer scope `scope` in `module`
    per counting job of the window; None where the trace or the scope is
    absent."""
    layers = of(ctx)
    jobs = ctx.counters.get("jobs") if layers is not None else None
    if not jobs:
        return None
    t = layers.path_s(scope, module)
    return 1e3 * t / jobs if t > 0 else None


def span_median_ms(ctx, name: str) -> Optional[float]:
    """Median duration in milliseconds of program span `name` in the
    window; None where the trace or the span is absent."""
    layers = of(ctx)
    times = [] if layers is None else layers.span_s(name)
    return 1e3 * statistics.median(times) if times else None
