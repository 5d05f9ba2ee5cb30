"""The program-layer reduction (bench/scopes.py): device time by the `tf_op`
path of each op, host time by program span, on hand-made intervals and on
traces recorded on the chip."""

import gzip
import importlib.util
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import scopes, trace as xt

TESTDATA = Path(__file__).resolve().parent / "testdata"
NEW_METRICS = {
    "count": ("extract.device_ms", "l3.device_ms", "route.device_ms",
              "insert.device_ms"),
    "serve": ("query.pack_ms", "query.put_ms", "query.fetch_ms",
              "serve.flush_idle_ms"),
}
ALL_NEW = NEW_METRICS["count"] + NEW_METRICS["serve"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", TESTDATA.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_intersect_keeps_the_common_parts():
    assert scopes._intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == [
        (5, 10), (20, 25), (28, 30)]
    assert scopes._intersect([(0, 5)], []) == []


def _hand_made():
    ops = {("jit_local_update", "jit(local_update)/while/body/insert/a:"): 30,
           ("jit_local_update", "jit(local_update)/while/body/route/b:"): 10,
           ("jit_local_update", "jit(local_update)/jit(insert_rows)/c:"): 4,
           ("jit_local_update", ""): 5,
           ("jit_local_query", "jit(local_query)/route/d:"): 7}
    spans = [("window", 0, 100), ("serve.flush", 10, 40),
             ("query.fetch", 20, 40), ("serve.flush", 60, 70)]
    gaps = [[(0, 15), (30, 65), (90, 100)]]
    return scopes.Layers((0, 100), 1, ops, spans, gaps)


def test_layers_sums_by_path_component_and_module():
    lay = _hand_made()
    ns = 1e-9
    # a component matches whole: jit(insert_rows) is not the insert layer
    assert lay.path_s("insert") == pytest.approx(30 * ns)
    assert lay.path_s("route") == pytest.approx(17 * ns)
    assert lay.path_s("route", "local_update") == pytest.approx(10 * ns)
    assert lay.path_s("route", "jit_local_query") == pytest.approx(7 * ns)
    assert lay.path_s("jit(insert_rows)") == pytest.approx(4 * ns)
    assert lay.module_s("local_update") == pytest.approx(49 * ns)
    assert lay.module_s() == pytest.approx(56 * ns)


def test_layers_idle_inside_spans_and_by_innermost_span():
    lay = _hand_made()
    ns = 1e-9
    # idle [0,15) [30,65) [90,100); serve.flush covers [10,40) and [60,70)
    assert lay.idle_within_s("serve.flush") == pytest.approx(
        (5 + 10 + 5) * ns)
    assert lay.span_s("serve.flush") == pytest.approx([30 * ns, 10 * ns])
    idle = lay.idle_by_span()
    assert idle["serve.flush"] == pytest.approx((5 + 5) * ns)
    assert idle["query.fetch"] == pytest.approx(10 * ns)
    assert idle["window"] == pytest.approx((10 + 20 + 10) * ns)


# --- traces recorded on the chip (TPU v5 lite) -------------------------------
# count.xplane.pb.gz and serve.xplane.pb.gz come from a program without layer
# scopes or program spans (one count job of 2,048 reads in 4 batches, and
# 0.34 s of serving at 2,000 requests/s); the *_scoped ones, further down,
# from the program that marks its layers.

def _unpacked(name, tmp_path):
    path = tmp_path / f"{name}.xplane.pb"
    with gzip.open(TESTDATA / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """name -> (trace dir, Layers, bench/trace.py Summary)."""
    out = {}
    for name in ("count", "serve"):
        d = tmp_path_factory.mktemp(name)
        path = _unpacked(name, d)
        out[name] = (d, scopes.load(path), xt.reduce(xt.load(path)))
    return out


@pytest.mark.parametrize("name,component,seconds,total", [
    # the insert kernel's executable: 65.548 of 89.302 ms of op time
    ("count", "jit(hash_insert)", 0.065547951, 0.089302463),
    # the lookup kernel's executable, with the small ops around the
    # kernel call: 36.197 of 39.747 ms (the kernel's own events alone,
    # bench/trace.py's kernel_s, are 36.154 ms)
    ("serve", "jit(hash_lookup)", 0.036196753, 0.039746637),
    # L3's local radix sort, told from the route's scatter by its path
    ("count", "jit(radix_sort)", 0.010641223, 0.089302463)])
def test_recorded_trace_op_paths(recorded, name, component, seconds, total):
    lay = recorded[name][1]
    assert lay.path_s(component) == pytest.approx(seconds, rel=1e-9)
    assert lay.module_s() == pytest.approx(total, rel=1e-9)


@pytest.mark.parametrize("name", ["count", "serve"])
def test_recorded_trace_agrees_with_the_reduction(recorded, name):
    """Window, op time and idle by harness span read alike through the raw
    protobuf and through ProfileData (to a nanosecond's rounding per
    event)."""
    _, lay, summary = recorded[name]
    assert lay.window_s == pytest.approx(summary.window_s, abs=1e-9)
    assert lay.n_devices == summary.n_devices
    assert lay.module_s() == pytest.approx(
        sum(summary.op_s.values()) / summary.n_devices, abs=1e-6)
    idle = lay.idle_by_span()
    for span, s in summary.idle_by_span:
        assert idle[span] == pytest.approx(s, abs=2e-6)


@pytest.mark.parametrize("name", ["count", "serve"])
@pytest.mark.parametrize("metric", ALL_NEW)
def test_new_readers_find_nothing_without_scopes_and_spans(
        recorded, monkeypatch, name, metric):
    """A program that marks no layer scopes or spans reads as nothing,
    not as zero and not as an error."""
    d, _, summary = recorded[name]
    monkeypatch.setattr(scopes, "TRACE_DIR", d)
    ctx = SimpleNamespace(trace=summary, peaks={}, counters={
        "jobs": 1, "sent_words": 1, "live_queries": 1, "flush_s": [0.01]})
    assert _reader(metric)(ctx) is None


def test_readers_skip_a_trace_of_another_run(recorded, monkeypatch):
    """A trace whose window is not the run's summary's is not read."""
    d, _, summary = recorded["count"]
    monkeypatch.setattr(scopes, "TRACE_DIR", d)
    other = SimpleNamespace(window_s=summary.window_s + 1e-3)
    assert scopes.of(SimpleNamespace(trace=other)) is None
    assert scopes.of(SimpleNamespace(trace=summary)) is not None
    assert scopes.of(SimpleNamespace(trace=None)) is None
    monkeypatch.setattr(scopes, "TRACE_DIR", d / "empty")
    assert scopes.of(SimpleNamespace(trace=summary)) is None


# --- traces of the program that marks its layers ---------------------------
# count_scoped: one count job of 2,048 reads in 4 batches into a 2^19-slot
# store; serve_scoped: 0.3 s of serving at 2,000 requests/s from a store
# counted from 2,048 reads (TPU v5 lite, after set-up's warm-up)

READ = {
    "count": {"extract.device_ms": 0.011207, "l3.device_ms": 13.606779,
              "route.device_ms": 5.844683, "insert.device_ms": 95.655302},
    "serve": {"query.pack_ms": 1.54874, "query.put_ms": 0.40763,
              "query.fetch_ms": 3.890169,
              "serve.flush_idle_ms": 5.634475555555556},
}
COUNTERS = {"count": {"jobs": 1}, "serve": {"flush_s": [0.01]}}


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """name -> (trace dir, Layers, Summary, bench/trace.py Trace)."""
    out = {}
    for name in ("count", "serve"):
        d = tmp_path_factory.mktemp(f"{name}_scoped")
        path = _unpacked(f"{name}_scoped", d)
        raw = xt.load(path)
        out[name] = (d, scopes.load(path), xt.reduce(raw), raw)
    return out


def test_scoped_count_trace_splits_the_update_into_layers(scoped):
    lay = scoped["count"][1]
    update = lay.module_s("local_update")
    layers = {s: lay.path_s(s, "local_update")
              for s in ("extract", "l3", "route", "insert")}
    assert sum(layers.values()) >= 0.95 * update
    assert lay.path_s("finalize", "local_finalize") >= 0.95 * lay.module_s(
        "local_finalize")
    # the insert kernel's executable runs inside the insert layer
    assert lay.path_s("jit(hash_insert)") == pytest.approx(
        lay.path_s("jit(hash_insert)", "local_update"))
    assert lay.path_s("jit(hash_insert)") <= layers["insert"]


def test_scoped_serve_trace_shares_the_device_clock(scoped):
    """Each run of the query executable starts after its flush's
    `query.run` span starts and ends before its `query.fetch` span ends:
    host spans and device events are on one clock."""
    _, lay, _, raw = scoped["serve"]
    runs = [(s, e) for n, s, e in raw.devices[0].modules
            if xt.base_name(n) == "jit_local_query"]
    flushes = [(s, e) for n, s, e in lay.spans if n == "serve.flush"]
    assert len(runs) == len(flushes) > 0

    def within(name, lo, hi):
        found = [(s, e) for n, s, e in lay.spans
                 if n == name and lo <= s and e <= hi]
        assert len(found) == 1
        return found[0]

    for s, e in runs:
        (lo, hi), = [f for f in flushes if f[0] <= s < f[1]]
        assert within("query.run", lo, hi)[0] <= s
        assert e <= within("query.fetch", lo, hi)[1]


def test_scoped_serve_trace_names_the_idle_inside_flushes(scoped):
    """Nearly all device idle inside the harness's `flush` spans falls in
    a program span: the host work of a flush has names."""
    lay = scoped["serve"][1]
    idle = lay.idle_by_span()
    in_flush = lay.idle_within_s("flush")
    named = sum(v for k, v in idle.items() if k.split(".")[0] in (
        "serve", "query"))
    assert named >= 0.95 * in_flush
    assert lay.path_s("lookup", "local_query") >= 0.9 * lay.module_s(
        "local_query")
    # the fetch waits for the lookup: the longest host step of a flush
    fetch = sorted(lay.span_s("query.fetch"))
    assert fetch[len(fetch) // 2] > max(
        sorted(lay.span_s(n))[len(fetch) // 2]
        for n in ("query.pack", "query.put"))


@pytest.mark.parametrize("name", ["count", "serve"])
@pytest.mark.parametrize("metric", ALL_NEW)
def test_scoped_trace_metrics(scoped, monkeypatch, name, metric):
    """Each new reader reads its value in its own cell's trace and nothing
    in the other cell's."""
    d, _, summary, _ = scoped[name]
    monkeypatch.setattr(scopes, "TRACE_DIR", d)
    got = _reader(metric)(SimpleNamespace(trace=summary, peaks={},
                                          counters=COUNTERS[name]))
    want = READ[name].get(metric)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
