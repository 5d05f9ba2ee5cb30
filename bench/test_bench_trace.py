"""The trace reduction (bench/trace.py) on hand-made intervals and on a
trace recorded on the chip."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace as xt

TESTDATA = Path(__file__).resolve().parent / "testdata"


def test_union_merges_overlaps_and_drops_empty():
    assert xt.union([(5, 7), (0, 2), (1, 3), (9, 9), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_subtract_leaves_the_uncovered_parts():
    assert xt.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]
    assert xt.subtract([(0, 5)], []) == [(0, 5)]


def test_clip_and_length():
    assert xt.clip([(0, 10), (12, 14), (20, 30)], 5, 25) == [
        (5, 10), (12, 14), (20, 25)]
    assert xt.length([(0, 2), (5, 9)]) == 6


def test_flatten_names_each_time_by_its_innermost_span():
    spans = [("window", 0, 100), ("job", 10, 60), ("update", 10, 30),
             ("update", 30, 50), ("finalize", 50, 60), ("job", 60, 90)]
    assert xt.flatten(spans) == [
        (0, 10, "window"), (10, 30, "update"), (30, 50, "update"),
        (50, 60, "finalize"), (60, 90, "job"), (90, 100, "window")]


def test_attribute_splits_gaps_across_spans():
    segs = [(0, 10, "a"), (10, 20, "b")]
    assert xt.attribute([(5, 15), (18, 25)], segs) == {
        "a": 5, "b": 7, "untracked": 5}


PLAN = ("%make_partition_plan.51 = s32[40,1024]{1,0:T(8,128)S(1)} "
        "custom-call(s32[40,1024]{1,0:T(8,128)S(1)} %pad.222, "
        "s32[40,384]{1,0:T(8,128)S(1)} %pad.223), custom_call_target="
        "\"tpu_custom_call\", operand_layout_constraints={s32[40,1024]{1,0}, "
        "s32[40,384]{1,0}}")


@pytest.mark.parametrize("name,base", [
    ("fusion.12", "fusion"), ("all-to-all.3", "all-to-all"),
    ("hash_insert", "hash_insert"), ("jit_f(3)", "jit_f"),
    ("copy.1.2", "copy"), (PLAN, "make_partition_plan"),
    ("%while.5 = (s32[]) while((s32[]) %tuple.87)", "while"),
    ("%hash_insert.10 = (s32[16384,128]) custom-call(s32[8])",
     "hash_insert")])
def test_base_name_drops_numeric_suffixes(name, base):
    assert xt.base_name(name) == base


def test_hlo_io_bytes_counts_results_and_operands():
    # result s32[40,1024], operands s32[40,1024] and s32[40,384]; the
    # layout constraints after the target are not shapes moved
    assert xt.hlo_io_bytes(PLAN) == 4 * (40 * 1024 * 2 + 40 * 384)
    assert xt.hlo_io_bytes("%x = (u8[3,5], bf16[2]) custom-call(pred[7])") \
        == 15 + 4 + 7


def test_container_ops_are_left_out():
    ops = [("%while.1 = (s32[]) while()", 0, 100),
           ("%hash_insert.2 = s32[8] custom-call(s32[8])", 10, 30),
           ("%fusion.3 = u32[8] fusion()", 50, 60)]
    s = xt.reduce(xt.Trace([xt.Device("/device:TPU:0", ops, [])],
                           [("window", 0, 100)]))
    assert s.busy_s == pytest.approx(30e-9)
    assert "while" not in s.op_s
    assert s.kernel_io_bytes("hash_insert") == 64
    assert s.kernel_io_bytes("fusion") == 0


def _two_chips():
    ops0 = [("hash_insert", 10, 40), ("fusion.1", 40, 50),
            ("all-to-all.1", 50, 70), ("fusion.2", 60, 65)]
    ops1 = [("hash_insert.2", 10, 30), ("all-to-all.1", 50, 80)]
    mods = [("jit_local_finalize(7)", 10, 50)]
    spans = [("window", 0, 100), ("update", 0, 45), ("finalize", 45, 100)]
    return xt.Trace([xt.Device("/device:TPU:0", ops0, mods),
                     xt.Device("/device:TPU:1", ops1, [])], spans)


def test_reduce_two_chips():
    s = xt.reduce(_two_chips())
    ns = 1e-9
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(100 * ns)
    # busy: chip 0 covers [10, 70) = 60, chip 1 [10, 30) + [50, 80) = 50
    assert s.busy_s == pytest.approx(55 * ns)
    assert s.idle_share == pytest.approx(0.45)
    assert s.kernel_s("hash_insert") == pytest.approx(50 * ns)
    assert s.kernel_calls("hash_insert") == 2
    assert s.kernel_calls("all-to-all") == 2
    assert s.module_time_s("local_finalize") == pytest.approx(40 * ns)
    assert s.module_count("local_finalize") == 1
    idle = dict(s.idle_by_span)
    # chip 0 idle [0, 10) update, [70, 100) finalize; chip 1 [0, 10)
    # update, [30, 45) update + [45, 50) finalize, [80, 100) finalize
    assert idle["update"] == pytest.approx((10 + 10 + 15) / 2 * ns)
    assert idle["finalize"] == pytest.approx((30 + 5 + 20) / 2 * ns)
    b = xt.breakdown(s)
    assert b["device_ops"][0][0] == "hash_insert"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_clips_ops_to_the_window():
    t = xt.Trace([xt.Device("/device:TPU:0", [("a", 0, 50), ("b", 60, 200)],
                            [])], [("window", 20, 100)])
    s = xt.reduce(t)
    assert s.window_s == pytest.approx(80e-9)
    assert s.busy_s == pytest.approx(70e-9)
    assert s.op_s["a"] == pytest.approx(30e-9)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        xt.reduce(xt.Trace([], [("window", 0, 1)]))


# --- a small trace recorded on the chip (TPU v5 lite): one count job of
# 2,048 reads in 4 batches, and 0.34 s of serving at 2,000 requests/s ----


def _recorded(name, tmp_path):
    path = tmp_path / f"{name}.xplane.pb"
    with gzip.open(TESTDATA / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xt.reduce(xt.load(path))


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", TESTDATA.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def count_trace(tmp_path_factory):
    return _recorded("count", tmp_path_factory.mktemp("count"))


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    return _recorded("serve", tmp_path_factory.mktemp("serve"))


def test_recorded_count_trace_window_and_busy(count_trace):
    s = count_trace
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.117482237, rel=1e-9)
    assert s.busy_s == pytest.approx(0.089301895, rel=1e-9)
    assert s.idle_share == pytest.approx(0.23986895993476864, rel=1e-9)


def test_recorded_count_trace_kernels(count_trace):
    s = count_trace
    assert s.kernel_calls("hash_insert") == 8
    assert s.kernel_s("hash_insert") == pytest.approx(0.065546395, rel=1e-9)
    assert s.kernel_calls("make_partition_plan") == 104
    assert s.kernel_s("make_partition_plan") == pytest.approx(0.00716591,
                                                              rel=1e-9)
    assert s.kernel_io_bytes("make_partition_plan") == 35520512
    assert s.module_count("local_finalize") == 1
    assert s.module_time_s("local_finalize") == pytest.approx(
        0.004215381, rel=1e-9)
    assert s.module_count("local_update") == 4
    assert [n for n, _ in s.top_ops[:3]] == [
        "hash_insert", "fusion", "make_partition_plan"]
    assert s.idle_by_span[0][0] == "update"
    assert s.idle_by_span[0][1] == pytest.approx(0.026740926, rel=1e-9)


def test_recorded_count_trace_metrics(count_trace):
    from types import SimpleNamespace
    ctx = SimpleNamespace(trace=count_trace, peaks=PEAKS,
                          counters={"jobs": 1, "sent_words": 1_000_000})
    assert _reader("count.idle_share")(ctx) == pytest.approx(
        23.986895993476864, rel=1e-9)
    assert _reader("hash_insert_roofline")(ctx) == pytest.approx(
        100 * 24e6 / 819e9 / 0.065546395, rel=1e-9)
    assert _reader("make_partition_plan_roofline")(ctx) == pytest.approx(
        100 * 35520512 / 819e9 / 0.00716591, rel=1e-9)
    assert _reader("finalize.device_ms")(ctx) == pytest.approx(
        4.215381, rel=1e-9)


def test_recorded_serve_trace(serve_trace):
    from types import SimpleNamespace
    s = serve_trace
    assert s.window_s == pytest.approx(0.33670039, rel=1e-9)
    assert s.busy_s == pytest.approx(0.039745653, rel=1e-9)
    assert s.kernel_calls("hash_lookup") == 51
    assert s.kernel_s("hash_lookup") == pytest.approx(0.036153935, rel=1e-9)
    assert s.kernel_calls("hash_insert") == 0
    assert s.idle_by_span[0][0] == "flush"
    ctx = SimpleNamespace(trace=s, peaks=PEAKS,
                          counters={"live_queries": 400 * 136,
                                    "flush_s": [0.004, 0.006, 0.009]})
    assert _reader("serve.idle_share")(ctx) == pytest.approx(
        100 * (1 - 0.039745653 / 0.33670039), rel=1e-9)
    assert _reader("hash_lookup_roofline")(ctx) == pytest.approx(
        100 * 16 * 400 * 136 / 819e9 / 0.036153935, rel=1e-9)
    assert _reader("serve.flush_ms")(ctx) == pytest.approx(6.0)
    # no counting job ran: the count readers find nothing to read
    assert _reader("finalize.device_ms")(ctx) is None
    assert _reader("hash_insert_roofline")(ctx) is None
