import os
from types import SimpleNamespace

import pytest

from chipbench import bench, tracing

HERE = os.path.dirname(os.path.abspath(__file__))


def test_busy_is_the_union_inside_the_window():
    ivs = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert tracing.busy(ivs, 0.0, 10.0) == pytest.approx(3 + 1 + 1)
    assert tracing.gaps(ivs, 0.0, 10.0) == [(3.0, 5.0), (6.0, 9.0)]
    assert tracing.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_idle_gaps_go_to_the_innermost_open_span():
    spans = [("bench.flush", 0.0, 4.0), ("bench.post", 1.0, 2.0),
             ("bench.wait", 6.0, 8.0)]
    gap_list = [(0.5, 1.5), (3.0, 7.0), (9.0, 10.0)]
    got = tracing.attribute(gap_list, spans)
    assert got == pytest.approx({"bench.flush": 0.5 + 1.0,
                                 "bench.post": 0.5,
                                 "bench.wait": 1.0,
                                 "no bench span": 2.0 + 1.0})


def test_reduce_events_averages_devices_and_names_ops_and_gaps():
    host = [("bench.window", 10.0, 20.0), ("bench.flush", 10.0, 12.0),
            ("bench.sleep", 15.0, 20.0)]
    dev = {"/device:TPU:0": [(9.0, 11.0), (12.0, 15.0)],
           "/device:TPU:1": [(12.0, 14.0)]}
    s = tracing.reduce_events(host, dev, {"loop": 5.0, "copy": 1.0})
    assert s["window_s"] == 10.0
    assert s["busy_s"] == pytest.approx((4.0 + 2.0) / 2)
    assert s["device_ops"] == [["loop", 2.5], ["copy", 0.5]]
    idle = dict(s["idle_gaps"])
    assert idle["bench.sleep"] == pytest.approx(5.0)
    assert idle["bench.flush"] == pytest.approx((1.0 + 2.0) / 2)
    assert idle["no bench span"] == pytest.approx(1.0 / 2)
    assert tracing.reduce_events(host[1:], dev) is None
    assert tracing.reduce_events(host, {}) is None
    assert tracing.op_name("%copy.11 = f32[2,64]{1,0} copy(f32[2,64] %p)") \
        == "copy.11"


def _reader(name):
    return bench.reader({"name": name, "moves": name.rsplit(".", 1)[1]})


def test_a_reader_serves_every_metric_its_quantity_moves():
    files = set(os.listdir(os.path.join(HERE, "metrics")))
    assert "device.idle_share.py" in files
    assert not any(f.startswith("device.idle_share.") and f.count(".") > 2
                   for f in files)
    run = SimpleNamespace(trace={"busy_s": 3.0, "window_s": 4.0})
    for moves in ("msg_rate", "goodput", "algbw"):
        read = bench.reader({"name": "device.idle_share." + moves,
                             "moves": moves})
        assert read(run) == pytest.approx(25.0)
    # a name that does not end in its ``moves`` is looked up whole
    with pytest.raises(FileNotFoundError):
        bench.reader({"name": "device.idle_share.goodput",
                      "moves": "msg_rate"})


def test_roofline_arithmetic_against_the_peak_table():
    peak = bench.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["ici_bytes_per_s"] * 8 == 1600e9
    trace = {"busy_s": 2.0, "window_s": 4.0}
    run = SimpleNamespace(trace=trace, peaks=peak, counters={
        "payload_bytes": 819e9 / 2, "ring_wire_bytes_per_chip": 100e9,
        "wqes_completed": 1000})
    # payload read once and written once: 819e9 bytes in 2 s of busy time
    assert _reader("transport.hbm_roofline.goodput")(run) == \
        pytest.approx(50.0)
    assert _reader("transport.ici_roofline.algbw")(run) == \
        pytest.approx(25.0)
    assert _reader("device.idle_share.goodput")(run) == pytest.approx(50.0)
    assert _reader("device.us_per_wqe.msg_rate")(run) == \
        pytest.approx(2000.0)
    # nothing to read: nothing returned, never a zero share
    empty = SimpleNamespace(trace=None, peaks=peak, counters={})
    for name in ("transport.hbm_roofline.goodput",
                 "transport.ici_roofline.algbw",
                 "device.idle_share.goodput", "device.us_per_wqe.msg_rate"):
        assert _reader(name)(empty) is None


def test_a_device_missing_from_the_peak_table_is_an_error():
    with pytest.raises(KeyError):
        bench.peaks("cpu")
