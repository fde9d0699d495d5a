"""The in-program span recorder: silent without the profiler running; under
one, spans at the engine's flush stages, the transport's dispatch and
host copies, and the collective's rounds, nested by parent id, with the
bytes each copy moved, landing on the profiler's host plane inside the
caller's annotations."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.rdma import RDMAEngine, WQE, Opcode, trace
from repro.train.collectives import RDMACollective

FLUSH_CHILDREN = {"rdma.flush.schedule", "rdma.flush.admit",
                  "rdma.transport.execute", "rdma.flush.complete"}


@pytest.fixture(autouse=True)
def _fresh_records():
    trace.clear()
    yield
    trace.clear()


def _post_reads(eng, qp, mr, n, length=7):
    # never adjacent, so nothing coalesces: one descriptor per WQE
    for i in range(n):
        eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, i,
                              local_addr=100 * i, remote_addr=50 * i + 1,
                              length=length, rkey=mr.rkey))


def _verbs_engine():
    eng = RDMAEngine(n_peers=2, pool_size=1024)
    return eng, eng.register_mr(0, 0, 1024), eng.create_qp(1, 0)


def _allreduce(words=100):
    eng = RDMAEngine(n_peers=4, pool_size=1024)
    rng = np.random.default_rng(0)
    shards = [rng.integers(-8, 9, words).astype(np.float32)
              for _ in range(4)]
    got = RDMACollective(eng).all_reduce(shards)
    assert all(np.array_equal(g, np.sum(shards, axis=0)) for g in got)
    return eng


def _children(recs, parent):
    return [r for r in recs if r.parent_id == parent.span_id]


def test_without_the_profiler_nothing_is_recorded():
    eng, mr, qp = _verbs_engine()
    _post_reads(eng, qp, mr, 5)
    eng.ring_sq_doorbell(qp)
    eng.read_buffer(0, 0, 16)
    _allreduce()
    assert trace.records() == []
    sp = trace.span("rdma.flush", flush=0)
    assert sp is trace.NO_SPAN
    with sp as inner:
        inner.set(wqes=1)
    assert trace.records() == []


def test_flush_and_host_copies_record_nested_spans(tmp_path):
    eng, mr, qp = _verbs_engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _post_reads(eng, qp, mr, 5)
        eng.ring_sq_doorbell(qp)
        eng.write_buffer(1, 0, np.ones(100, np.float32))   # pads to 128
        eng.read_buffer(1, 3, 37)
    finally:
        jax.profiler.stop_trace()
    recs = trace.records()
    (flush,) = [r for r in recs if r.name == "rdma.flush"]
    assert flush.parent_id is None
    assert flush.attrs == {"flush": 0, "qps": 1, "wqes": 5}
    kids = _children(recs, flush)
    assert {r.name for r in kids} == FLUSH_CHILDREN and len(kids) == 4
    for r in kids:
        assert flush.t0 <= r.t0 <= r.t1 <= flush.t1
    (ex,) = [r for r in kids if r.name == "rdma.transport.execute"]
    # 5 WQEs -> 8 descriptor slots of 5 int32; 7 words -> a chunk of 16
    assert ex.attrs == {"wqes": 5, "slots": 8, "chunk": 16,
                        "h2d_bytes": 8 * 5 * 4}
    (admit,) = [r for r in kids if r.name == "rdma.flush.admit"]
    assert admit.attrs == {"coalesced": 0}
    (h2d,) = [r for r in recs if r.name == "rdma.qdma.h2d"]
    (d2h,) = [r for r in recs if r.name == "rdma.qdma.d2h"]
    assert h2d.attrs == {"bytes": 128 * 4}
    assert d2h.attrs == {"bytes": 37 * 4}
    assert [c.wr_id for c in eng.poll_cq(qp, 8)] == list(range(5))


def test_allreduce_spans_count_its_rounds_and_host_copies(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng = _allreduce(words=100)
    finally:
        jax.profiler.stop_trace()
    recs = trace.records()
    (call,) = [r for r in recs if r.name == "rdma.coll.allreduce"]
    assert call.attrs == {"allreduce": 0, "buckets": 1,
                          "bucket_bytes": 400}
    top = _children(recs, call)
    names = [r.name for r in top]
    # ring over 4 peers: 3 reduce-scatter rounds, each followed by its
    # host reduce, then 3 all-gather rounds
    assert names == (["rdma.coll.load"]
                     + ["rdma.coll.round", "rdma.coll.reduce"] * 3
                     + ["rdma.coll.round"] * 3 + ["rdma.coll.readout"])
    rounds = [r for r in top if r.name == "rdma.coll.round"]
    assert [r.attrs["round"] for r in rounds] == list(range(6))
    for r in rounds:
        (flush,) = _children(recs, r)
        assert flush.name == "rdma.flush" and flush.attrs["wqes"] == 4
    copies = {"rdma.qdma.h2d": 0, "rdma.qdma.d2h": 0}
    for part in top:
        for r in _children(recs, part):
            if r.name in copies:
                copies[r.name] += r.attrs["bytes"]
    # load: 4 rows of 100 words padded to 128; each reduce-scatter round:
    # 4 peers read 2 chunks of 25 words and stage 25 padded to 32;
    # read-out: 4 sums of 100 words. All f32.
    assert copies["rdma.qdma.h2d"] == 4 * (4 * 128 + 3 * 4 * 32)
    assert copies["rdma.qdma.d2h"] == 4 * (3 * 4 * 2 * 25 + 4 * 100)
    # each round's dispatch uploads its descriptor table: 4 WQEs in 8
    # slots of 5 int32
    descriptors = [r.attrs["h2d_bytes"] for r in recs
                   if r.name == "rdma.transport.execute"]
    assert descriptors == [8 * 5 * 4] * 6


def test_a_full_record_list_counts_what_it_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "MAX_RECORDS", 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(5):
            with trace.span("rdma.test", i=i):
                pass
    finally:
        jax.profiler.stop_trace()
    assert [r.attrs["i"] for r in trace.records()] == [0, 1]
    assert trace.dropped == 3
    trace.clear()
    assert trace.records() == [] and trace.dropped == 0


def test_program_spans_land_inside_the_callers_annotation(tmp_path):
    from jax.profiler import ProfileData
    eng, mr, qp = _verbs_engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _post_reads(eng, qp, mr, 5)
        with jax.profiler.TraceAnnotation("bench.flush"):
            eng.ring_sq_doorbell(qp)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for line in plane.lines for ev in line.events]
        outer = [e for e in events if e[0] == "bench.flush"]
        inner = [e for e in events if e[0] == "rdma.flush"]
        if outer or inner:
            found.append(plane.name)
            assert len(outer) == 1 and len(inner) == 1
            assert outer[0][1] <= inner[0][1] <= inner[0][2] <= outer[0][2]
            names = {e[0] for e in events}
            assert FLUSH_CHILDREN <= names
    assert len(found) == 1
