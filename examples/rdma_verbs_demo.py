"""libreconic-style RDMA verbs walkthrough (paper §IV-B):

READ / WRITE / SEND-RECV / batch READ / batch WRITE — each in both
single-request and batch-requests doorbell modes, with QPs on host_mem or
dev_mem (the `-l` option of the paper's examples), plus engine telemetry.

    PYTHONPATH=src python examples/rdma_verbs_demo.py
"""
import numpy as np

from repro.core.rdma import (DoorbellCoalescer, Opcode, RDMAEngine, WQE)
from repro.core.rdma.simulator import simulate_rdma
from repro.core.rdma.verbs import Placement


def main():
    eng = RDMAEngine(n_peers=2, pool_size=8192)
    server, client = 1, 0
    qp = eng.create_qp(client, server)
    rqp = eng.create_qp(server, client)
    mr = eng.register_mr(server, 0, 4096)
    eng.write_buffer(server, 0, np.arange(256, dtype=np.float32))

    # -- READ (single-request) -------------------------------------------
    eng.post_send(qp, WQE(Opcode.READ, qp.qp_num, 1, local_addr=0,
                          remote_addr=0, length=64, rkey=mr.rkey))
    eng.ring_sq_doorbell(qp)
    print("READ  :", eng.poll_cq(qp)[0].status.value,
          eng.read_buffer(client, 0, 4))

    # -- WRITE -------------------------------------------------------------
    eng.write_buffer(client, 128, np.full(32, 3.5, np.float32))
    eng.post_send(qp, WQE(Opcode.WRITE, qp.qp_num, 2, local_addr=128,
                          remote_addr=512, length=32, rkey=mr.rkey))
    eng.ring_sq_doorbell(qp)
    print("WRITE :", eng.poll_cq(qp)[0].status.value,
          eng.read_buffer(server, 512, 4))

    # -- SEND / RECV (two-sided, with immediate) ---------------------------
    eng.post_recv(rqp, WQE(Opcode.RECV, rqp.qp_num, 7, local_addr=1024,
                           length=16))
    eng.post_send(qp, WQE(Opcode.SEND_IMM, qp.qp_num, 3, local_addr=0,
                          length=16, imm=0x1234))
    eng.ring_sq_doorbell(qp)
    rc = eng.poll_cq(rqp)[0]
    print(f"SEND  : responder got {rc.byte_len}B imm=0x{rc.imm:x}")

    # -- BATCH READ: n WQEs, ONE doorbell (paper's batch-requests) --------
    d0 = eng.transport.dispatch_count
    with DoorbellCoalescer(eng, qp, flush_threshold=50) as db:
        for i in range(50):
            db.post(WQE(Opcode.READ, qp.qp_num, 100 + i,
                        local_addr=2048 + i, remote_addr=i, length=1,
                        rkey=mr.rkey))
    print(f"BATCH READ: 50 WQEs -> "
          f"{eng.transport.dispatch_count - d0} dispatch(es), "
          f"{len(eng.poll_cq(qp, 64))} completions")

    # -- timing model: what batching buys on the paper's hardware ---------
    for payload in (4096, 16384, 32768):
        s = simulate_rdma("read", payload, 1)
        b = simulate_rdma("read", payload, 50)
        print(f"model {payload//1024:3d}KB: single "
              f"{s.throughput_bps/1e9:5.1f} Gb/s -> batch "
              f"{b.throughput_bps/1e9:5.1f} Gb/s "
              f"({b.throughput_bps/s.throughput_bps:.1f}x)")

    # -- CONCURRENT DOORBELLS: two QPs share the engine fairly ------------
    # The engine is shared (the paper's key flexibility point), so a deep
    # SQ could starve a shallow one. Ring with defer=True, then one flush
    # interleaves both windows round-robin under a WQE budget.
    deep = eng.create_qp(client, server)           # 24 pending WQEs
    shallow = eng.create_qp(client, server, weight=1)
    eng.scheduler, eng.flush_budget = "rr", 8
    for i in range(24):
        eng.post_send(deep, WQE(Opcode.READ, deep.qp_num, i,
                                local_addr=4096 + i, remote_addr=i,
                                length=1, rkey=mr.rkey))
    for i in range(4):
        eng.post_send(shallow, WQE(Opcode.READ, shallow.qp_num, 500 + i,
                                   local_addr=4200 + i, remote_addr=i,
                                   length=1, rkey=mr.rkey))
    eng.ring_sq_doorbell(deep, defer=True)
    eng.ring_sq_doorbell(shallow, defer=True)
    counts = eng.flush_doorbells()                 # ONE scheduled batch
    print(f"2-QP flush: deep got {counts[deep.qp_num]}/8, "
          f"shallow got {counts[shallow.qp_num]}/8 "
          f"(rr — the shallow QP is not starved)")
    while deep.pending() or shallow.pending():     # drain the leftovers
        eng.flush_doorbells()
    print(f"2-QP done : deep {len(eng.poll_cq(deep, 64))} CQEs in order, "
          f"shallow {len(eng.poll_cq(shallow, 64))} CQEs, "
          f"service={eng.stats['qp_service']}")
    eng.scheduler, eng.flush_budget = "rr", None

    # -- LOOKASIDE OFFLOAD: one host QP + one LC kernel share a flush ------
    # The compute blocks are CLIENTS of the same engine (paper §I): the
    # registered systolic_mm kernel RDMA-reads A,B from the server,
    # computes on the NIC, and RDMA-writes C back — its WQEs ride the
    # same descriptor tables as the host QP's verbs traffic, scheduled
    # by deficit round-robin under a budget.
    import jax.numpy as jnp

    from repro.core.lookaside import ControlMsg, LookasideBlock
    from repro.kernels.lc_offload import MM_WORKLOAD, register_default_kernels
    from repro.kernels.ref import ref_matmul

    eng.scheduler, eng.flush_budget = "drr", 8
    blk = LookasideBlock(eng, peer=client, scratch_base=6144)
    register_default_kernels(blk)
    blk.eager_writeback = False       # StatusMsg rides the write-back CQE

    host_qp = eng.create_qp(client, server)
    for i in range(6):                # concurrent host verbs traffic
        eng.post_send(host_qp, WQE(Opcode.READ, host_qp.qp_num, 700 + i,
                                   local_addr=5000 + i, remote_addr=i,
                                   length=1, rkey=mr.rkey))
    eng.ring_sq_doorbell(host_qp, defer=True)      # armed, not flushed

    i0 = eng.stats["transport"]["interleaved_batches"]
    m = 8
    blk.dispatch(ControlMsg(MM_WORKLOAD,
                            (server, mr.rkey, 0, 64, 2048, m, m, m),
                            tag=42))
    print(f"LC mm  : kernel done, status deferred "
          f"(poll={blk.poll(MM_WORKLOAD)}) — write-back CQE pending")
    eng.flush_doorbells()             # host-driven flush completes it
    st = blk.poll(MM_WORKLOAD)
    A = eng.read_buffer(server, 0, m * m).reshape(m, m)
    B = eng.read_buffer(server, 64, m * m).reshape(m, m)
    C = eng.read_buffer(server, 2048, m * m).reshape(m, m)
    err = float(np.abs(
        C - np.asarray(ref_matmul(jnp.asarray(A), jnp.asarray(B)))).max())
    while host_qp.pending():
        eng.flush_doorbells()
    print(f"LC mm  : ok={st.ok} tag={st.tag} |C-A@B|={err:.1e}; "
          f"{eng.stats['transport']['interleaved_batches'] - i0} "
          f"interleaved flush(es), lc_service="
          f"{eng.stats['lc_service']}, host got "
          f"{len(eng.poll_cq(host_qp, 64))} CQEs alongside")
    assert st.ok and err == 0.0
    eng.scheduler, eng.flush_budget = "rr", None

    # -- STREAMING RX (§IV-D): packets off the MAC, no ControlMsg ----------
    # Non-RDMA packets land in a device-resident RX ring (the ingress
    # classifier splits RoCEv2 traffic off to the RDMA engine);
    # LCKernel.stream() drains the ring in bursts — each burst's gather
    # is ONE descriptor-table execution, and with pipeline_depth > 1
    # burst i+1's gather is armed while burst i parses, so fetches and
    # write-backs share a flush (watch stats["lc_pipeline"]).
    from repro.core.streaming import RXRing, TrafficRouter, make_roce_header
    from repro.kernels.lc_offload import STREAM_PARSER_WORKLOAD

    sblk = LookasideBlock(eng, peer=client, scratch_base=4096,
                          scratch_size=2048, pipeline_depth=2,
                          eager_writeback=False)
    register_default_kernels(sblk)
    ring = RXRing(eng, peer=client, base=8192 - 16 * 64, depth=16)
    meta_mr = eng.register_mr(server, 3072, 16 * 4)
    sk = sblk.attach_ring(STREAM_PARSER_WORKLOAD, ring, out_peer=server,
                          out_rkey=meta_mr.rkey, out_base=3072, burst=4)
    router = TrafficRouter(rx_ring=ring)
    headers = np.stack([make_roce_header(4, 99, is_rdma=(i % 2 == 0))
                        for i in range(10)])
    counts = router.ingest_packets(headers)     # RDMA share bypasses ring
    consumed = sk.stream()                      # batched ring drain
    meta = eng.read_buffer(server, 3072, consumed * 4).reshape(-1, 4)
    print(f"STREAM : ingested {counts}, parsed {consumed} off the ring "
          f"(meta rows all non-RDMA: {not meta[:, 0].any()}), "
          f"pipeline={eng.stats['lc_pipeline']['head']}/"
          f"{eng.stats['lc_pipeline']['tail']} done, ring "
          f"occupancy peak {ring.stats['peak_occupancy']}")
    assert consumed == counts["streamed"] and not meta[:, 0].any()

    # -- MATCH→ACTION DISPATCH PLANE: per-packet handler routing -----------
    # The streaming path above hardwires ONE parser consuming the whole
    # ring. The dispatch plane is the multi-tenant version (the paper's
    # Vitis Networking P4 block): a MatchTable routes each ingress
    # packet by its parsed fields — RoCEv2 to the RDMA engine, ctrl
    # traffic (port 9000) to the parser handler, bulk traffic (port
    # 9100) to the int8-quantize handler — and the StreamDispatcher
    # demuxes the shared ring into per-handler sub-bursts whose operand
    # gathers all ride ONE descriptor table per flush. Both handlers
    # write class-mirrored output rings; host verbs traffic can share
    # the very same flushes (the engine stays one shared machine).
    from repro.core.streaming import (Drop, Forward, Handler, MatchTable,
                                      StreamDispatcher)
    from repro.kernels.lc_offload import (QUANT_ROW, STREAM_QUANT_WORKLOAD)

    # client pool layout: sblk scratch is 4096..6144 and the streaming
    # ring above sits at 7168..8192 — this ring takes 6144..7168
    dring = RXRing(eng, peer=client, base=6144, depth=16)
    dmeta_mr = eng.register_mr(server, 3328, 16 * 4)
    dquant_mr = eng.register_mr(server, 3392, 16 * QUANT_ROW)
    table = (MatchTable(default=Drop())
             .add(Forward(), priority=10, is_rdma=1)
             .add(Handler(STREAM_PARSER_WORKLOAD), udp_dport=9000)
             .add(Handler(STREAM_QUANT_WORKLOAD), udp_dport=9100))
    disp = StreamDispatcher(sblk, dring, table, burst=4)
    disp.register_handler(STREAM_PARSER_WORKLOAD, server, dmeta_mr.rkey,
                          3328)
    disp.register_handler(STREAM_QUANT_WORKLOAD, server, dquant_mr.rkey,
                          3392)
    drouter = TrafficRouter(rx_ring=dring, table=table)

    mixed = np.stack([make_roce_header(4, i) if i % 3 == 0
                      else make_roce_header(0, i, is_rdma=False,
                                            dport=9000 if i % 3 == 1
                                            else 9100)
                      for i in range(12)])
    # host verbs traffic armed alongside: one flush serves everything
    # (local_addr 3000.. is outside every scratch/ring region)
    for i in range(4):
        eng.post_send(host_qp, WQE(Opcode.READ, host_qp.qp_num, 900 + i,
                                   local_addr=3000 + i, remote_addr=i,
                                   length=1, rkey=mr.rkey))
    eng.ring_sq_doorbell(host_qp, defer=True)
    dp = eng.stats["dispatch"]           # engine-wide ledger: deltas
    r0, m0 = dp["dispatch_rounds"], dp["dispatch_mixed_rounds"]
    p0 = {n: c["pkts"] for n, c in dp["classes"].items()}
    dcounts = drouter.ingest_packets(mixed)
    dconsumed = disp.service()
    print(f"DISPATCH: ingested {dcounts} via the match table, "
          f"{dconsumed} pkts demuxed to "
          f"{ {n: c['pkts'] - p0.get(n, 0) for n, c in dp['classes'].items()} } "
          f"in {dp['dispatch_rounds'] - r0} round(s) "
          f"({dp['dispatch_mixed_rounds'] - m0} mixed — both handlers' "
          f"gathers in one flush), host CQEs alongside: "
          f"{len(eng.poll_cq(host_qp, 64))}")
    assert dconsumed == dcounts["streamed"] == 8
    assert dp["dispatch_mixed_rounds"] - m0 >= 1

    # -- SERVICE CHAIN: a MatchTable action that is a kernel PIPELINE ------
    # The dispatch plane generalized (BALBOA-style service chaining): a
    # table entry can name a Chain of lookaside kernels, where stage N's
    # RDMA write-back region IS stage N+1's operand-fetch source — no
    # host hop between stages, every stage's gathers and write-backs
    # riding the engine's shared shape-bucketed descriptor tables. The
    # production pipeline below is gradient egress: rows stream through
    # compress→checksum (int8 wire bytes byte-identical to
    # kops.compress(chunk=64), integrity stamps computed FROM those wire
    # bytes by the next stage), while host verbs traffic armed on the
    # same engine shares the very same flushes.
    from repro.core.streaming import GradEgressChain
    from repro.kernels import ops as kops

    geng = RDMAEngine(n_peers=2, pool_size=1 << 15, scheduler="drr",
                      flush_budget=16)
    chain = GradEgressChain(geng, data_peer=server, ring_base=1024,
                            out_base=4096, lc_peer=client,
                            scratch_base=1 << 14, scratch_size=1 << 14,
                            depth=16, burst=8)
    cqp = geng.create_qp(client, server)
    cmr = geng.register_mr(server, 0, 512)
    for i in range(4):                  # host verbs armed alongside
        geng.post_send(cqp, WQE(Opcode.READ, cqp.qp_num, 800 + i,
                                local_addr=700 + i, remote_addr=i,
                                length=1, rkey=cmr.rkey))
    geng.ring_sq_doorbell(cqp, defer=True)
    gflat = np.random.default_rng(3).normal(size=500).astype(np.float32)
    q, s, csum, resid = chain.compress(gflat, np.zeros(500, np.float32))
    kq, ks, _ = kops.compress(jnp.asarray(gflat), chunk=64)
    cparity = (np.array_equal(q, np.asarray(kq))
               and np.array_equal(s, np.asarray(ks)))
    cled = geng.stats["dispatch"]["chains"]["grad_egress"]
    print(f"CHAIN : compress→checksum egress of {q.shape[0]} rows in "
          f"{cled['bursts']} burst(s): {cled['stage_invocations']} stage "
          f"invocations / {cled['wqes']} chain WQEs, wire parity vs "
          f"kops.compress={cparity}, checksums "
          f"ok={GradEgressChain.verify_checksums(q, s, csum)}, host CQEs "
          f"alongside: {len(geng.poll_cq(cqp, 64))}")
    assert cparity and cled["stages"] == 2
    assert cled["completed_pkts"] == q.shape[0]
    assert GradEgressChain.verify_checksums(q, s, csum)

    # -- RELIABILITY: a lossy wire behind the same verbs (paper §III-A) ----
    # RoCEv2 RC semantics: every WQE transmission gets a PSN, a seeded
    # FaultInjector at the transport boundary loses 5% of them (plus
    # duplicates and corruption), and the go-back-N layer retransmits
    # until the bytes land — the host sees only SUCCESS CQEs, in posting
    # order, and a ledger of what the wire did. A stalled peer exhausts
    # the bounded retry budget into TERMINAL error CQEs (never an
    # exception), and recover_qp reopens the QP on a fresh PSN epoch.
    from repro.core.rdma import (CQEStatus, FaultInjector, QPState,
                                 ReliabilityConfig)

    reng = RDMAEngine(n_peers=2, pool_size=4096, flush_budget=8)
    injector = reng.install_fault_injector(
        FaultInjector(seed=7, drop=0.05, duplicate=0.02, corrupt=0.02),
        ReliabilityConfig(retry_cnt=8))
    rqp2 = reng.create_qp(client, server)
    rmr = reng.register_mr(server, 0, 2048)
    reng.write_buffer(client, 0, np.arange(512, dtype=np.float32))
    for i in range(32):
        reng.post_send(rqp2, WQE(Opcode.WRITE, rqp2.qp_num, i,
                                 local_addr=16 * i, remote_addr=16 * i,
                                 length=16, rkey=rmr.rkey))
    reng.ring_sq_doorbell(rqp2, defer=True)
    cqes = []
    while rqp2.pending_count or reng._reliability.outstanding():
        reng.flush_doorbells()
        cqes.extend(reng.poll_cq(rqp2, 64))
    rel = reng.stats["reliability"]
    ok = (np.array_equal(reng.read_buffer(server, 0, 512),
                         np.arange(512, dtype=np.float32))
          and [c.wr_id for c in cqes] == list(range(32)))
    print(f"RELIAB : 32 WRITEs over a 5%-loss wire -> parity={ok}, "
          f"ledger: acks={rel['acks']} retx={rel['retransmits']} "
          f"drops={rel['dropped']} naks={rel['naks']} "
          f"dup_suppressed={rel['dup_suppressed']}")
    assert ok and rel["acks"] == 32

    injector.stall_peer(server)          # the far side goes dark
    retx_before_stall = rel["retransmits"]
    reng.post_send(rqp2, WQE(Opcode.WRITE, rqp2.qp_num, 99, local_addr=0,
                             remote_addr=0, length=16, rkey=rmr.rkey))
    reng.ring_sq_doorbell(rqp2, defer=True)
    dead_cqes = []
    while not dead_cqes:
        reng.flush_doorbells()
        dead_cqes.extend(reng.poll_cq(rqp2))
    print(f"RELIAB : stalled peer -> {dead_cqes[0].status.value} after "
          f"{rel['retransmits'] - retx_before_stall} retransmissions, QP "
          f"{rqp2.state.value}, qp_errors={rel['qp_errors']}")
    assert dead_cqes[0].status is CQEStatus.RETRY_EXC_ERROR
    injector.unstall_peer(server)
    reng.recover_qp(rqp2)
    reng.post_send(rqp2, WQE(Opcode.WRITE, rqp2.qp_num, 100, local_addr=0,
                             remote_addr=1024, length=16, rkey=rmr.rkey))
    reng.ring_sq_doorbell(rqp2)
    print(f"RELIAB : recovered -> {reng.poll_cq(rqp2)[0].status.value}, "
          f"QP {rqp2.state.value}, recoveries={rel['recovered']}")
    assert rqp2.state is QPState.RTS

    # -- KV-SERVE: decode workers as transport clients ---------------------
    # Disaggregated KV-cache serving over the same verbs: KV pages are
    # MRs in a remote pool, a decode tenant fetches them with one-sided
    # READs on its own QP (weight = SLO tier), and a compressed pool
    # moves quantize-packed pages — 64/33 fewer wire words. Migration is
    # ONE doorbell batch of READs that evicts a source page only after
    # its SUCCESS CQE, so a lossy wire can never lose a page.
    from repro.serve.kv_cache import (PagedKVPool, RemoteKVClient,
                                      migrate_sequence, packed_page_words)

    keng = RDMAEngine(n_peers=2, pool_size=8192, scheduler="drr")
    kpool = PagedKVPool(keng, server, page_elems=256, max_pages=8)
    krows = np.random.default_rng(0).standard_normal(
        (2, 256)).astype(np.float32)
    for row in krows:
        kpool.write_page(kpool.append_page(seq_id=0), row)
    kclient = RemoteKVClient(keng, client, kpool)
    gold = kclient.register_tenant("gold", weight=2)
    kb0 = keng.stats["qp_bytes"].get(gold.qp.qp_num, 0)
    fetched = kclient.complete(kclient.fetch_sequence(gold, 0))
    kwire = keng.stats["qp_bytes"][gold.qp.qp_num] - kb0
    print(f"KV-SERVE: tenant '{gold.name}' (weight={gold.weight}) "
          f"fetched {len(kpool.pages[0])} pages = {kwire} words over "
          f"one-sided READs, parity={bool((fetched == krows).all())}")
    assert (fetched == krows).all() and kwire == 2 * 256

    zpool = PagedKVPool(keng, server, page_elems=256, max_pages=4,
                        compressed=True)
    zpool.write_page(zpool.append_page(seq_id=0), krows[0])
    zclient = RemoteKVClient(keng, client, zpool)
    bulk = zclient.register_tenant("bulk", weight=1)
    zb0 = keng.stats["qp_bytes"].get(bulk.qp.qp_num, 0)
    zfetched = zclient.complete(zclient.fetch_sequence(bulk, 0))
    zwire = keng.stats["qp_bytes"][bulk.qp.qp_num] - zb0
    zerr = float(np.abs(zfetched[0] - krows[0]).max())
    print(f"KV-SERVE: compressed pool moved {zwire} words for a 256-elem "
          f"page (= {packed_page_words(256)}: scales + packed int8 "
          f"pairs) -> wire ratio {256 / zwire:.2f}x, "
          f"max dequant err {zerr:.3f}")
    assert zwire == 132

    kdst = PagedKVPool(keng, client, page_elems=256, max_pages=8)
    kqp = keng.create_qp(client, server)
    moved = migrate_sequence(keng, TrafficRouter(), kpool, kdst, 0, kqp)
    print(f"KV-SERVE: migrated {moved} pages in ONE doorbell batch "
          f"(src evicted on SUCCESS CQEs only), "
          f"ledger={keng.stats['kv_serve']}")
    assert moved == 2 and kpool.allocated == 0

    # -- COLLECTIVES: gradient all-reduce as scheduled verbs ---------------
    # Training comm on the SAME engine kind serving uses: a ring
    # all-reduce is 2(n-1) rounds of one-sided chunk READs, one deferred
    # doorbell flush per round, host partial-reduces between rounds.
    from repro.train.collectives import RDMACollective, ideal_wire_words

    ceng = RDMAEngine(n_peers=4, pool_size=4096, scheduler="drr")
    coll = RDMACollective(ceng, 4, algorithm="ring", pipeline_depth=2)
    crng = np.random.default_rng(1)
    grads = [[crng.integers(-8, 9, 256).astype(np.float32)
              for _ in range(4)] for _ in range(2)]     # 2 buckets
    summed = coll.all_reduce_buckets(grads)
    parity = all(
        np.array_equal(summed[b][p], np.sum(grads[b], axis=0))
        for b in range(2) for p in range(4))
    led = ceng.stats["collectives"]
    print(f"COLLECTIVES: ring all-reduce of 2 buckets x 256 words over "
          f"4 peers: {led['rounds']} rounds in {led['flushes']} flushes "
          f"({led['overlapped_flushes']} overlapped), "
          f"{led['wire_bytes'] // 4} wire words "
          f"(ideal {2 * ideal_wire_words('ring', 4, 256)}), "
          f"parity={parity}")
    assert parity and led["overlapped_flushes"] > 0
    assert led["wire_bytes"] == 4 * 2 * ideal_wire_words("ring", 4, 256)

    # -- AUTOTUNE: the transport tunes its own knobs -----------------------
    # Every knob above (ring_burst=32, pipeline_depth, flush_budget, the
    # per-QP window) started life hand-picked. The engine now learns
    # both halves online: a decaying (slots, chunk) histogram built from
    # its OWN dispatch stream replaces replayed `bucket_hist` dumps as
    # the prewarm source, and a seeded coordinate sweep re-measures the
    # knobs against the engine's own traffic shape, scoring trials on
    # deterministic flush/WQE counts through the doorbell cost model —
    # never wall-clock — so the chosen point is reproducible.
    from repro.core.rdma.autotune import AutoTuner, TuningGrid

    tuner = AutoTuner(eng, seed=7, passes=1, rows=64,
                      grid=TuningGrid(ring_burst=(16, 32, 64),
                                      pipeline_depth=(1, 2, 4),
                                      flush_budget=(None,),
                                      qp_window=(None, 8)))
    chosen = tuner.sweep()                  # installs via apply_tuning()
    at = eng.stats["autotune"]
    print(f"AUTOTUNE: {at['trials']} trials -> burst={chosen.ring_burst} "
          f"depth={chosen.pipeline_depth} window={chosen.qp_window} "
          f"({at['improvement']:.2f}x over hand-picked defaults)")
    assert at["improvement"] >= 1.0 and eng.tuning == chosen

    # A fresh engine prewarms straight off the live engine's learned
    # histogram — widened buckets included — so its first real batch is
    # a descriptor-cache hit instead of a cold compile.
    warm = RDMAEngine(n_peers=2, pool_size=eng.pool_size)
    n_warm = warm.transport.prewarm(eng.transport.bucket_learner)
    print(f"AUTOTUNE: fresh engine prewarmed {n_warm} learned buckets "
          f"({eng.transport.stats['learned_buckets']} live, "
          f"{eng.transport.stats['bucket_merges']} merged, "
          f"{eng.transport.stats['bucket_decay_events']} decayed)")
    assert n_warm >= 1 and warm.transport.stats["cache_misses"] == 0

    # -- host_mem vs dev_mem placement (the -l flag) -----------------------
    eng.write_buffer(client, 0, np.ones(8, np.float32),
                     Placement.HOST_MEM)
    print("host_mem buffer:", eng.read_buffer(client, 0, 4,
                                              Placement.HOST_MEM))
    print("engine stats   :", eng.stats)
    print("OK")


if __name__ == "__main__":
    main()
