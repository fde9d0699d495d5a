"""Disaggregated paged KV-cache serving over one-sided RDMA READs.

The serving-layer embodiment of RecoNIC's memory model (the "In-Network
Memory Access: Bridging SmartNIC and Host Memory" direction), mapped
block by block:

  KV page      -> a registered ``MemoryRegion`` in a peer's dev_mem pool.
                  The page table is host-side metadata (numpy dicts);
                  page payloads live in the engine's device pool and
                  only ever move through verbs or the QDMA staging path.
  page fetch   -> a one-sided READ WQE (responder CPU not involved,
                  exactly the paper's §III-A one-sided semantics),
                  posted on the fetching tenant's own QP and scheduled
                  into the SAME shape-bucketed descriptor tables as all
                  other engine traffic: pages are pow2 chunk buckets, so
                  steady-state decode fetches compile nothing new.
  migration    -> ONE doorbell batch of READs (the paper's
                  batch-requests applied to KV movement), completion-
                  tracked per page: on the lossy fabric a source page is
                  evicted ONLY after its READ completed with SUCCESS,
                  and destination pages of failed READs are rolled back.
                  (The seed evicted unconditionally — silent data loss
                  under any error CQE or partial completion.)
  SLO tiers    -> per-tenant QPs whose scheduler ``weight`` is the tier:
                  under ``scheduler="drr"`` a weight-w tenant is offered
                  w WQEs per round when fetches contend for a flush, so
                  a gold tenant's pages land sooner and an adversarial
                  tenant's deep backlog is confined to its own share
                  (innocent-tenant Jain stays 1.0 — CI-gated).
  compression  -> pages may be stored quantize-packed (``compressed=True``
                  pools): per 64-lane chunk, int8 values + one fp32
                  scale, int8 pairs packed two-per-pool-word. The wire
                  moves 64/33 fewer words per page and the decode worker
                  dequantizes after the fetch through the same cached
                  jitted programs as the bulk-class ``quantize_stream``
                  dispatch handler.

Byte accounting derives from the pool's element dtype (``itemsize``) —
never a hardcoded ``* 4``: an int8 page bills 1 byte/element, a bf16
page 2, a compressed page its packed payload (int8 values + fp32
scales), so the router's per-class byte counters and the cost model's
bytes-moved ratios stay truthful across mixed-precision pools.

Reliability contract (PR 6 fabric): every completion loop here drives
``engine.flush_doorbells`` so retransmission timers advance; retry
exhaustion surfaces terminal CQEs (never hangs), after which the caller
either recovers the QP (``RemoteKVClient.complete(recover=True)``) or
receives the error (``KVFetchError`` / migration rollback).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.core.memory import BufferPool
from repro.core.rdma.doorbell import DoorbellCoalescer
from repro.core.rdma.verbs import CQEStatus, Opcode, WQE
from repro.core.streaming.classifier import (TrafficClass, TransferDesc)

#: quantization chunk of a compressed page (= the bulk-class stream
#: handler's slot width, so both share the same cached Pallas programs)
PAGE_CHUNK = 64

#: wr_id tokens for KV traffic: engine-wide unique so a client never
#: mistakes a stale CQE (earlier fetch on the same QP) for its own
_wr_tokens = itertools.count(0x4B560000)


def _ledger(engine) -> dict:
    """The engine's ``stats["kv_serve"]`` ledger, default-initialized."""
    led = engine.stats.setdefault("kv_serve", {})
    for key in ("fetches", "completed", "failed", "pages_posted",
                "pages_fetched", "pages_failed", "posted_words",
                "recoveries", "migrations", "pages_migrated",
                "pages_rolled_back"):
        led.setdefault(key, 0)
    return led


def packed_page_words(page_elems: int) -> int:
    """Pool words of one quantize-packed page: one fp32 scale per
    64-elem chunk + the int8 values packed two per word — 33/64 of the
    uncompressed footprint."""
    assert page_elems % PAGE_CHUNK == 0, page_elems
    return page_elems // PAGE_CHUNK + page_elems // 2


def quant_pack_page(x: np.ndarray) -> np.ndarray:
    """Quantize-pack one logical page into its wire format.

    ``x`` (page_elems,) f32 -> (packed_page_words,) f32 pool words:
    ``[scales (n_chunks) | int8 pairs (page_elems/2)]`` where a pair
    word is ``(q0+128)*256 + (q1+128)`` — an exact small integer in
    fp32 (< 2^16), so the float pool carries it bit-faithfully.
    Quantization runs the same cached jitted ``quantize_stream``
    program as the bulk-class dispatch handler (byte-identical to
    ``ref.ref_quantize`` row-wise)."""
    from repro.kernels.lc_offload import _quant_bucketed
    x = np.asarray(x, np.float32).reshape(-1, PAGE_CHUNK)
    q, s = _quant_bucketed(x)
    pairs = (np.asarray(q, np.int64) + 128).reshape(-1, 2)
    packed = (pairs[:, 0] * 256 + pairs[:, 1]).astype(np.float32)
    return np.concatenate([np.asarray(s, np.float32).reshape(-1), packed])


def quant_unpack_page(words: np.ndarray, page_elems: int) -> np.ndarray:
    """Inverse of ``quant_pack_page``: (packed_page_words,) pool words
    -> (page_elems,) dequantized f32, through the cached jitted
    ``dequantize_stream`` program (bit-identical to
    ``ref.ref_dequantize`` on the unpacked int8/scales)."""
    from repro.kernels.lc_offload import _dequant_bucketed
    n_chunks = page_elems // PAGE_CHUNK
    s = np.asarray(words[:n_chunks], np.float32).reshape(n_chunks, 1)
    pw = np.rint(np.asarray(words[n_chunks:], np.float64)).astype(np.int64)
    q = np.stack([pw // 256 - 128, pw % 256 - 128], axis=1)
    q = q.reshape(n_chunks, PAGE_CHUNK).astype(np.int8)
    return _dequant_bucketed(q, s).reshape(-1)


@dataclass
class Page:
    """One KV page: its MR in the owning peer's pool, plus the billable
    payload bytes (dtype-derived — what a real NIC would serialize)."""
    mr: object                  # MemoryRegion holding the page payload
    seq_id: int
    page_idx: int
    nbytes: int = 0


class PagedKVPool:
    """Fixed-size page allocator over a peer's BufferPool.

    ``dtype`` is the logical element type of a page (one element per
    pool word; int8/bf16 values are exact in the f32 pool) and drives
    billing: a page's ``nbytes`` is ``page_elems * dtype.itemsize``.
    ``compressed=True`` stores pages quantize-packed instead: the MR
    shrinks to ``packed_page_words`` and bills the packed payload
    (int8 values + fp32 scales).
    """

    def __init__(self, engine, peer: int, page_elems: int,
                 max_pages: int, dtype=np.float32,
                 compressed: bool = False):
        self.engine = engine
        self.peer = peer
        self.page_elems = page_elems
        self.dtype = np.dtype(dtype)
        self.compressed = compressed
        if compressed:
            self.page_words = packed_page_words(page_elems)
            self.page_nbytes = (page_elems
                                + 4 * (page_elems // PAGE_CHUNK))
        else:
            self.page_words = page_elems
            self.page_nbytes = page_elems * self.dtype.itemsize
        self.pool = BufferPool(engine, peer)
        self.pages: Dict[int, List[Page]] = {}      # seq_id -> pages
        self.max_pages = max_pages
        self.allocated = 0

    def append_page(self, seq_id: int,
                    page_idx: Optional[int] = None) -> Page:
        """Allocate the next page of ``seq_id``. ``page_idx`` pins the
        logical index (migration mirrors the source page's index so a
        retried partial migration never collides)."""
        if self.allocated >= self.max_pages:
            raise MemoryError("KV pool exhausted (eviction required)")
        mr = self.pool.alloc(self.page_words)
        if page_idx is None:
            page_idx = len(self.pages.get(seq_id, []))
        page = Page(mr, seq_id, page_idx, self.page_nbytes)
        self.pages.setdefault(seq_id, []).append(page)
        self.allocated += 1
        return page

    def write_page(self, page: Page, data: np.ndarray) -> None:
        """Stage logical page data (``page_elems`` elements) into the
        page's MR — compressed pools quantize-pack on the way in. Rides
        the QDMA pow2 chunk-bucketed staging path (no per-length
        recompile)."""
        data = np.asarray(data, np.float32).reshape(-1)
        if self.compressed:
            data = quant_pack_page(data)
        self.pool.write(page.mr, data)

    def read_page(self, page: Page) -> np.ndarray:
        """Logical page contents (dequantized for compressed pools)."""
        raw = self.pool.read(page.mr)
        if self.compressed:
            return quant_unpack_page(raw, self.page_elems)
        return raw

    def read_page_raw(self, page: Page) -> np.ndarray:
        """The page's pool words exactly as the wire moves them."""
        return self.pool.read(page.mr)

    def evict(self, seq_id: int) -> int:
        pages = self.pages.pop(seq_id, [])
        for p in pages:
            self.pool.free(p.mr)
        self.allocated -= len(pages)
        return len(pages)

    def evict_pages(self, seq_id: int, pages: List[Page]) -> int:
        """Partial eviction: free exactly ``pages`` of ``seq_id`` (the
        rollback path of a failed migration/fetch). Pages not present
        are ignored. Returns how many were freed."""
        live = self.pages.get(seq_id, [])
        doomed = {id(p) for p in pages}
        keep, freed = [], 0
        for p in live:
            if id(p) in doomed:
                self.pool.free(p.mr)
                freed += 1
            else:
                keep.append(p)
        if keep:
            self.pages[seq_id] = keep
        else:
            self.pages.pop(seq_id, None)
        self.allocated -= freed
        return freed

    def seq_len_pages(self, seq_id: int) -> int:
        return len(self.pages.get(seq_id, []))


def _drive_completions(engine, qp, wanted, max_flushes: int = 64) -> dict:
    """Collect one CQE per wr_id in ``wanted`` from ``qp``'s CQ,
    driving ``engine.flush_doorbells`` between polls so the reliability
    layer's retransmission timers advance (a silently dropped READ is
    only replayed ``timeout_flushes`` flushes later). Stale CQEs (other
    wr_ids) are skipped. Terminates without the full set only at
    ``max_flushes`` — unreached in practice, because retry exhaustion
    surfaces terminal CQEs (RETRY_EXC / WR_FLUSH drain) for every
    outstanding WQE instead of hanging."""
    wanted = set(wanted)
    got: dict = {}
    batch = 4 * len(wanted) + 16
    for _ in range(max_flushes):
        for cqe in engine.poll_cq(qp, max_entries=batch):
            if cqe.wr_id in wanted and cqe.wr_id not in got:
                got[cqe.wr_id] = cqe.status
        if len(got) == len(wanted):
            return got
        engine.flush_doorbells()
    for cqe in engine.poll_cq(qp, max_entries=batch):
        if cqe.wr_id in wanted and cqe.wr_id not in got:
            got[cqe.wr_id] = cqe.status
    return got


def migrate_sequence(engine, router, src_pool: PagedKVPool,
                     dst_pool: PagedKVPool, seq_id: int, qp,
                     max_flushes: int = 64) -> int:
    """Move all pages of ``seq_id`` src->dst as ONE doorbell batch of
    RDMA READs (the paper's batch-requests applied to KV migration),
    reliability-aware:

      * each page's READ is tracked to its own CQE; a source page is
        evicted ONLY on SUCCESS — error CQEs (RETRY_EXC_ERROR after the
        PR-6 retry budget, WR_FLUSH_ERROR drains, REMOTE_ACCESS_ERROR)
        leave it in place and roll the matching destination page back;
      * destination exhaustion mid-batch (``MemoryError``) aborts the
        unrung doorbell (no half-built batch executes), rolls back the
        pages already allocated, and re-raises — the source is intact;
      * a QP driven to ERROR is surfaced, not hidden: the failed pages
        stay at the source and the caller decides (``engine.recover_qp``
        + retry, or reroute).

    Partial success leaves the sequence split across the pools; the
    destination mirrors each source page's ``page_idx``, so a retry of
    the remainder slots in cleanly. Returns pages actually migrated.
    """
    src_pages = src_pool.pages.get(seq_id, [])
    if not src_pages:
        return 0
    assert src_pool.page_words == dst_pool.page_words, \
        "src/dst pools disagree on the page wire format"
    router.route([TransferDesc(TrafficClass.KV_PAGE, p.nbytes,
                               src=src_pool.peer, dst=dst_pool.peer)
                  for p in src_pages])

    dst_pages: List[Page] = []
    tokens: Dict[int, int] = {}          # wr_id token -> batch index
    try:
        with DoorbellCoalescer(engine, qp,
                               flush_threshold=len(src_pages)) as db:
            for i, p in enumerate(src_pages):
                dp = dst_pool.append_page(seq_id, page_idx=p.page_idx)
                dst_pages.append(dp)
                tok = next(_wr_tokens)
                tokens[tok] = i
                db.post(WQE(Opcode.READ, qp.qp_num, wr_id=tok,
                            local_addr=dp.mr.base, remote_addr=p.mr.base,
                            length=p.mr.length, rkey=p.mr.rkey))
    except MemoryError:
        # The coalescer aborted the unrung tail on our way out, so none
        # of the posted READs can ever execute: roll back the partially
        # allocated destination and leave the source untouched.
        dst_pool.evict_pages(seq_id, dst_pages)
        raise

    statuses = _drive_completions(engine, qp, tokens, max_flushes)
    moved, failed_dst = [], []
    for tok, i in tokens.items():
        if statuses.get(tok) is CQEStatus.SUCCESS:
            moved.append(src_pages[i])
        else:
            failed_dst.append(dst_pages[i])
    dst_pool.evict_pages(seq_id, failed_dst)
    src_pool.evict_pages(seq_id, moved)
    led = _ledger(engine)
    led["migrations"] += 1
    led["pages_migrated"] += len(moved)
    led["pages_rolled_back"] += len(failed_dst)
    return len(moved)


# ---------------------------------------------------------------------------
# Decode workers as transport clients
# ---------------------------------------------------------------------------

class KVFetchError(RuntimeError):
    """A sequence fetch that could not be completed; ``statuses`` maps
    the failed wr_id tokens to their terminal CQE statuses."""

    def __init__(self, msg: str, statuses: Optional[dict] = None):
        super().__init__(msg)
        self.statuses = dict(statuses or {})


@dataclass
class KVTenant:
    """One serving tenant: its own QP whose scheduler ``weight`` is the
    SLO tier (a weight-w tenant is offered w WQEs per DRR round when
    fetches from several tenants share a flush)."""
    name: str
    qp: object
    weight: int


@dataclass
class FetchTicket:
    """One in-flight sequence fetch: n one-sided READs on the tenant's
    QP, one wr_id token per page. ``issued_flush``/``done_flush`` stamp
    the engine flush counter — the open-loop bench's deterministic
    "clock" for tail latency."""
    tenant: KVTenant
    seq_id: int
    pages: List[Page]
    stage: object                       # local staging MR
    tokens: Dict[int, tuple]            # token -> (page i, offset, words)
    statuses: Dict[int, CQEStatus] = field(default_factory=dict)
    data: Optional[np.ndarray] = None   # (n_pages, page_elems) on success
    issued_flush: int = 0
    done_flush: int = 0

    @property
    def outstanding(self) -> int:
        return len(self.tokens) - len(self.statuses)

    @property
    def failed(self) -> List[int]:
        return [tok for tok, st in self.statuses.items()
                if st is not CQEStatus.SUCCESS]


class RemoteKVClient:
    """A decode worker's transport-client view of a remote PagedKVPool.

    Fetches ride one-sided READ WQEs on per-tenant QPs into a local
    staging BufferPool; pages are pow2-sized chunks, so steady-state
    fetches reuse the descriptor executor's warmed shape buckets (zero
    XLA compiles — CI-gated). ``advance`` is the non-blocking completion
    pump for open-loop serving loops; ``complete`` is the closed-loop
    wrapper that also recovers errored QPs on request. Everything is
    ledgered in ``engine.stats["kv_serve"]``.
    """

    def __init__(self, engine, local_peer: int, pool: PagedKVPool,
                 router=None, staging_size: Optional[int] = None):
        self.engine = engine
        self.local_peer = local_peer
        self.pool = pool                     # the REMOTE pool
        self.router = router
        self.staging = BufferPool(engine, local_peer, size=staging_size)
        self.tenants: Dict[str, KVTenant] = {}
        self._outstanding: Dict[str, List[FetchTicket]] = {}

    # --------------------------------------------------------- tenants
    def register_tenant(self, name: str, weight: int = 1) -> KVTenant:
        qp = self.engine.create_qp(self.local_peer, self.pool.peer,
                                   weight=weight)
        tenant = KVTenant(name, qp, weight)
        self.tenants[name] = tenant
        return tenant

    def _tenant(self, tenant) -> KVTenant:
        return (self.tenants[tenant] if isinstance(tenant, str)
                else tenant)

    # --------------------------------------------------------- fetches
    def fetch_sequence(self, tenant, seq_id: int,
                       defer: bool = False) -> FetchTicket:
        """Post one READ per page of ``seq_id`` on the tenant's QP and
        ring ONE doorbell (``defer=True`` arms it for the next shared
        flush — the open-loop mode). Staging exhaustion raises
        ``MemoryError`` — the caller's admission-control point."""
        t = self._tenant(tenant)
        pages = self.pool.pages.get(seq_id)
        if not pages:
            raise KeyError(f"seq {seq_id} has no pages in the remote "
                           f"pool on peer {self.pool.peer}")
        total = sum(p.mr.length for p in pages)
        stage = self.staging.alloc(total)
        tokens: Dict[int, tuple] = {}
        off = 0
        for i, p in enumerate(pages):
            tok = next(_wr_tokens)
            tokens[tok] = (i, off, p.mr.length)
            self.engine.post_send(t.qp, WQE(
                Opcode.READ, t.qp.qp_num, wr_id=tok,
                local_addr=stage.base + off, remote_addr=p.mr.base,
                length=p.mr.length, rkey=p.mr.rkey))
            off += p.mr.length
        self.engine.ring_sq_doorbell(t.qp, defer=defer)
        if self.router is not None:
            self.router.route([TransferDesc(
                TrafficClass.KV_PAGE, p.nbytes,
                src=self.pool.peer, dst=self.local_peer)
                for p in pages])
        led = _ledger(self.engine)
        led["fetches"] += 1
        led["pages_posted"] += len(pages)
        led["posted_words"] += total
        ticket = FetchTicket(t, seq_id, list(pages), stage, tokens,
                             issued_flush=self.engine.stats["flushes"])
        self._outstanding.setdefault(t.name, []).append(ticket)
        return ticket

    def advance(self, tenant) -> List[FetchTicket]:
        """Non-blocking completion pump (the open-loop serving loop's
        per-tick call): drain the tenant's CQ, credit statuses to its
        in-flight tickets, finalize the fully-resolved ones. A ticket
        whose READs all landed SUCCESS carries its (dequantized)
        payload in ``.data``; one with failures carries ``data=None``.
        Staging is freed either way. Returns the finalized tickets."""
        t = self._tenant(tenant)
        live = self._outstanding.get(t.name, [])
        if not live:
            return []
        by_tok = {tok: tk for tk in live for tok in tk.tokens
                  if tok not in tk.statuses}
        for cqe in self.engine.poll_cq(t.qp,
                                       max_entries=len(by_tok) + 64):
            tk = by_tok.get(cqe.wr_id)
            if tk is not None and cqe.wr_id not in tk.statuses:
                tk.statuses[cqe.wr_id] = cqe.status
        finished = [tk for tk in live if tk.outstanding == 0]
        if finished:
            self._outstanding[t.name] = [tk for tk in live
                                         if tk.outstanding]
            for tk in finished:
                self._finalize(tk)
        return finished

    def _finalize(self, tk: FetchTicket) -> None:
        led = _ledger(self.engine)
        tk.done_flush = self.engine.stats["flushes"]
        if not tk.failed:
            raw = self.engine.read_buffer(self.local_peer,
                                          tk.stage.base, tk.stage.length)
            rows = raw.reshape(len(tk.pages), self.pool.page_words)
            if self.pool.compressed:
                rows = np.stack([
                    quant_unpack_page(r, self.pool.page_elems)
                    for r in rows])
            tk.data = rows
            led["pages_fetched"] += len(tk.pages)
            led["completed"] += 1
        else:
            led["pages_failed"] += len(tk.failed)
            led["failed"] += 1
        self.staging.free(tk.stage)

    def _wait(self, ticket: FetchTicket, max_flushes: int) -> bool:
        for _ in range(max_flushes):
            self.advance(ticket.tenant)
            if ticket.outstanding == 0:
                return True
            self.engine.flush_doorbells()
        self.advance(ticket.tenant)
        return ticket.outstanding == 0

    def complete(self, ticket: FetchTicket, max_flushes: int = 64,
                 recover: bool = False) -> np.ndarray:
        """Drive engine flushes until ``ticket`` resolves; return its
        (n_pages, page_elems) payload. On failed READs: with
        ``recover=True`` the errored QP is re-armed (``recover_qp``,
        fresh PSN epoch) and the sequence fetched once more — the
        transient-fault path; otherwise (or when the retry fails too)
        the error surfaces as ``KVFetchError``. Source pages are never
        touched by a fetch, so no data is ever lost here."""
        if not self._wait(ticket, max_flushes):
            raise KVFetchError(
                f"fetch of seq {ticket.seq_id} unresolved after "
                f"{max_flushes} flushes", ticket.statuses)
        if ticket.data is not None:
            return ticket.data
        failed = {tok: ticket.statuses[tok] for tok in ticket.failed}
        if not recover:
            raise KVFetchError(
                f"fetch of seq {ticket.seq_id}: {len(failed)}/"
                f"{len(ticket.tokens)} pages failed "
                f"({sorted(st.value for st in failed.values())})", failed)
        self.engine.recover_qp(ticket.tenant.qp)
        _ledger(self.engine)["recoveries"] += 1
        retry = self.fetch_sequence(ticket.tenant, ticket.seq_id)
        if not self._wait(retry, max_flushes) or retry.data is None:
            raise KVFetchError(
                f"fetch of seq {ticket.seq_id} failed again after QP "
                "recovery", retry.statuses)
        ticket.data = retry.data
        return retry.data

    # ------------------------------------------- cache pytree plumbing
    def publish_caches(self, seq_id: int, caches) -> int:
        """Prefill-node role: flatten a KV-cache pytree into pages of
        the remote pool (zero-padded to the page boundary), staged over
        the QDMA pow2 chunk-bucketed path. Returns pages written."""
        flat = flatten_cache_leaves(caches)
        pe = self.pool.page_elems
        n_pages = max(1, -(-int(flat.size) // pe))
        padded = np.zeros(n_pages * pe, np.float32)
        padded[:flat.size] = flat
        for i in range(n_pages):
            page = self.pool.append_page(seq_id)
            self.pool.write_page(page, padded[i * pe:(i + 1) * pe])
        return n_pages

    def fetch_caches(self, seq_id: int, like, tenant, **kw):
        """Decode-node role: fetch ``seq_id``'s pages over one-sided
        READs and rebuild a cache pytree shaped ``like`` (bit-exact for
        uncompressed f32 pools; int8-quantized for compressed ones)."""
        ticket = self.fetch_sequence(tenant, seq_id)
        data = self.complete(ticket, **kw)
        return unflatten_cache_leaves(data.reshape(-1), like)

    def roundtrip_caches(self, seq_id: int, caches, tenant,
                         evict: bool = True, **kw):
        """publish -> fetch: the prefill-node -> decode-node handoff of
        one sequence's caches through the remote pool."""
        self.publish_caches(seq_id, caches)
        out = self.fetch_caches(seq_id, caches, tenant, **kw)
        if evict:
            self.pool.evict(seq_id)
        return out


def flatten_cache_leaves(caches) -> np.ndarray:
    """Flatten a cache pytree to one f32 vector (leaf order = jax tree
    order). Integer leaves (positions) are small enough to be exact in
    f32."""
    import jax
    leaves = jax.tree_util.tree_leaves(caches)
    if not leaves:
        return np.zeros(0, np.float32)
    return np.concatenate([np.asarray(x, np.float32).reshape(-1)
                           for x in leaves])


def unflatten_cache_leaves(flat: np.ndarray, like):
    """Rebuild a pytree shaped/dtyped ``like`` from the flat f32 vector
    (inverse of ``flatten_cache_leaves``; trailing page padding is
    ignored)."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for leaf in leaves:
        a = np.asarray(leaf)
        n = int(a.size)
        vals = np.asarray(flat[off:off + n],
                          np.float32).reshape(a.shape)
        out.append(jnp.asarray(vals.astype(a.dtype)))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)
