"""In-flight gradient compression with error feedback (Streaming Compute).

The SC block's training-system role: compress gradient buckets to int8 as
they stream into the cross-pod all-reduce, keeping a local fp32 residual
(error feedback) so compression noise does not bias convergence.

The pure functions jit/pjit cleanly inside the train step (state threaded
explicitly). ``GradEgressChain`` is the same compression expressed as the
dispatch plane's first PRODUCTION service chain: gradient rows stream
through a compress→checksum ``Chain`` on the datapath — the compress
stage int8-quantizes each 64-lane row (byte parity with
``kops.compress(x, chunk=64)``), its RDMA write-back region feeds the
checksum stage's fetch, and the error-feedback residual is computed from
the ACTUAL wire bytes read back from the chain's output rings, so what
the residual corrects is exactly what the fabric carried.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lookaside.registry import LookasideBlock
from repro.core.streaming.dispatch import Chain, MatchTable, StreamDispatcher
from repro.core.streaming.rx_ring import RXRing
from repro.kernels import ops as kops
from repro.kernels.lc_offload import (CHAIN_CHECKSUM_WORKLOAD,
                                      CHAIN_COMPRESS_WORKLOAD, CSUM_ROW,
                                      HDR_BYTES, QUANT_ROW, _checksum_rows,
                                      register_chain_kernels)


def init_error_state(grads) -> dict:
    """Residual pytree, same structure/shape as grads, fp32 zeros."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_bucket(flat: jax.Array, residual: jax.Array, *,
                    chunk: int = 1024
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize (flat + residual) to int8 chunks; new residual = error.

    Returns (q_int8 (n,chunk), scales (n,1), new_residual).
    """
    target = flat.astype(jnp.float32) + residual
    q, s, _ = kops.compress(target, chunk=chunk)
    back = kops.decompress(q, s, target.shape, dtype=jnp.float32)
    return q, s, target - back


def decompress_bucket(q: jax.Array, scales: jax.Array, shape,
                      dtype=jnp.float32) -> jax.Array:
    return kops.decompress(q, scales, shape, dtype=dtype)


def compressed_all_reduce(flat: jax.Array, residual: jax.Array,
                          axis: str, *, chunk: int = 1024
                          ) -> Tuple[jax.Array, jax.Array]:
    """Compress -> psum(int8 as int32) -> dequant mean. Inside shard_map.

    int8 payloads psum as int32 (no overflow below ~2^23 peers); scales are
    psum'd too so the dequant uses the mean scale — a standard 1-bit/8-bit
    SGD style estimator with error feedback carrying the bias.
    """
    n = jax.lax.psum(1, axis)
    q, s, new_residual = compress_bucket(flat, residual, chunk=chunk)
    q_sum = jax.lax.psum(q.astype(jnp.int32), axis)
    s_mean = jax.lax.psum(s, axis) / n
    # mean over peers: (sum_i q_i * s_i) ~= s_mean * sum_i q_i  / n
    est = (q_sum.astype(jnp.float32) * s_mean / n)
    out = est.reshape(-1)[: flat.shape[0]].astype(flat.dtype)
    return out, new_residual


def compression_ratio(nbytes_fp32: int, chunk: int = 1024) -> float:
    """Wire-bytes ratio: int8 payload + fp32 scale per chunk vs fp32."""
    n_chunks = -(-nbytes_fp32 // 4 // chunk)
    compressed = nbytes_fp32 // 4 + n_chunks * 4
    return compressed / nbytes_fp32


class GradEgressChain:
    """compress→checksum gradient egress as a datapath service chain.

    Wiring: a 64-word-slot ``RXRing`` on the LC peer receives gradient
    rows; a two-stage ``Chain`` (``chain_compress`` → ``chain_checksum``)
    is the ring's DEFAULT owner, so every pushed row belongs to it. One
    ``dispatcher.service()`` pass per window drives both stages — the
    compress stage's [q ‖ scale] write-back rows land slot-mirrored at
    ``out_base`` on ``data_peer`` and are the checksum stage's fetch
    source; its [checksum, width] rows land after them. Every stage
    gather/write-back shares the engine's descriptor tables with
    whatever host verbs traffic is armed (``stats["dispatch"]["chains"]``
    ledgers the pipeline).

    ``compress()`` then reads the wire bytes BACK from the chain's
    output rings to form the error-feedback residual — the estimator
    corrects exactly what the fabric carried, checksum-stamped.
    """

    def __init__(self, engine, *, data_peer: int, ring_base: int,
                 out_base: int, lc_peer: int = 0, depth: int = 32,
                 burst: int = 8, block: "LookasideBlock" = None,
                 scratch_base: int = None, scratch_size: int = None,
                 pipeline_depth: int = 4, name: str = "grad_egress"):
        self.engine = engine
        self.data_peer = data_peer
        if block is None:
            block = LookasideBlock(engine, peer=lc_peer,
                                   scratch_base=scratch_base,
                                   scratch_size=scratch_size,
                                   eager_writeback=False,
                                   pipeline_depth=pipeline_depth)
            register_chain_kernels(block)
        self.block = block
        self.ring = RXRing(engine, peer=block.peer, base=ring_base,
                           depth=depth, slot_bytes=HDR_BYTES)
        self.q_base = out_base
        self.csum_base = out_base + depth * QUANT_ROW
        self.out_mr = engine.register_mr(
            data_peer, out_base, depth * (QUANT_ROW + CSUM_ROW))
        self.chain = Chain((CHAIN_COMPRESS_WORKLOAD,
                            CHAIN_CHECKSUM_WORKLOAD), name=name)
        self.dispatcher = StreamDispatcher(
            block, self.ring, MatchTable(default=self.chain), burst=burst)
        self.dispatcher.register_chain(self.chain, data_peer,
                                       self.out_mr.rkey,
                                       [self.q_base, self.csum_base])
        self._seq = 0                    # rows pushed since construction

    def compress(self, flat, residual
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Stream one bucket through the chain in ring-sized windows.

        Returns ``(q int8 (rows, 64), scales (rows, 1), checksums
        (rows,), new_residual (n,))`` — byte-compatible with
        ``compress_bucket(flat, residual, chunk=64)``'s (q, s) plus the
        wire-integrity stamps, the residual formed from the read-back
        wire bytes."""
        target = (np.asarray(flat, np.float32).reshape(-1)
                  + np.asarray(residual, np.float32).reshape(-1))
        n = target.shape[0]
        rows = -(-n // HDR_BYTES)
        padded = np.zeros(rows * HDR_BYTES, np.float32)
        padded[:n] = target
        batch = padded.reshape(rows, HDR_BYTES)
        depth = self.ring.depth
        q_rows = np.empty((rows, QUANT_ROW), np.float32)
        c_rows = np.empty((rows, CSUM_ROW), np.float32)
        done = 0
        while done < rows:
            take = min(depth, rows - done)
            for r in range(done, done + take):
                if not self.ring.push(batch[r]):
                    raise RuntimeError("egress ring refused a row "
                                       "(window exceeds ring depth?)")
            self.dispatcher.service()
            for r in range(done, done + take):
                slot = (self._seq + r) % depth
                q_rows[r] = self.engine.read_buffer(
                    self.data_peer, self.q_base + slot * QUANT_ROW,
                    QUANT_ROW)
                c_rows[r] = self.engine.read_buffer(
                    self.data_peer, self.csum_base + slot * CSUM_ROW,
                    CSUM_ROW)
            done += take
        self._seq += rows
        q = q_rows[:, :HDR_BYTES].astype(np.int8)
        s = q_rows[:, HDR_BYTES:].astype(np.float32)
        back = np.asarray(kops.decompress(
            jnp.asarray(q), jnp.asarray(s), (rows * HDR_BYTES,)))
        new_residual = target - back[:n]
        return q, s, c_rows[:, 0].copy(), new_residual

    @staticmethod
    def verify_checksums(q: np.ndarray, s: np.ndarray,
                         checksums: np.ndarray) -> bool:
        """Recompute the integrity stamps host-side from (q, s) wire
        rows and compare — what a receiver does before trusting a
        compressed bucket."""
        rows = np.concatenate([np.asarray(q, np.float32),
                               np.asarray(s, np.float32)], axis=1)
        return bool(np.array_equal(_checksum_rows(rows)[:, 0],
                                   np.asarray(checksums, np.float32)))
