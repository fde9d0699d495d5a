"""Per-kernel correctness: shape/dtype sweeps, Pallas (interpret=True) vs
the pure-jnp oracles in ``repro.kernels.ref``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.packet_parser import HDR_BYTES, parse_packets
from repro.kernels.quantize_stream import dequantize_stream, quantize_stream
from repro.kernels.systolic_mm import systolic_mm

RNG = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# systolic_mm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (128, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_systolic_mm_aligned(m, k, n, dtype):
    x, y = randn((m, k), dtype), randn((k, n), dtype)
    got = systolic_mm(x, y, interpret=True)
    want = ref.ref_matmul(x, y)
    # fp32 tolerance scales with the K-dim accumulation reassociation
    tol = 1e-5 * (k / 128) if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m,k,n", [(50, 70, 30), (1, 128, 5), (200, 33, 17)])
def test_matmul_unaligned_padding(m, k, n):
    x, y = randn((m, k)), randn((k, n))
    np.testing.assert_allclose(ops.matmul(x, y), ref.ref_matmul(x, y),
                               rtol=2e-5, atol=2e-5)


def test_systolic_mm_rejects_unaligned():
    with pytest.raises(AssertionError):
        systolic_mm(randn((100, 128)), randn((128, 128)), interpret=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,blocks", [(128, 128, (64, 64)),
                                           (256, 256, (128, 64)),
                                           (64, 192, (32, 64))])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(sq, skv, blocks, causal):
    if causal and sq != skv:
        pytest.skip("causal requires square here")
    bq, bk = blocks
    q, k, v = randn((3, sq, 16)), randn((3, skv, 16)), randn((3, skv, 16))
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_sliding_window():
    q, k, v = randn((2, 128, 8)), randn((2, 128, 8)), randn((2, 128, 8))
    got = flash_attention(q, k, v, causal=True, window=32, block_q=32,
                          block_k=32, interpret=True)
    want = ref.ref_attention(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_attention_gqa(hq, hkv):
    q = randn((2, 64, hq, 16))
    k, v = randn((2, 64, hkv, 16)), randn((2, 64, hkv, 16))
    got = ops.attention(q, k, v, causal=True, block_q=32, block_k=32)
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    want = ref.ref_attention(
        q.transpose(0, 2, 1, 3).reshape(2 * hq, 64, 16),
        kr.transpose(0, 2, 1, 3).reshape(2 * hq, 64, 16),
        vr.transpose(0, 2, 1, 3).reshape(2 * hq, 64, 16),
        causal=True).reshape(2, hq, 64, 16).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_attention_bf16():
    q, k, v = (randn((2, 64, 2, 16), jnp.bfloat16) for _ in range(3))
    got = ops.attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# quantize stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,chunk", [(4, 64), (16, 128), (1, 256),
                                     (1030, 64), (70, 1024)])
def test_quantize_roundtrip_error_bound(n, chunk):
    x = randn((n, chunk))
    q, s = quantize_stream(x, chunk=chunk, interpret=True)
    qr, sr = ref.ref_quantize(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(s, sr, rtol=1e-6)
    back = dequantize_stream(q, s, interpret=True)
    # error bounded by half an int8 step per chunk
    bound = np.asarray(s)[:, 0] * 0.5 + 1e-7
    err = np.max(np.abs(np.asarray(back) - np.asarray(x)), axis=1)
    assert (err <= bound).all()


def test_quantize_zero_chunk():
    x = jnp.zeros((2, 64))
    q, s = quantize_stream(x, chunk=64, interpret=True)
    assert np.all(np.asarray(q) == 0)
    back = dequantize_stream(q, s, interpret=True)
    assert np.all(np.asarray(back) == 0)


def test_compress_decompress_pytree_shapes():
    x = randn((777,))
    q, s, n = ops.compress(x, chunk=64)
    assert n == 777
    back = ops.decompress(q, s, (777,))
    assert back.shape == (777,)


# ---------------------------------------------------------------------------
# SSD scan (mamba-2 chunked state-space kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,chunk", [(32, 16), (64, 16), (48, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_vs_oracle(s, chunk, dtype):
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.ssm import _ssd_chunked
    b, nh, hd, n = 2, 4, 16, 32
    xh = randn((b, s, nh, hd), dtype)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, (b, s, nh)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (nh,)), jnp.float32)
    B = randn((b, s, 1, n))
    C = randn((b, s, 1, n))
    got = ssd_scan(xh, dt, a, B, C, chunk=chunk, interpret=True)
    want, _ = _ssd_chunked(xh.astype(jnp.float32), dt, a, B, C, chunk)
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# packet parser
# ---------------------------------------------------------------------------

def _mk(opcode, qp, rdma=True):
    from repro.core.streaming.classifier import make_roce_header
    return make_roce_header(opcode, qp, is_rdma=rdma)


def test_packet_parser_vs_ref():
    pkts = np.stack([_mk(12, 7), _mk(0, 1), _mk(6, 2), _mk(17, 3),
                     _mk(13, 4), _mk(0, 0, rdma=False)] * 4)
    got = parse_packets(jnp.asarray(pkts), block_p=8, interpret=True)
    want = ref.ref_parse_packets(jnp.asarray(pkts))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packet_classes():
    from repro.kernels.packet_parser import (
        CLS_ACK, CLS_NON_RDMA, CLS_READ_REQ, CLS_READ_RESP, CLS_SEND,
        CLS_WRITE)
    pkts = np.stack([_mk(0, 1), _mk(6, 1), _mk(12, 1), _mk(13, 1),
                     _mk(17, 1), _mk(12, 1, rdma=False), _mk(3, 1),
                     _mk(10, 1)])
    meta = np.asarray(ops.classify_packets(jnp.asarray(pkts)))
    assert list(meta[:, 3]) == [CLS_SEND, CLS_WRITE, CLS_READ_REQ,
                                CLS_READ_RESP, CLS_ACK, CLS_NON_RDMA,
                                CLS_SEND, CLS_WRITE]
    assert meta[2, 2] == 1  # dest_qp parsed
    assert meta[5, 0] == 0  # non-rdma flag
