"""Compile the main path's programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described and not attached. It refuses what Mosaic or the
chip's memory would refuse (misaligned kernel blocks, too much VMEM, a
program larger than HBM), which interpret-mode tests cannot show. Sizes are
those ``chip_smoke.py`` runs on the chip.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under pytest-xdist
only the worker given this file does.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core.rdma.transport import _exec_descriptors_local, _exec_staging
from repro.kernels import lc_offload
from repro.kernels import ops as kops
from repro.kernels.packet_parser import HDR_BYTES
from repro.kernels.quantize_stream import dequantize_stream, quantize_stream
from repro.models.transformer import init_caches, init_params
from repro.serve.serve_step import decode_step, prefill_step

#: HBM of one v5e chip
HBM_BYTES = 16 * 2 ** 30
#: sizes of chip_smoke.py
POOL_WORDS = 1 << 26
MM = 1024
N_PKTS = 4096
N_REQ, PROMPT, GEN = 8, 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Trace the backend-switched programs as they are traced on a TPU."""
    monkeypatch.setattr(kops, "_interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kind", ["quantize", "dequantize"])
def test_quantize_stream_compiles(one_chip, kind):
    rows, chunk = N_PKTS, 64
    if kind == "quantize":
        text = _kernel_text(lambda x: quantize_stream(x, chunk=chunk),
                            _spec((rows, chunk), jnp.float32, one_chip))
    else:
        text = _kernel_text(dequantize_stream,
                            _spec((rows, chunk), jnp.int8, one_chip),
                            _spec((rows, 1), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [1, 100, 1024])
def test_stream_bucket_programs_compile(one_chip, mosaic, rows):
    """The cached per-bucket programs the KV pool and stream handlers run
    (odd row counts exercise the row padding)."""
    bp = lc_offload._next_pow2(rows)
    q = _kernel_text(lc_offload._stream_quant.__wrapped__(bp),
                     _spec((bp, HDR_BYTES), jnp.float32, one_chip))
    d = _kernel_text(lc_offload._stream_dequant.__wrapped__(bp),
                     _spec((bp, HDR_BYTES), jnp.int8, one_chip),
                     _spec((bp, 1), jnp.float32, one_chip))
    assert "tpu_custom_call" in q and "tpu_custom_call" in d


def test_parse_packet_fields_compiles(one_chip, mosaic):
    text = _kernel_text(kops.classify_packet_fields.__wrapped__,
                        _spec((N_PKTS, HDR_BYTES), jnp.uint8, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(MM, MM, MM), (100, 600, 130)])
def test_offload_matmul_compiles(one_chip, mosaic, shape):
    """``lc_systolic_mm``'s program at the smoke's size, and an unaligned
    shape whose dims above ``MAX_WHOLE_DIM`` are padded to 128-blocks."""
    m, k, n = shape
    text = _kernel_text(lc_offload._mm_program.__wrapped__(m, k, n),
                        _spec((m, k), jnp.float32, one_chip),
                        _spec((k, n), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("program", ["descriptors", "staging"])
def test_pool_executors_fit_hbm(one_chip, program):
    pool = _spec((2, POOL_WORDS), jnp.float32, one_chip)
    chunk = 1 << 18
    if program == "descriptors":
        lowered = _exec_descriptors_local.lower(
            pool, _spec((8, 5), jnp.int32, one_chip), chunk=chunk)
    else:
        lowered = _exec_staging.lower(
            pool, _spec((chunk,), jnp.float32, one_chip),
            _spec((3,), jnp.int32, one_chip), chunk=chunk)
    ma = lowered.compile().memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("chunk", [16, 1 << 18])
def test_descriptor_executor_temp_is_window_sized(one_chip, chunk):
    """The benchmark's two-peer pool of 2^28 words a peer: each WQE moves
    through a chunk-sized window, so the temp stays far below the 2 GiB
    pool (a lane scatter over the flattened pool needs 3 pools of it)."""
    lowered = _exec_descriptors_local.lower(
        _spec((2, 1 << 28), jnp.float32, one_chip),
        _spec((64, 5), jnp.int32, one_chip), chunk=chunk)
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2 ** 20, temp


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_tinyllama_serve_step_compiles(one_chip, step):
    cfg = get_config("tinyllama-1.1b")

    def specs(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                            tree)

    params = specs(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    caches = specs(jax.eval_shape(
        lambda: init_caches(cfg, N_REQ, PROMPT + GEN, jnp.bfloat16)))
    if step == "prefill":
        fn = jax.jit(lambda p, t, c: prefill_step(p, cfg, {"tokens": t}, c))
        args = (params, _spec((N_REQ, PROMPT), jnp.int32, one_chip), caches)
    else:
        fn = jax.jit(lambda p, t, c, pos: decode_step(p, cfg, t, c, pos))
        args = (params, _spec((N_REQ, 1), jnp.int32, one_chip), caches,
                _spec((), jnp.int32, one_chip))
    ma = fn.lower(*args).compile().memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < HBM_BYTES
