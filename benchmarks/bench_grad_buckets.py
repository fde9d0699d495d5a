"""Doorbell batching -> training analogue (the paper's §VI-C insight in
its distributed-training role): per-tensor gradient all-reduce
("single-request") vs bucketed sync ("batch-requests").

Two measurements:
  1. alpha-beta model: predicted sync time vs bucket size for real model
     grad-size distributions (all 10 assigned archs).
  2. dispatch counts: actual all-reduce ops in the lowered bucketed train
     step at two bucket sizes (tiny model, 8 host devices, subprocess).
"""
import json
import os
import subprocess
import sys

from repro.configs.registry import ARCHS, get_config
from repro.core.rdma.cost_model import TPU_V5E
from repro.core.rdma.doorbell import (choose_bucket_bytes, plan_buckets,
                                      predicted_sync_time)

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _leaf_sizes(arch: str):
    """PER-LAYER grad tensor byte sizes (the granularity a DDP-style
    framework dispatches at): scan-stacked leaves are unstacked into
    their per-layer tensors."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_params
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        functools.partial(init_params, cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    sizes = []
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        stacked = any(s in path for s in ("layers/", "enc_layers/",
                                          "dec_layers/"))
        if stacked and leaf.ndim >= 1:
            per_layer = leaf.size // leaf.shape[0] * leaf.dtype.itemsize
            sizes.extend([per_layer] * leaf.shape[0])
        else:
            sizes.append(leaf.size * leaf.dtype.itemsize)
    return sizes


def run(verbose: bool = True):
    rows = []
    n_dev = 512
    hw = TPU_V5E
    for arch in list(ARCHS):
        sizes = _leaf_sizes(arch)
        t_single = predicted_sync_time(len(sizes), sum(sizes), n_dev,
                                       hw.alpha_dispatch,
                                       hw.ici_bw_per_link)
        best_bytes, t_best = choose_bucket_bytes(
            sizes, n_dev, hw.alpha_dispatch, hw.ici_bw_per_link)
        n_buckets = len(plan_buckets(sizes, best_bytes or sum(sizes)))
        # dispatch ("doorbell") overhead eliminated by coalescing
        saved = (len(sizes) - n_buckets) * hw.alpha_dispatch
        overhead_frac = len(sizes) * hw.alpha_dispatch / t_single
        rows.append((f"grad_sync_{arch}", t_best * 1e6,
                     f"tensors={len(sizes)},buckets={n_buckets},"
                     f"single={t_single*1e3:.2f}ms,"
                     f"bucketed={t_best*1e3:.2f}ms,"
                     f"dispatch_saved={saved*1e3:.2f}ms,"
                     f"dispatch_frac={overhead_frac:.3f}"))
        assert t_best <= t_single
    if verbose:
        for n, us, d in rows:
            print(f"{n},{us:.3f},{d}")
    return rows


def run_dispatch_counts(verbose: bool = True):
    """Lower the bucketed step twice and count all-reduces (subprocess
    with 8 host devices)."""
    code = """
import jax, jax.numpy as jnp, re
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.train import init_adam
from repro.train.train_step import make_bucketed_train_step
cfg = get_config('tiny')
mesh = make_mesh((8,), ('data',))
out = {}
for mb in [0.0625, 64.0]:
    tcfg = TrainConfig(remat=False, zero1=False, sequence_parallel=False,
                       grad_bucket_mb=mb)
    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = init_adam(params)
        res = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        step = make_bucketed_train_step(cfg, tcfg, mesh)
        batch = {'tokens': jnp.zeros((8, 32), jnp.int32),
                 'labels': jnp.zeros((8, 32), jnp.int32)}
        txt = jax.jit(step).lower(params, opt, batch, res).as_text()
        out[str(mb)] = len(re.findall(r'all_reduce|all-reduce', txt))
import json
print('RESULT ' + json.dumps(out))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            counts = json.loads(line[len("RESULT "):])
            small, big = counts["0.0625"], counts["64.0"]
            ok = small > big
            rows.append(("grad_bucket_dispatches", 0.0,
                         f"64KB_buckets={small},64MB_buckets={big},"
                         f"fewer_with_batching={'PASS' if ok else 'FAIL'}"))
            assert ok, counts
    if not rows:
        rows.append(("grad_bucket_dispatches", 0.0,
                     f"SKIP:{r.stderr[-200:]}"))
    if verbose:
        for n, us, d in rows:
            print(f"{n},{us:.3f},{d}")
    return rows
