"""Faults planted under the timed path, and the controls, for showing that
the comparison which decides ``correct`` fails when it should.

Each is a context manager that patches the program for its duration. The
benchmark's own runs never plant one: ``control.py`` does, on the chip at a
cell's own size, and ``test_chipbench_faults.py`` at a tiny size.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np


@contextlib.contextmanager
def patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _both_transports(attr: str, wrap):
    from repro.core.rdma import transport as tp
    stack = contextlib.ExitStack()
    for cls in (tp.LocalTransport, tp.ICITransport):
        stack.enter_context(patched(cls, attr, wrap(getattr(cls, attr))))
    return stack


def unchanged():
    """A step that returns its state unchanged: the executor leaves the
    pool as it was."""
    return _both_transports("_run_descriptors",
                            lambda orig: lambda self, desc, chunk: None)


def half_batch():
    """Half of each doorbell's transfers left out."""
    def wrap(orig):
        @functools.wraps(orig)
        def run(self, plan):
            return orig(self, list(plan)[:(len(plan) + 1) // 2])
        return run
    return _both_transports("execute_batch", wrap)


def altered():
    """An answer altered where it is produced: after each dispatch the
    first word the first transfer wrote is off by one."""
    def wrap(orig):
        @functools.wraps(orig)
        def run(self, desc, chunk):
            orig(self, desc, chunk)
            src, dst, _, dst_addr, length = (int(v) for v in
                                             np.asarray(desc[0]))
            if length:
                self.pool = self.pool.at[dst, dst_addr].add(1.0)
        return run
    return _both_transports("_run_descriptors", wrap)


def truncated():
    """Control of the verbs cells, which state no precision: it
    breaks the guarantee that a transfer delivers all ``length`` words, by
    leaving the last word of every transfer undelivered."""
    def wrap(orig):
        @functools.wraps(orig)
        def run(self, plan):
            return orig(self, [e[:5] + (max(0, e[5] - 1),) for e in plan])
        return run
    return _both_transports("execute_batch", wrap)


def no_exchange():
    """The exchange between chips left out: the ICI executor's broadcast of
    each transfer (a masked ``psum``) is dropped, so a peer other than the
    source scatters zeros."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.rdma import transport as tp

    def make(mesh, axis):
        @functools.partial(jax.jit, static_argnames=("chunk",))
        def run(pool, desc, chunk):
            def body(pool_row, desc):
                local = pool_row[0]
                size = local.shape[0]
                lane = jnp.arange(chunk, dtype=jnp.int32)
                me = jax.lax.axis_index(axis)

                def step(i, local):
                    src, dst, sa, da, n = (desc[i][k] for k in range(5))
                    vals = jnp.where(me == src,
                                     local[jnp.clip(sa + lane, 0, size - 1)],
                                     0)
                    sidx = jnp.where((lane < n) & (me == dst), da + lane, size)
                    return local.at[sidx].set(vals, mode="drop")

                return jax.lax.fori_loop(0, desc.shape[0], step, local)[None]
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(axis, None), P(None, None)),
                                 out_specs=P(axis, None), check_vma=False,
                                 )(pool, desc)
        return run
    return patched(tp, "_make_ici_program", make)


def bf16_sum():
    """Control of the all-reduce cell: the plain reference, computed one
    precision below the configuration's float32 (bfloat16), in the place of
    ``RDMACollective.all_reduce``."""
    from chipbench import refs
    from repro.train.collectives import RDMACollective

    def all_reduce(self, shards, algorithm=None):
        out = refs.sum_bf16(np.stack([np.asarray(s) for s in shards]))
        return [out.copy() for _ in range(self.n)]
    return patched(RDMACollective, "all_reduce", all_reduce)


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered, "no_exchange": no_exchange}
CONTROLS = {"truncated": truncated, "bf16_sum": bf16_sum}

#: the faults each loop kind's cells can have, and each kind's control
APPLIES = {
    "verbs_closed_loop": (("unchanged", "half_batch", "altered"), "truncated"),
    "allreduce_closed_loop": (("unchanged", "half_batch", "altered",
                               "no_exchange"), "bf16_sum"),
}


def plant(name: str):
    return {**FAULTS, **CONTROLS}[name]()
