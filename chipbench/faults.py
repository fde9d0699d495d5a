"""Faults planted under the timed path, and the controls, for showing that
the comparison which decides ``correct`` fails when it should.

Each is a context manager that patches the program for its duration. A loop
kind's file lists the faults its cells can have (``FAULTS``) and names its
control (``CONTROL``); the faults here are shared by several loops, a
control or a fault of one loop is defined in its file. The benchmark's own
runs never plant one: ``control.py`` does, on the chip at a cell's own size,
and ``test_chipbench_faults.py`` at a tiny size.
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

from chipbench import bench


@contextlib.contextmanager
def patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def both_transports(attr: str, wrap):
    from repro.core.rdma import transport as tp
    stack = contextlib.ExitStack()
    for cls in (tp.LocalTransport, tp.ICITransport):
        stack.enter_context(patched(cls, attr, wrap(getattr(cls, attr))))
    return stack


def unchanged():
    """A step that returns its state unchanged: the executor leaves the
    pool as it was."""
    return both_transports("_run_descriptors",
                           lambda orig: lambda self, desc, chunk: None)


def half_batch():
    """Half of each doorbell's transfers left out."""
    def wrap(orig):
        @functools.wraps(orig)
        def run(self, plan):
            return orig(self, list(plan)[:(len(plan) + 1) // 2])
        return run
    return both_transports("execute_batch", wrap)


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
    return both_transports("_run_descriptors", wrap)


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


#: the faults any loop may list; a loop's file may define others, and its
#: control, under the names it lists
SHARED = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered, "no_exchange": no_exchange}


def plant(name: str, loop: str, root: str = bench.ROOT):
    """Plant fault or control ``name`` of the loop kind ``loop``: the factory
    of that name in ``loops/<loop>.py``, else the shared one above."""
    mod = bench.loop_module(loop, root)
    if name not in (*mod.FAULTS, mod.CONTROL):
        raise KeyError(f"loop {loop!r} lists no fault or control {name!r}")
    return (getattr(mod, name, None) or SHARED[name])()
