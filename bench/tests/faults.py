"""Faults planted in the program underneath a benchmark run.

Each is a context manager that breaks the timed path at the place the fault
would arise, and clears JAX's in-memory caches on the way in and out, so the
program is traced again with (and then without) the fault.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.net import sender, topology


@contextlib.contextmanager
def _patched(module, name, replacement):
    original = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)
        jax.clear_caches()


def state_unchanged():
    """Every fabric tick returns the state it was given."""
    def wrap(tick):
        def stuck(topo, sched, state, arrivals, key, **kw):
            _, fb = tick(topo, sched, state, arrivals, key, **kw)
            return state, fb
        return stuck
    return _patched(sender, "shared_fabric_tick", wrap)


def half_batch():
    """The per-link sums leave out the second half of the flows."""
    def wrap(link_sum):
        def half(vals, route, links):
            keep = jnp.arange(vals.shape[1]) < vals.shape[1] // 2
            return link_sum(vals * keep[None, :, None], route, links)
        return half
    return _patched(topology, "_link_sum", wrap)


def answer_altered():
    """A flow completes a few packets short of what it needs."""
    def wrap(need):
        return lambda *a, **kw: need(*a, **kw) - 24.0
    return _patched(sender, "completion_need", wrap)


def exchange_left_out(n_shards: int):
    """Each chip sums the link loads of its own flows only, repeated in
    place of the other chips' flows."""
    def wrap(all_gather):
        def local(x, axis_name, *, axis=0, tiled=False):
            return jnp.concatenate([x] * n_shards, axis=axis)
        return local
    return _patched(jax.lax, "all_gather", wrap)
