"""The reference simulator against the program, on the CPU at small sizes."""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from bench.harness import compare
from bench.reference import topologies as rt
from bench.reference.fabric import simulate, theta
from bench.tests.helpers import ROOT

from repro.net import scenarios, sender, topology


def _limits(cell):
    with open(os.path.join(ROOT, "bench", "cells", cell + ".json")) as f:
        return json.load(f)["limits"]


def _within(values, limits):
    return all(values[k] <= limits[k] for k in values if k in limits)


def test_theta_is_the_papers_bit_reversal():
    assert theta(249, 10) == 636
    assert theta(636, 10) == 249


# every kind of flow: between pods, inside a pod (via the virtual wire), and
# several flows sharing one leaf's uplinks
PAIRS = [(0, 2), (0, 5), (1, 3), (2, 1), (3, 0), (4, 7), (5, 4), (6, 3), (7, 0), (7, 6)]


def _engine_and_reference(fabric, pairs, route_fault=False):
    P, Lp, S, C = 4, 2, 2, 2
    kw = dict(queue_limit=48.0, ecn_threshold=12.0, fb_delay=8, ring_len=128)
    if fabric == "fat_tree":
        topo = topology.fat_tree(P, Lp, S, C, pairs, uplink_capacity=32.0, **kw)
        ref = rt.fat_tree(n_pods=P, leaves_per_pod=Lp, spines_per_pod=S,
                          cores_per_spine=C, leaf_pairs=pairs, link_capacity=32.0,
                          latency=6, intra_pod_latency=4, **kw)
    else:
        topo = topology.leaf_spine(P * Lp, S, pairs, uplink_capacity=16.0, **kw)
        ref = rt.leaf_spine(n_spines=S, leaf_pairs=pairs, link_capacity=16.0,
                            latency=4, **kw)
    if route_fault:  # every first hop one link further along
        route = topo.route.at[0].set((topo.route[0] + 1) % topo.links)
        topo = topology.TopologyParams(**{**topo.__dict__, "route": route})
    sched = topology.null_schedule(topo.links)
    sp = sender.stack_params([sender.sender_params(p, rate=32, seed=(101, 57))
                              for p in (sender.Policy.ECMP, sender.Policy.WAM)])
    key = jax.random.PRNGKey(3)
    spec = sender.SenderSpec(rate_cap=32, early_exit=True, exit_chunk=32)
    r = sender.sweep_flows(topo, sched, spec, sp, 96, key[None], 256)
    ecmp = np.asarray(jax.random.randint(jax.random.split(key)[0], (len(pairs),),
                                         0, topo.n))
    points, fin = [], []
    for p, policy in enumerate(("ECMP", "WAM")):
        cct, f = simulate(ref, policy=policy, n_packets=96, horizon=256,
                          ecmp_path=ecmp, sa=101, sb=57)
        points.append((np.asarray(r.cct[p, 0], np.float64), cct))
        fin.append((np.asarray(r.finished[p, 0]), f))
    return points, fin


@pytest.mark.parametrize("fabric", ["fat_tree", "leaf_spine"])
def test_reference_agrees_with_engine(fabric):
    points, fin = _engine_and_reference(fabric, PAIRS)
    for got, want in fin:
        np.testing.assert_array_equal(got, want)
        assert want.all()
    values = compare(points)
    assert _within(values, _limits("ft8.permutation")), values


def test_route_off_by_one_link_is_caught():
    points, _ = _engine_and_reference("fat_tree", PAIRS, route_fault=True)
    assert not _within(compare(points), _limits("ft8.permutation"))


def test_reference_agrees_under_capacity_events():
    """Scheduled capacity events: spine 0 of a leaf-spine is down for the
    first half of every 128 ticks (the program's `link_flap` scenario)."""
    pairs = [(2 * f, 2 * f + 1) for f in range(4)]
    topo, sched = scenarios.link_flap(flows=4, n_spines=4, horizon=1024)
    sp = sender.stack_params([sender.sender_params(p, rate=32, seed=(7, 9))
                              for p in (sender.Policy.ECMP, sender.Policy.WAM)])
    key = jax.random.PRNGKey(11)
    r = sender.sweep_flows(topo, sched, sender.SenderSpec(rate_cap=32), sp, 512,
                           key[None], 1024)
    fab = rt.leaf_spine(n_spines=4, leaf_pairs=pairs, link_capacity=8.0,
                        queue_limit=48.0, ecn_threshold=12.0, latency=4,
                        fb_delay=8, ring_len=128)
    events = rt.spine_flap(fab, spine=0, period=128, duty=0.5, length=1024)
    ecmp = np.asarray(jax.random.randint(jax.random.split(key)[0], (4,), 0, 4))
    points = []
    for p, policy in enumerate(("ECMP", "WAM")):
        cct, finished = simulate(fab, policy=policy, n_packets=512, horizon=1024,
                                 ecmp_path=ecmp, sa=7, sb=9,
                                 cap_scale=lambda t: events[t])
        assert finished.all() and np.asarray(r.finished[p, 0]).all()
        points.append((np.asarray(r.cct[p, 0], np.float64), cct))
    values = compare(points)
    assert _within(values, _limits("ft8.permutation")), values
