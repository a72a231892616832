"""Fabrics for the reference simulator, built from a configuration's numbers.

A fabric is a set of links, each with a capacity, a tail-drop queue limit
and an ECN threshold, and for every flow an ordered list of `n` paths, each
a sequence of `hops` links.  Links are numbered here in the order they are
first named; the numbering is private to the reference (a link sum adds its
contributions in (hop, flow, path) order whatever the numbering).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# The k-ary fat-tree keeps every path at four hops: a flow between two leaves
# of one pod turns at the pod's spine, and its two middle hops cross a
# virtual wire that serves everything at once and never queues.
BYPASS_CAPACITY = 1e9


@dataclasses.dataclass(frozen=True)
class Fabric:
    route: np.ndarray          # int [hops, F, n] link crossed at each hop
    capacity: np.ndarray       # float [L] packets served per tick
    queue_limit: np.ndarray    # float [L]
    ecn_threshold: np.ndarray  # float [L]
    latency: np.ndarray        # int [F, n] propagation delay in ticks
    fb_delay: int
    ring_len: int
    link_ids: dict             # link name -> id

    @property
    def links(self) -> int:
        return int(self.capacity.shape[0])


class _Links:
    def __init__(self):
        self.ids: dict = {}
        self.cap: list = []

    def __call__(self, name, capacity):
        if name not in self.ids:
            self.ids[name] = len(self.cap)
            self.cap.append(capacity)
        return self.ids[name]


def _fabric(links: _Links, route, latency, queue_limit, ecn_threshold,
            fb_delay, ring_len) -> Fabric:
    cap = np.asarray(links.cap, np.float64)
    virtual = cap >= BYPASS_CAPACITY
    return Fabric(
        route=np.asarray(route, np.int64),
        capacity=cap,
        queue_limit=np.where(virtual, BYPASS_CAPACITY, queue_limit),
        ecn_threshold=np.where(virtual, BYPASS_CAPACITY, ecn_threshold),
        latency=np.asarray(latency, np.int64),
        fb_delay=int(fb_delay),
        ring_len=int(ring_len),
        link_ids=dict(links.ids),
    )


def fat_tree(
    *, n_pods, leaves_per_pod, spines_per_pod, cores_per_spine, leaf_pairs,
    link_capacity, queue_limit, ecn_threshold, latency, intra_pod_latency,
    fb_delay, ring_len,
) -> Fabric:
    """Three-tier fat-tree: spine s of every pod connects to the cores of
    plane s.  Path q = s * cores_per_spine + c of a flow between pods climbs
    leaf -> spine s -> core (s, c) and descends core -> spine s of the
    destination pod -> leaf.  Between two leaves of one pod, path q turns at
    spine s = q // cores_per_spine."""
    links = _Links()
    S, C, Lp = spines_per_pod, cores_per_spine, leaves_per_pod
    route, lat = [], []
    for src, dst in leaf_pairs:
        sp, sl = divmod(int(src), Lp)
        dp, dl = divmod(int(dst), Lp)
        if (sp, sl) == (dp, dl):
            raise ValueError("a flow inside one leaf never reaches a spine")
        paths, plat = [], []
        for q in range(S * C):
            s, c = divmod(q, C)
            up = links(("leaf-spine", sp, sl, s), link_capacity)
            down = links(("spine-leaf", dp, s, dl), link_capacity)
            if sp != dp:
                mid = (links(("spine-core", sp, s, c), link_capacity),
                       links(("core-spine", s, c, dp), link_capacity))
                plat.append(latency)
            else:
                wire = links(("virtual",), BYPASS_CAPACITY)
                mid = (wire, wire)
                plat.append(intra_pod_latency)
            paths.append((up,) + mid + (down,))
        route.append(paths)
        lat.append(plat)
    route = np.transpose(np.asarray(route), (2, 0, 1))   # [hops, F, n]
    return _fabric(links, route, lat, queue_limit, ecn_threshold,
                   fb_delay, ring_len)


def leaf_spine(
    *, n_spines, leaf_pairs, link_capacity, queue_limit, ecn_threshold,
    latency, fb_delay, ring_len,
) -> Fabric:
    """Two-tier leaf-spine: path s of a flow crosses the uplink from its
    source leaf to spine s, then the downlink from spine s to its
    destination leaf."""
    links = _Links()
    route = [
        [(links(("up", int(src), s), link_capacity),
          links(("down", s, int(dst)), link_capacity)) for s in range(n_spines)]
        for src, dst in leaf_pairs
    ]
    route = np.transpose(np.asarray(route), (2, 0, 1))
    lat = np.full(route.shape[1:], latency)
    return _fabric(links, route, lat, queue_limit, ecn_threshold,
                   fb_delay, ring_len)


def spine_flap(fab: Fabric, *, spine, period, duty, length):
    """Capacity multipliers [length, L] of a spine whose links to and from
    every leaf are down for the first `duty` share of each `period` ticks."""
    cap = np.ones((length, fab.links))
    down = (np.arange(length) % period) < duty * period
    for name, lid in fab.link_ids.items():
        if name[0] == "up" and name[2] == spine or name[0] == "down" and name[1] == spine:
            cap[down, lid] = 0.0
    return cap
