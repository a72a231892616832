"""`host_permutation`: a fat-tree under a host permutation, new each call."""
from __future__ import annotations

import numpy as np

from bench.generators import Kind, host_permutation, rng, spray_seed, sweep_params
from bench.reference import topologies as ref_topologies
from bench.reference.fabric import simulate


class Generator(Kind):
    """One host permutation per call on a fat-tree, every host sending one
    message, under each policy of the mix and each PRNG draw.  One chip runs
    `sender.sweep_flows_scenarios`; more run its flow-sharded form
    `sender.shard_sweep_flows_scenarios` over a flow mesh of that many."""

    def __init__(self, cfg: dict, mix: dict, chips: int, seed: int):
        import jax
        from repro.net import sender, topology

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.n_paths = cfg["spines_per_pod"] * cfg["cores_per_spine"]
        self.n_hosts = (cfg["n_pods"] * cfg["leaves_per_pod"]
                        * cfg["hosts_per_leaf"])
        self.points_per_call = len(mix["policies"]) * mix["draws"]
        self.spec = sender.SenderSpec(
            ell=cfg["ell"], rate_cap=cfg["rate"],
            early_exit=mix["early_exit"], exit_chunk=mix["exit_chunk"],
        )
        if chips > 1:
            self.entry = "sender.shard_sweep_flows_scenarios"
            self._fn = sender.shard_sweep_flows_scenarios
            self._kw = {"mesh": sender.flow_mesh(chips)}
        else:
            self.entry = "sender.sweep_flows_scenarios"
            self._fn = sender.sweep_flows_scenarios
            self._kw = {}
        self._jax, self._sender, self._topology = jax, sender, topology
        self.pool = [self._inputs(c) for c in range(mix["pool"])]

    def _draw(self, call: int):
        r = rng(self.seed, call)
        dst = host_permutation(self.n_hosts, self.cfg["hosts_per_leaf"], r)
        hpl = self.cfg["hosts_per_leaf"]
        pairs = np.stack([np.arange(self.n_hosts) // hpl, dst // hpl], axis=1)
        sa, sb = spray_seed(r, self.cfg["ell"])
        key = int(r.integers(0, 2**31 - 1))
        return pairs, sa, sb, key

    def _inputs(self, call: int):
        jax, sender, topology = self._jax, self._sender, self._topology
        c = self.cfg
        pairs, sa, sb, key = self._draw(call)
        topo = topology.fat_tree(
            c["n_pods"], c["leaves_per_pod"], c["spines_per_pod"],
            c["cores_per_spine"], pairs,
            uplink_capacity=c["link_capacity"], queue_limit=c["queue_limit"],
            ecn_threshold=c["ecn_threshold"], latency_ticks=c["latency"],
            intra_latency_ticks=c["intra_pod_latency"],
            fb_delay=c["fb_delay"], ring_len=c["ring_len"],
        )
        sched = topology.null_schedule(topo.links)
        topos, scheds = jax.tree.map(lambda x: x[None], (topo, sched))
        sp, keys = sweep_params(sender, c, self.mix, sa, sb, key)
        return jax.block_until_ready((topos, scheds, sp, keys))

    def _args(self, i: int):
        topos, scheds, sp, keys = self.pool[i % len(self.pool)]
        return (topos, scheds, self.spec, sp, self.mix["message_packets"],
                keys, self.mix["horizon"])

    def call(self, i: int) -> dict:
        r = self._fn(*self._args(i), **self._kw)
        return {"cct": r.cct[0], "finished": r.finished[0]}  # [P, D, F]

    def programs(self, i: int) -> list:
        return [self._fn.lower(*self._args(i), **self._kw).compile().as_text()]

    def reference_fabric(self, pairs):
        c = self.cfg
        return ref_topologies.fat_tree(
            n_pods=c["n_pods"], leaves_per_pod=c["leaves_per_pod"],
            spines_per_pod=c["spines_per_pod"],
            cores_per_spine=c["cores_per_spine"], leaf_pairs=pairs,
            link_capacity=c["link_capacity"], queue_limit=c["queue_limit"],
            ecn_threshold=c["ecn_threshold"], latency=c["latency"],
            intra_pod_latency=c["intra_pod_latency"],
            fb_delay=c["fb_delay"], ring_len=c["ring_len"],
        )

    def check(self, i: int, out: dict, dtype=np.float32) -> dict:
        jax = self._jax
        c, mix = self.cfg, self.mix
        pairs, sa, sb, _ = self._draw(i % len(self.pool))
        keys = self.pool[i % len(self.pool)][3]
        fab = self.reference_fabric(pairs)
        points = []
        for p, policy in enumerate(mix["policies"]):
            for d in range(mix["draws"]):
                k_hash, _ = jax.random.split(keys[d])
                ecmp = np.asarray(jax.random.randint(
                    k_hash, (len(pairs),), 0, self.n_paths))
                cct, _ = simulate(
                    fab, policy=policy, n_packets=mix["message_packets"],
                    horizon=mix["horizon"], ecmp_path=ecmp, sa=sa, sb=sb,
                    rate=c["rate"], ell=c["ell"],
                    ctrl_interval=c["ctrl_interval"],
                    code_overhead=c["code_overhead"], dtype=dtype,
                )
                got = None if out is None else np.asarray(out["cct"][p, d], np.float64)
                points.append((got, cct))
        return {"points": points}
