"""Regenerate the transport golden traces.

Two pinned files live next to this script:

  * ``transport_seed.npz``     — `simulate_message` on the independent-
    bundle seed fabric for the five BASELINE policies x both reliability
    modes (plus one default-config trace and one coupled-flows trace).
    These are the bit-identity acceptance contract for any refactor of the
    sender engine: a change that alters a single float in any field of any
    trace is a semantic change, not a refactor.  The file is NEVER
    rewritten by default — even value-identical arrays would change the
    file bytes (zip member timestamps), and the whole point of the file is
    that it predates the refactors it gates.  It was re-pinned once, when
    the installed JAX moved to 0.9.0, whose default threefry is
    partitionable (`jax_threefry_partitionable=True`).
  * ``transport_policies.npz`` — the same trace schema for the
    state-bearing bake-off policies (PRIME / STRACK / CC_COUPLED), coded +
    ARQ, plus a coupled-flows case per policy.  Pinned when the policies
    landed; regenerating it is a semantic change to THOSE policies only
    and must leave transport_seed.npz untouched.

Both files record the JAX version and PRNG mode they were pinned under
(``meta/jax_version``, ``meta/threefry_partitionable``); the tests assert
that the running JAX matches them, since the traces are bit-identity pins
of one JAX version's random bits.

Only rerun deliberately — never to make a red test green:

    PYTHONPATH=src python tests/golden/gen_golden_transport.py            # policies file
    PYTHONPATH=src python tests/golden/gen_golden_transport.py --seed    # BOTH files
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.net.policies import BASELINE_POLICIES
from repro.net.transport import (
    Policy,
    TransportConfig,
    simulate_flows,
    simulate_message,
)
from repro.net.fabric import FabricParams
from repro.net.topology import leaf_spine, null_schedule

OUT = os.path.join(os.path.dirname(__file__), "transport_seed.npz")
OUT_POLICIES = os.path.join(os.path.dirname(__file__), "transport_policies.npz")
FIELDS = ("cct", "sent_total", "dropped_total", "final_b", "received")
META_KEYS = ("meta/jax_version", "meta/threefry_partitionable")

NEW_POLICIES = (Policy.PRIME, Policy.STRACK, Policy.CC_COUPLED)


def golden_params(n=4):
    """Small degrading fabric: nonzero moles so the PRNG path is exercised."""
    return FabricParams(
        capacity=jnp.full((n,), 4.0),
        latency=jnp.full((n,), 4, jnp.int32),
        queue_limit=jnp.full((n,), 16.0),
        ecn_threshold=jnp.full((n,), 6.0),
        degrade_p=jnp.full((n,), 0.02),
        recover_p=jnp.full((n,), 0.1),
        degrade_factor=jnp.full((n,), 0.1),
        fb_delay=8,
        ring_len=64,
    )


def _message_cases(policies):
    params4 = golden_params(4)
    cases = []
    for pol in policies:
        for coded in (True, False):
            rel = "coded" if coded else "arq"
            cases.append(
                (
                    f"{pol.name}/{rel}",
                    params4,
                    TransportConfig(policy=pol, coded=coded, rate=16),
                    256,
                    7,
                    512,
                )
            )
    return cases


def golden_cases():
    """(name, params, cfg, n_packets, key_seed, horizon) for every
    transport_seed.npz trace — the five baselines only (frozen set)."""
    cases = _message_cases(BASELINE_POLICIES)
    # one default-config trace on the wider fabric (the README quickstart shape)
    cases.append(
        ("WAM/default8", golden_params(8),
         TransportConfig(policy=Policy.WAM), 512, 0, 1024)
    )
    return cases


def golden_policy_cases():
    """transport_policies.npz message traces: the bake-off newcomers."""
    return _message_cases(NEW_POLICIES)


def golden_flows_case():
    """One coupled-flows trace on the shared leaf-spine fabric."""
    topo = leaf_spine(4, 4, [(0, 1), (0, 2), (3, 1), (2, 3)], uplink_capacity=8.0)
    cfg = TransportConfig(policy=Policy.WAM, rate=16)
    return topo, null_schedule(topo.links), cfg, 128, 3, 512


def golden_policy_flows_cases():
    """Coupled-flows traces per new policy (same shape as the WAM one)."""
    topo, sched, _, n_packets, seed, horizon = golden_flows_case()
    return [
        (f"FLOWS/{pol.name}", topo, sched,
         TransportConfig(policy=pol, rate=16), n_packets, seed, horizon)
        for pol in NEW_POLICIES
    ]


def pin_meta() -> dict:
    """The JAX version and PRNG mode the traces are pinned under."""
    return {
        "meta/jax_version": np.asarray(jax.__version__),
        "meta/threefry_partitionable": np.asarray(
            bool(jax.config.jax_threefry_partitionable)
        ),
    }


def _render_message(blobs, cases):
    for name, params, cfg, n_packets, seed, horizon in cases:
        r = simulate_message(
            params, cfg, n_packets, jax.random.PRNGKey(seed), horizon
        )
        for field in FIELDS:
            blobs[f"{name}/{field}"] = np.asarray(getattr(r, field))
        print(f"{name:24s} cct={float(r.cct):7.1f} received={float(r.received):8.1f}")


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    write_seed = "--seed" in argv

    blobs = pin_meta()
    _render_message(blobs, golden_policy_cases())
    for name, topo, sched, cfg, n_packets, seed, horizon in golden_policy_flows_cases():
        r = simulate_flows(
            topo, sched, cfg, n_packets, jax.random.PRNGKey(seed), horizon
        )
        for field in FIELDS:
            blobs[f"{name}/{field}"] = np.asarray(getattr(r, field))
        print(f"{name:24s} cct={np.asarray(r.cct)}")
    np.savez(OUT_POLICIES, **blobs)
    print(f"wrote {len(blobs)} arrays to {OUT_POLICIES}")

    if not write_seed:
        return
    blobs = pin_meta()
    _render_message(blobs, golden_cases())
    topo, sched, cfg, n_packets, seed, horizon = golden_flows_case()
    r = simulate_flows(topo, sched, cfg, n_packets, jax.random.PRNGKey(seed), horizon)
    for field in FIELDS:
        blobs[f"FLOWS/WAM/{field}"] = np.asarray(getattr(r, field))
    print(f"{'FLOWS/WAM':24s} cct={np.asarray(r.cct)}")
    np.savez(OUT, **blobs)
    print(f"wrote {len(blobs)} arrays to {OUT}")


if __name__ == "__main__":
    main()
