"""Plain NumPy reference of the packet-spraying fabric simulator.

It steps ONE traffic instance under ONE policy and ONE PRNG draw, tick by
tick, with no sweep axes, no early exit, no sharding and no telemetry.  It
imports nothing from the system under test: the fabric is described by
`topologies.Fabric`, built here from the configuration's own numbers.

The semantics, per tick t (all fluid quantities in `dtype`, float32 unless
the control asks for less):

1. Emission.  A flow that has not completed emits `rate` packets (coded
   transport keeps the pipe full until completion).
2. Path assignment.  ECMP sends every packet of a flow on its hashed path.
   WAM (Whack-a-Mole, SHUFFLE_1 seeding) gives packet j the selection point
   theta((sa + j*sb) mod m, ell), the bit reversal of its ell low bits, and
   the path is the first bin whose inclusive cumulative ball count exceeds
   that point.
3. The shared fabric.  Every path crosses `hops` links; packets served at
   hop h enter hop h+1 on the next tick.  Per link: arrivals beyond the
   queue limit are tail-dropped in proportion to what each (flow, path)
   brought this tick; the link then serves min(backlog, capacity) shared in
   proportion to backlog (fluid FIFO).  A path is ECN-marked when any of its
   links holds more than its threshold after service; its queueing delay is
   the sum over its links of residual backlog / capacity.
4. Delivery.  Packets leaving the last hop land 1 + latency +
   round(queueing delay) ticks later (capped at ring_len - 1).
5. Delayed feedback.  What a flow sent, had marked, had dropped and the
   path delay it saw return `fb_delay` ticks later.
6. Controller (WAM only, every `ctrl_interval` ticks): severity per path
   from the feedback, smoothed, then a whack-down of the degraded paths
   (embodiment 3 redistribution with a persistent residual index) and a
   recovery ramp for a starved healthy path.
7. Completion.  A flow completes at t + 1 once it has received
   floor(K + K * overhead) + 1 - 0.25 packets.
"""
from __future__ import annotations

import numpy as np

from bench.reference.topologies import Fabric

ECMP, WAM = "ECMP", "WAM"

# controller constants (the paper's section 6 controller)
_EWMA = 0.5
_DEGRADED = 0.05
_RECOVERY_W = 0.01
_RECOVERY_SHARE = 0.02
_ALPHA_CAP = 0.5
_BETA = 0.125
_LOSS_WEIGHT = 4.0


def theta(x: np.ndarray, ell: int) -> np.ndarray:
    """Reverse the `ell` low bits of each integer in `x`."""
    x = np.asarray(x, np.int64) & ((1 << ell) - 1)
    out = np.zeros_like(x)
    for bit in range(ell):
        out |= ((x >> bit) & 1) << (ell - 1 - bit)
    return out


def uniform_balls(n: int, ell: int) -> np.ndarray:
    """m = 2**ell balls split as evenly as possible over n bins, the
    remainder going to the first bins."""
    m = 1 << ell
    b = np.full(n, m // n, np.int64)
    b[: m % n] += 1
    return b


def completion_need(n_packets: np.ndarray, overhead: float, dtype) -> np.ndarray:
    k = np.asarray(n_packets, dtype)
    need = np.floor(k + k * dtype(overhead)) + dtype(1.0)
    need = np.where(k <= 4, k, need)
    return (need - dtype(0.25)).astype(dtype)


def _embodiment3(b, r, e):
    """Remove e[i] balls from each bin with e[i] > 0, hand them out evenly
    over the other bins, and walk the remainder one ball at a time from the
    residual index r over those bins only (the paper's section 7 loop)."""
    b = b.copy()
    n = b.size
    receivers = [i for i in range(n) if e[i] == 0]
    tot = int(e.sum())
    x, y = divmod(tot, len(receivers))
    for i in range(n):
        if e[i] > 0:
            b[i] -= e[i]
        else:
            b[i] += x
    while y > 0:
        if e[r] == 0:
            b[r] += 1
            y -= 1
        r = (r + 1) % n
    return b, r


class Controller:
    """Per-flow WAM profile controller state: balls per path, residual
    index, smoothed severities."""

    def __init__(self, n: int, ell: int, dtype):
        self.ell = ell
        self.dtype = dtype
        self.b = uniform_balls(n, ell)
        self.r = 0
        self.ewma_w = np.zeros(n, dtype)

    def step(self, ecn_rate, loss_rate, rtt):
        d = self.dtype
        floor = rtt.min()
        excess = (rtt - floor) / floor if floor > 0 else np.zeros_like(rtt)
        w_inst = (ecn_rate + d(_LOSS_WEIGHT) * loss_rate) + (
            np.clip(excess, d(0), d(4)) / d(4)
        )
        w = (d(_EWMA) * w_inst + d(1.0 - _EWMA) * self.ewma_w).astype(d)
        self.ewma_w = w
        # whack-down: never the least-bad path
        b = self.b
        alpha = np.clip(w, d(0), d(1)) * d(_ALPHA_CAP)
        degraded = w > d(_DEGRADED)
        degraded[int(np.argmin(w))] = False
        e = np.where(degraded, (alpha * b.astype(d)).astype(np.int64), 0)
        e = np.minimum(e, np.maximum(b, 0))
        if np.any(e > 0):
            self.b, self.r = _embodiment3(b, self.r, e)
        # recovery of the most starved healthy path
        share = self.b.astype(d) / d(1 << self.ell)
        starved = (w < d(_RECOVERY_W)) & (share < d(_RECOVERY_SHARE))
        if np.any(starved):
            target = int(np.argmin(np.where(starved, share, np.inf)))
            self._restore(target)

    def _restore(self, target):
        b = self.b
        idx = np.arange(b.size)
        e = np.where(
            idx != target, (self.dtype(_BETA) * b.astype(self.dtype)).astype(np.int64), 0
        )
        if not np.any(e > 0):
            donor_b = np.where(idx != target, b, -1)
            donor = int(np.argmax(donor_b))
            e = np.zeros_like(b)
            e[donor] = min(max(int(donor_b[donor]), 0), 1)
        b = b - e
        b[target] += int(e.sum())
        self.b = b


def simulate(
    fab: Fabric,
    *,
    policy: str,
    n_packets,
    horizon: int,
    ecmp_path: np.ndarray,
    sa: int,
    sb: int,
    cap_scale=None,
    rate: int = 32,
    ell: int = 10,
    ctrl_interval: int = 4,
    code_overhead: float = 0.05,
    dtype=np.float32,
):
    """Run one instance to completion (or the horizon).

    `cap_scale(t)` returns the [L] capacity multipliers of tick t (None: all
    ones).  `ecmp_path[F]` is each flow's hashed path; `(sa, sb)` the spray
    seed of flow 0, from which flow f's seed is derived.  Returns
    ``(cct[F] float64, finished[F] bool)``: the completion tick, or
    `horizon` for a flow that did not complete.
    """
    d = dtype
    if policy not in (ECMP, WAM):
        raise ValueError(f"reference models ECMP and WAM, not {policy!r}")
    H, F, n = fab.route.shape
    L = fab.links
    m = 1 << ell
    mask = m - 1
    route = fab.route
    flat_route = route.reshape(-1)
    cap0 = fab.capacity.astype(d)
    qlim = fab.queue_limit.astype(d)
    ecn_thr = fab.ecn_threshold.astype(d)
    latency = fab.latency.astype(np.int64)
    latency_f = fab.latency.astype(d)
    ring = fab.ring_len
    fbd = fab.fb_delay

    def link_sum(vals):
        out = np.zeros(L, d)
        np.add.at(out, flat_route, vals.reshape(-1))
        return out

    need = completion_need(np.broadcast_to(n_packets, (F,)), code_overhead, d)
    fidx = np.arange(F, dtype=np.int64)
    sa_f = (sa + fidx * 0x9E3779B9) & mask
    sb_f = ((sb + 2 * fidx) & mask) | 1
    j = np.zeros(F, np.int64)
    ctrls = [Controller(n, ell, d) for _ in range(F)] if policy == WAM else None
    lanes = np.arange(rate, dtype=np.int64)

    queue = np.zeros((H, F, n), d)
    forward = np.zeros((H - 1, F, n), d)
    landing = np.zeros((horizon + ring + 1, F), d)
    received = np.zeros(F, d)
    history = []  # per tick: (sent, marked, dropped, qdelay), each [F, n]
    done_at = np.full(F, -1, np.int64)
    zero_fn = np.zeros((F, n), d)

    for t in range(horizon):
        # 1-2. emission and path assignment
        live = done_at < 0
        arrivals = np.zeros((F, n), d)
        if policy == ECMP:
            arrivals[fidx[live], ecmp_path[live]] = d(rate)
        else:
            keys = theta((sa_f[:, None] + (j[:, None] + lanes) * sb_f[:, None]) & mask, ell)
            c = np.stack([np.cumsum(ctrl.b) for ctrl in ctrls])        # [F, n]
            paths = (c[:, None, :] <= keys[:, :, None]).sum(-1)        # [F, rate]
            for p in range(n):
                arrivals[:, p] = (paths == p).sum(1)
            arrivals[~live] = 0
            j = j + np.where(live, rate, 0)

        # 3. the shared fabric
        cap = cap0 if cap_scale is None else (cap0 * cap_scale(t).astype(d))
        inflow = np.concatenate([arrivals[None], forward], axis=0)
        q_in = queue + inflow
        backlog = link_sum(q_in)
        incoming = link_sum(inflow)
        dropable = np.minimum(np.maximum(backlog - qlim, d(0)), incoming)
        drop_frac = np.where(incoming > 0, dropable / np.maximum(incoming, d(1e-9)), d(0))
        drops = inflow * drop_frac[route]
        q_in = q_in - drops
        backlog = backlog - dropable
        served_l = np.minimum(backlog, cap)
        serve_frac = np.where(backlog > 0, served_l / np.maximum(backlog, d(1e-9)), d(0))
        served = q_in * serve_frac[route]
        queue = q_in - served
        forward = served[:-1]
        residual = backlog - served_l
        qdelay_l = np.where(cap > 0, residual / np.maximum(cap, d(1e-6)), d(0))
        path_qdelay = qdelay_l[route].sum(axis=0, dtype=d)
        path_drops = drops.sum(axis=0, dtype=d)
        path_marked = (residual > ecn_thr)[route].any(axis=0)
        exiting = served[-1]
        marked = np.where(path_marked, exiting, d(0))

        # 4. delivery after propagation + rounded queueing delay
        delay = np.minimum(latency + np.round(path_qdelay).astype(np.int64), ring - 1)
        deposits = np.zeros((F, ring + 1), d)
        np.add.at(deposits, (np.broadcast_to(fidx[:, None], delay.shape), 1 + delay), exiting)
        landing[t : t + ring + 1] += deposits.T
        received = received + landing[t]

        # 5. delayed feedback
        history.append((arrivals, marked, path_drops, path_qdelay))
        if t >= fbd:
            fb_sent, fb_marked, fb_dropped, fb_qdelay = history[t - fbd]
        else:
            fb_sent = fb_marked = fb_dropped = fb_qdelay = zero_fn

        # 6. controller
        if policy == WAM and t % ctrl_interval == 0:
            sent = np.maximum(fb_sent, d(1e-6))
            seen = np.minimum(fb_sent, d(1.0))
            ecn_rate = fb_marked / sent * seen
            loss_rate = fb_dropped / sent * seen
            rtt = latency_f + fb_qdelay
            for f in range(F):
                ctrls[f].step(ecn_rate[f], loss_rate[f], rtt[f])

        # 7. completion
        done_now = (received >= need) & (done_at < 0)
        done_at[done_now] = t + 1
        if np.all(done_at >= 0):
            break

    finished = done_at >= 0
    cct = np.where(finished, done_at, horizon).astype(np.float64)
    return cct, finished
