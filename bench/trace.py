"""Reduction of a `jax.profiler` trace to what the per-layer metrics read.

The profiler writes an `.xplane.pb` under `<dir>/plugins/profile/<time>/`.
Its device planes (`/device:TPU:<i>`) carry one event per operation run on
the device, on the line `XLA Ops`; the host plane (`/host:CPU`) carries the
harness's own spans (`HOST_SPANS`), written with
`jax.profiler.TraceAnnotation`.  Both are on one clock, in nanoseconds from
the start of the trace.  The traced window runs from the first host span's
start to the last one's end.

An op's name is its HLO instruction's name, and on a TPU most are
`fusion.<n>`, which says nothing of what they compute.  `op_kinds` reads
the optimized HLO of the programs the traced calls ran and gives each
instruction its kind: its opcode, or for a fusion the opcode at the root of
the computation it calls (a scatter fused with its operands is `scatter`).
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
from collections import defaultdict

HOST_SPANS = ("dispatch", "read_results", "post_process")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
TOP = 10


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# ops that only move or relabel what they are given: a fusion whose root is
# one of these computes what their first operand computes
PASS_THROUGH = ("reshape", "bitcast", "transpose", "copy", "convert")


def op_kinds(hlo_texts) -> dict:
    """{instruction name: kind} over the optimized HLO modules given: the
    opcode; for a fusion, the kind of the root of the computation it calls,
    looking through `PASS_THROUGH` ops to their first operand (a gather
    whose result is reshaped is `gather`)."""
    roots, ops = {}, {}
    for text in hlo_texts:
        computation = None
        for line in text.splitlines():
            m = _INSTRUCTION.match(line)
            if m is None:
                c = _COMPUTATION.match(line)
                if c is not None:
                    computation = c.group(1)
                continue
            root, name, opcode = m.groups()
            calls = _CALLS.search(line) if opcode == "fusion" else None
            operand = _OPERAND.search(line, m.end())
            ops[name] = (opcode, calls.group(1) if calls else None,
                         operand.group(1) if operand else None)
            if root and computation is not None:
                roots[computation] = name
    kinds = {}
    for name, (opcode, called, _) in ops.items():
        if opcode == "fusion" and called in roots:
            inner = roots[called]
            for _ in range(len(ops)):
                opcode, called, operand = ops[inner]
                if opcode == "fusion" and called in roots:
                    inner = roots[called]
                elif opcode in PASS_THROUGH and operand in ops:
                    inner = operand
                else:
                    break
        kinds[name] = opcode
    return kinds


def _union(intervals):
    """Merge [start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """Device operations per device and the harness's host spans, in
    seconds from the start of the trace.  An op is named by its HLO
    instruction (`fusion.12`); `opcodes` gives each the opcode its trace
    event states, `kinds` (from `op_kinds`, None where the programs' HLO is
    unknown) what a fusion computes."""

    def __init__(self, ops: dict, spans: list, kinds: dict | None = None,
                 opcodes: dict | None = None):
        self.ops = ops        # device name -> [(op name, start, duration)]
        self.spans = spans    # [(span name, start, end)]
        self.kinds = kinds
        self.opcodes = opcodes or {}
        if spans:
            self.t0 = min(s for _, s, _ in spans)
            self.t1 = max(e for _, _, e in spans)
        else:
            self.t0 = self.t1 = 0.0

    @classmethod
    def from_profile(cls, profile, n_devices: int | None = None):
        """A TPU names each op event by its whole HLO instruction
        (`%fusion.12 = f32[8]{0} fusion(...), calls=...`); keep the name and
        the opcode."""
        ops, spans, opcodes = {}, [], {}
        for plane in profile.planes:
            if plane.name.startswith("/device:") and "CPU" not in plane.name:
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    evs = []
                    for e in line.events:
                        m = _INSTRUCTION.match(e.name)
                        name = e.name if m is None else m.group(2)
                        if m is not None:
                            opcodes[name] = m.group(3)
                        evs.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
                    ops[plane.name] = evs
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name in HOST_SPANS:
                            spans.append((e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9))
        if n_devices is not None:
            ops = dict(sorted(ops.items())[:n_devices])
        return cls(ops, spans, opcodes=opcodes)

    def to_json(self) -> dict:
        return {"ops": self.ops, "spans": self.spans, "kinds": self.kinds,
                "opcodes": self.opcodes}

    @classmethod
    def from_json(cls, d: dict):
        return cls({k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   [tuple(s) for s in d["spans"]], d.get("kinds"),
                   d.get("opcodes"))

    def kind(self, name: str) -> str:
        """What op `name` computes: its fusion's root opcode, else its
        opcode, else ""."""
        return (self.kinds or {}).get(name) or self.opcodes.get(name, "")

    def excerpt(self, start: float, max_ops: int):
        """The part of the trace from `start` seconds into the window, as
        long as no device runs more than `max_ops` ops in it: ops and spans
        cut to that part, the kinds of the ops in it."""
        t0 = self.t0 + start
        t1 = self.t1
        for evs in self.ops.values():
            later = sorted(s for _, s, _ in evs if s >= t0)
            if len(later) > max_ops:
                t1 = min(t1, later[max_ops])
        ops = {dev: [(n, s, d) for n, s, d in evs if s >= t0 and s + d <= t1]
               for dev, evs in self.ops.items()}
        spans = [(n, max(s, t0), min(e, t1)) for n, s, e in self.spans
                 if e > t0 and s < t1]
        names = {n for evs in ops.values() for n, _, _ in evs}
        kinds = (None if self.kinds is None else
                 {n: k for n, k in self.kinds.items() if n in names})
        opcodes = {n: k for n, k in self.opcodes.items() if n in names}
        return Trace(ops, spans, kinds, opcodes)

    def kind_seconds(self, kind, device: str | None = None):
        """(seconds of ops whose kind starts with one of `kind`, seconds of
        all ops), inside the window; None where the kinds are unknown."""
        if self.kinds is None:
            return None
        ops = self.op_seconds(device)
        hit = sum(s for name, s in ops.items() if self.kind(name).startswith(kind))
        return hit, sum(ops.values())

    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy_intervals(self, device: str):
        return [[max(s, self.t0), min(e, self.t1)] for s, e in _union(
            (s, s + d) for _, s, d in self.ops[device]) if e > self.t0 and s < self.t1]

    def device_busy_s(self, device: str) -> float:
        return sum(e - s for s, e in self.busy_intervals(device))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(self.device_busy_s(d) for d in self.ops) / len(self.ops)

    def busiest(self) -> str | None:
        return max(self.ops, key=self.device_busy_s) if self.ops else None

    def op_seconds(self, device: str | None = None) -> dict:
        """Device seconds per operation name (summed over the devices, or on
        one), inside the window.  A loop or call is left out: its event
        spans the events of the ops it runs."""
        out = defaultdict(float)
        for dev, evs in self.ops.items():
            if device is not None and dev != device:
                continue
            for name, s, d in evs:
                if self.kind(name) in CONTAINERS:
                    continue
                lo, hi = max(s, self.t0), min(s + d, self.t1)
                if hi > lo:
                    out[name] += hi - lo
        return dict(out)

    def idle_gaps(self) -> dict:
        """Idle seconds of the busiest device, each gap attributed to the
        host span that overlaps it most (`other` where none does)."""
        dev = self.busiest()
        if dev is None:
            return {}
        busy = self.busy_intervals(dev)
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        out = defaultdict(float)
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            best, overlap = "other", 0.0
            for name, hs, he in self.spans:
                o = min(e, he) - max(s, hs)
                if o > overlap:
                    best, overlap = name, o
            out[best] += e - s
        return dict(out)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

        ops = {f"{n} [{self.kind(n)}]" if self.kind(n) else n: s   # fusion.12 [scatter]
               for n, s in self.op_seconds().items()}
        return {"device_ops": top(ops), "idle_gaps": top(self.idle_gaps())}


def load(trace_dir: str, n_devices: int | None = None, remove: bool = True,
         programs=()) -> Trace:
    """Read the one `.xplane.pb` under `trace_dir` and delete the directory
    (unless `remove` is false); `programs` are the HLO texts of the programs
    traced, for the ops' kinds."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {paths}")
    tr = Trace.from_profile(jax.profiler.ProfileData.from_file(paths[0]), n_devices)
    tr.kinds = op_kinds(programs) if programs else None
    if remove:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return tr


def save(trace: Trace, path: str) -> None:
    """Write a trace (or an excerpt of one) as gzipped JSON."""
    import gzip

    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def read(path: str) -> Trace:
    import gzip

    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))
