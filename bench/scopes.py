"""Device time by the named scopes the engine opens, from a trace and the
optimized HLO of the programs it ran.

JAX writes the path of `jax.named_scope`s an op was traced under into its
HLO instruction's `op_name` metadata (`.../tick/fabric/delivery/scatter-add`),
fusions included.  The engine (`repro.net.sender.run_sender`) opens `tick`
round each tick, with `tick/assign` (each policy's branch under
`tick/assign/<policy>`), `tick/fabric` (`link_sums`, `signals`, `delivery`,
`feedback`) and `tick/sender`, and `engine/keys`, `engine/chunk` (the
early-exit chunk scans), `engine/settle` and `engine/result` round it.
`op_scopes` reads them, and how many times each `while` loops;
`ScopedTrace` is a `bench.trace.Trace` that carries them, and reads op time
by component (`shares`) and the ticks a traced call ran (`ticks_per_call`).

A run with `--trace 1 --keep-trace DIR` keeps the raw trace and the traced
programs' HLO in DIR; this reads them:

    python3 bench/scopes.py DIR [--chips N] [--save RECORDING.json.gz]

It prints one JSON line: the four shares, the ticks per call and the
seconds under each scope.  `--save` writes the trace condensed to what
those read, with its scopes.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.trace import (  # noqa: E402
    CONTAINERS, PASS_THROUGH, Trace, _CALLS, _COMPUTATION, _INSTRUCTION,
    _OPERAND, load, save,
)

# share name -> the component whose op time it reads; the fourth share is
# the op time under no `tick` scope
SHARES = {"tick.assign_share": "tick/assign", "tick.fabric_share": "tick/fabric",
          "tick.sender_share": "tick/sender"}
OUTSIDE = "engine.outside_tick_share"
CHUNK = "engine/chunk"
COMPONENTS = ("tick", "tick/assign", "tick/fabric", "tick/fabric/link_sums",
              "tick/fabric/signals", "tick/fabric/delivery",
              "tick/fabric/feedback", "tick/sender", "engine/keys",
              "engine/chunk", "engine/settle", "engine/result")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KNOWN_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_LOOP = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALLEE = re.compile(
    r"(?:calls|to_apply|condition|body|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_INDEX = re.compile(r"\bindex=(\d+)")
_CONSTANT = re.compile(r"\bconstant\((-?\d+)\)")
# transformation wrappers JAX puts round a scope name (`vmap(tick)`,
# `jit(searchsorted)`); a scope of its own is what they wrap
_WRAPPER = re.compile(r"[\w\-]+\(")


def _operands(line: str, start: int) -> list:
    """The `%name` operands between the parenthesis that opens at `start`
    and the one that closes it."""
    depth, i = 0, start
    while i < len(line):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            break
        i += 1
    return _OPERAND.findall(line, start, i)


def op_scopes(hlo_texts) -> dict:
    """What the optimized HLO modules given say of each instruction's place
    in the program: {"ops": {instruction name: its `op_name` metadata},
    "trips": {while instruction name: the times it loops}}.

    A fusion takes the `op_name` of the root of the computation it calls,
    looking through `PASS_THROUGH` ops as `bench.trace.op_kinds` does.
    Where the compiler left that root none (a TPU's scatters), it takes the
    fusion's own, else that of the instruction nearest the root that has
    one, looking first at the computation a scatter or reduction applies (a
    constant's `op_name` says where it was first made, not where it is
    used).  An instruction with no `op_name` takes that of the fusion, loop
    or call that holds it.  A loop's trip count is the `known_trip_count`
    its backend config states or, where the compiler states none (a TPU),
    what its condition states: a counter that starts at a constant, steps
    by one, and is compared less-than with a constant."""
    ins, roots = {}, {}
    home, holder = {}, {}   # instruction -> its computation -> its caller
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
            op_name = _OP_NAME.search(line)
            callees = _CALLEE.findall(line)
            branches = _BRANCHES.search(line)
            if branches:
                callees += _OPERAND.findall(branches.group(1))
            ins[name] = (opcode, op_name.group(1) if op_name else None,
                         _operands(line, m.end() - 1), line, callees)
            home[name] = computation
            if root and computation is not None:
                roots[computation] = name
            for callee in callees:
                holder.setdefault(callee, name)

    def through(name):
        if ins[name][0] != "fusion":
            return name
        for _ in range(len(ins)):
            opcode, _, args, line, _ = ins[name]
            called = _CALLS.search(line) if opcode == "fusion" else None
            if called and called.group(1) in roots:
                name = roots[called.group(1)]
            elif opcode in PASS_THROUGH and args and args[0] in ins:
                name = args[0]
            else:
                break
        return name

    def nearest(name):
        queue = [through(name), name]
        for n in queue:
            opcode, op_name, args, _, callees = ins[n]
            if op_name and opcode != "constant":
                return op_name
            near = [roots[c] for c in callees if c in roots]
            if home[n] != home[name]:           # inside the fusion
                near += [a for a in args if a in ins]
            queue += [a for a in near if a not in queue]
        return None

    def scope(name):
        for _ in range(len(ins)):
            found = nearest(name)
            if found or holder.get(home[name]) is None:
                return found
            name = holder[home[name]]
        return None

    def constant(name):
        while name in ins and ins[name][0] in PASS_THROUGH and ins[name][2]:
            name = ins[name][2][0]
        c = _CONSTANT.search(ins[name][3]) if name in ins else None
        return int(c.group(1)) if c and ins[name][0] == "constant" else None

    def trips(name):
        opcode, _, args, line, _ = ins[name]
        known = _KNOWN_TRIPS.search(line)
        if known:
            return int(known.group(1))
        loop = _LOOP.search(line)
        cond = roots.get(loop.group(1)) if loop else None
        body = roots.get(loop.group(2)) if loop else None
        if cond is None or body is None or not args:
            return None
        c_op, _, c_args, c_line, _ = ins[cond]
        if c_op != "compare" or "direction=LT" not in c_line or len(c_args) != 2:
            return None
        counter = ins.get(c_args[0])
        if counter is None or counter[0] != "get-tuple-element":
            return None
        k = int(_INDEX.search(counter[3]).group(1))
        init, step = ins.get(args[0]), ins[body]
        if init is None or init[0] != "tuple" or step[0] != "tuple":
            return None
        bound, start = constant(c_args[1]), constant(init[2][k])
        add = ins.get(step[2][k])
        if (bound is None or start is None or add is None or add[0] != "add"
                or 1 not in [constant(a) for a in add[2]]):
            return None
        return max(bound - start, 0)

    return {"ops": {name: scope(name) for name in ins},
            "trips": {name: n for name in ins if ins[name][0] == "while"
                      for n in [trips(name)] if n is not None}}


def scope_path(op_name: str | None) -> list:
    """The scopes of an `op_name`, outermost first, with transformation
    wrappers taken off: `jit(f)/vmap(vmap(engine/chunk))/while` gives
    [f, engine, chunk, while]."""
    if not op_name:
        return []
    return [s for s in _WRAPPER.sub("", op_name).replace(")", "").split("/") if s]


class ScopedTrace(Trace):
    """A `Trace` with `scopes`, what `op_scopes` read from the programs it
    ran (None where their HLO is unknown)."""

    def __init__(self, ops: dict, spans: list, kinds: dict | None = None,
                 opcodes: dict | None = None, scopes: dict | None = None):
        super().__init__(ops, spans, kinds, opcodes)
        self.scopes = scopes

    @classmethod
    def of(cls, trace: Trace, scopes: dict | None):
        return cls(trace.ops, trace.spans, trace.kinds, trace.opcodes, scopes)

    def to_json(self) -> dict:
        return {**super().to_json(), "scopes": self.scopes}

    @classmethod
    def from_json(cls, d: dict):
        return cls.of(Trace.from_json(d), d.get("scopes"))

    def _scopes_of(self, names):
        if self.scopes is None:
            return None
        return {part: {n: v for n, v in d.items() if n in names}
                for part, d in self.scopes.items()}

    def excerpt(self, start: float, max_ops: int):
        part = super().excerpt(start, max_ops)
        names = {n for evs in part.ops.values() for n, _, _ in evs}
        return ScopedTrace.of(part, self._scopes_of(names))

    def condensed(self):
        """The trace cut down to what the op-time and loop readings read:
        on each device, one event per op that lasts its seconds inside the
        window, laid end to end from the window's start, and every run of a
        loop, call or conditional as it was; the spans as they were.  Op
        seconds, scope and kind shares and loop runs read the same as on
        the whole trace; busy and idle time do not."""
        ops = {}
        for dev in self.ops:
            t, evs = self.t0, []
            for name, sec in self.op_seconds(dev).items():
                evs.append((name, t, sec))
                t += sec
            evs += [e for e in self.ops[dev] if self.kind(e[0]) in CONTAINERS
                    and e[1] < self.t1 and e[1] + e[2] > self.t0]
            ops[dev] = evs
        names = {n for evs in ops.values() for n, _, _ in evs}
        kinds = (None if self.kinds is None else
                 {n: k for n, k in self.kinds.items() if n in names})
        opcodes = {n: k for n, k in self.opcodes.items() if n in names}
        return ScopedTrace(ops, list(self.spans), kinds, opcodes,
                           self._scopes_of(names))

    def under(self, name: str, component: str) -> bool:
        """Does op `name` lie under the named scope `component`: do the
        scopes of its `op_name` hold the component's, one after another?"""
        path = scope_path((self.scopes or {}).get("ops", {}).get(name))
        want = component.split("/")
        return any(path[i:i + len(want)] == want
                   for i in range(len(path) - len(want) + 1))

    def scope_seconds(self, component: str, device: str | None = None):
        """(seconds of ops under the named scope `component`, such as
        `tick/assign`, seconds of all ops), inside the window, summed over
        the devices or on one; None where the scopes are unknown."""
        if self.scopes is None:
            return None
        ops = self.op_seconds(device)
        hit = sum(s for name, s in ops.items() if self.under(name, component))
        return hit, sum(ops.values())

    def loop_runs(self, device: str):
        """[(op name, trip count)] of each run on `device` that overlaps the
        window, of a loop whose trip count the HLO states; None where the
        scopes are unknown.  (A run that overlaps it, not one that starts
        in it: the device's clock and the host's differ by microseconds,
        and a call's first loop starts microseconds after its dispatch.)"""
        if self.scopes is None:
            return None
        trips = self.scopes["trips"]
        return [(name, trips[name]) for name, s, d in self.ops.get(device, [])
                if name in trips and s < self.t1 and s + d > self.t0]

    def shares(self) -> dict | None:
        """Percent of the op time under each of the tick's components
        (`SHARES`) and under no `tick` scope (`OUTSIDE`: the engine round
        the tick, the sweep's set-up, ops the HLO names no scope of); the
        four partition the op time.  None where no op carries a `tick`
        scope, as in a program from before the engine named them."""
        tick = self.scope_seconds("tick")
        if tick is None or tick[0] <= 0:
            return None
        out = {name: 100.0 * self.scope_seconds(component)[0] / tick[1]
               for name, component in SHARES.items()}
        out[OUTSIDE] = 100.0 * (tick[1] - tick[0]) / tick[1]
        return out

    def ticks_per_call(self) -> float | None:
        """The engine ticks a traced call ran: each run of a loop under
        `engine/chunk` and outside the tick (the chunk scans of
        `sender._scan_early_exit`) runs as many ticks as its trip count;
        their sum on the busiest device over the traced calls (the
        harness's `dispatch` spans).  None where there is no call or no
        chunk loop."""
        calls = sum(1 for name, _, _ in self.spans if name == "dispatch")
        runs = self.loop_runs(self.busiest()) if calls else None
        ticks = [n for name, n in runs or ()
                 if self.under(name, CHUNK) and not self.under(name, "tick")]
        if not ticks:
            return None
        return sum(ticks) / calls

    def components(self) -> dict:
        """Seconds of op time under each of `COMPONENTS` and under each
        scope directly inside `tick/assign` (a policy's branch,
        `tick/assign/wam`), summed over the devices; {} where the scopes
        are unknown."""
        if self.scopes is None:
            return {}
        inner = set()
        for name in self.op_seconds():
            path = scope_path(self.scopes["ops"].get(name))
            # the last scope of a path is the op's own primitive
            for i in range(len(path) - 3):
                if path[i:i + 2] == ["tick", "assign"]:
                    inner.add(f"tick/assign/{path[i + 2]}")
        return {c: self.scope_seconds(c)[0]
                for c in COMPONENTS[:2] + tuple(sorted(inner)) + COMPONENTS[2:]}


def read(path: str) -> ScopedTrace:
    """A trace `bench.trace.save` wrote, with its scopes."""
    with gzip.open(path, "rt") as f:
        return ScopedTrace.from_json(json.load(f))


def load_kept(trace_dir: str, n_devices: int | None = None) -> ScopedTrace:
    """The trace a `--keep-trace` run left in `trace_dir`, with the scopes
    of the programs' HLO it wrote there (`program<k>.hlo.txt`)."""
    programs = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "program*.hlo.txt"))):
        with open(path) as f:
            programs.append(f.read())
    tr = load(trace_dir, n_devices=n_devices, remove=False, programs=programs)
    return ScopedTrace.of(tr, op_scopes(programs) if programs else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="the directory a --keep-trace run kept")
    ap.add_argument("--chips", type=int, default=None,
                    help="read the first N devices (default: all)")
    ap.add_argument("--save", metavar="PATH",
                    help="write the condensed trace with its scopes here")
    args = ap.parse_args(argv)
    tr = load_kept(args.trace_dir, args.chips)
    if tr.scopes is None:
        print(f"no program<k>.hlo.txt in {args.trace_dir}", file=sys.stderr)
        return 1
    print(json.dumps({"shares": tr.shares(), "ticks_per_call": tr.ticks_per_call(),
                      "seconds": tr.components()}))
    if args.save:
        save(tr.condensed(), args.save)
    return 0


if __name__ == "__main__":
    sys.exit(main())
