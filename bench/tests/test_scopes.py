"""The reduction of a trace by the named scopes the engine opens
(`bench/scopes.py`)."""
from __future__ import annotations

import json
import os

import pytest

from bench.scopes import OUTSIDE, SHARES, ScopedTrace, op_scopes, read, scope_path
from bench.tests.helpers import ROOT, SCOPE_METRICS, reader
from bench.trace import op_kinds, save

SCOPE = "jit(sweep)/vmap(vmap(engine/chunk))/while/body/while/body/closed_call"

# A fused scatter whose root names `tick/fabric/delivery`; a TPU-style fused
# scatter whose root names nothing, whose combiner names `link_sums` and whose
# operand names another component; a gather under `vmap(tick)/assign`,
# reshaped at the root; the chunk scan as a `while` whose trip count only
# its condition states, with a copy in its body that names nothing; a loop
# whose backend config states its trip count.
SCOPED_HLO = f"""HloModule jit_sweep, entry_computation_layout={{()->f32[8]{{0}}}}

%region_add (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(f32[] %a, f32[] %b), metadata={{op_name="tick/fabric/link_sums/add"}}
}}

%fused_delivery (p0: f32[8], p1: s32[4,1], p2: f32[4]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %p1 = s32[4,1]{{1,0}} parameter(1)
  %p2 = f32[4]{{0}} parameter(2)
  ROOT %scatter.1 = f32[8]{{0}} scatter(f32[8]{{0}} %p0, s32[4,1]{{1,0}} %p1, f32[4]{{0}} %p2), update_window_dims={{}}, to_apply=%region_add, metadata={{op_name="{SCOPE}/tick/fabric/delivery/scatter-add"}}
}}

%fused_link_sum (q0: f32[8], q1: s32[4,1], q2: f32[4]) -> f32[8] {{
  %q0 = f32[8]{{0}} parameter(0)
  %q1 = s32[4,1]{{1,0}} parameter(1)
  %q2 = f32[4]{{0}} parameter(2)
  %reshape.3 = f32[4]{{0}} reshape(f32[4]{{0}} %q2), metadata={{op_name="{SCOPE}/tick/fabric/signals/squeeze"}}
  ROOT %scatter.2 = f32[8]{{0}} scatter(f32[8]{{0}} %q0, s32[4,1]{{1,0}} %q1, f32[4]{{0}} %reshape.3), update_window_dims={{}}, to_apply=%region_add
}}

%fused_select (r0: s32[16], r1: s32[4,1]) -> s32[4] {{
  %r0 = s32[16]{{0}} parameter(0)
  %r1 = s32[4,1]{{1,0}} parameter(1)
  %gather.4 = s32[4,1]{{1,0}} gather(s32[16]{{0}} %r0, s32[4,1]{{1,0}} %r1), offset_dims={{1}}, collapsed_slice_dims={{}}, start_index_map={{0}}, index_vector_dim=1, slice_sizes={{1}}, metadata={{op_name="{SCOPE}/vmap(tick)/assign/vmap(wam)/jit(searchsorted)/gather"}}
  ROOT %bitcast.5 = s32[4]{{0}} bitcast(s32[4,1]{{1,0}} %gather.4), metadata={{op_name="jit(sweep)/reshape"}}
}}

%body (arg: (s32[], f32[8], s32[4,1], f32[4])) -> (s32[], f32[8], s32[4,1], f32[4]) {{
  %arg = (s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) parameter(0)
  %gte.0 = s32[] get-tuple-element((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %arg), index=0
  %gte.1 = f32[8]{{0}} get-tuple-element((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %arg), index=1
  %gte.2 = s32[4,1]{{1,0}} get-tuple-element((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %arg), index=2
  %gte.3 = f32[4]{{0}} get-tuple-element((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %arg), index=3
  %one = s32[] constant(1)
  %add.2 = s32[] add(s32[] %gte.0, s32[] %one), metadata={{op_name="{SCOPE}/add"}}
  %fusion.10 = f32[8]{{0}} fusion(f32[8]{{0}} %gte.1, s32[4,1]{{1,0}} %gte.2, f32[4]{{0}} %gte.3), kind=kLoop, calls=%fused_delivery
  %fusion.11 = f32[8]{{0}} fusion(f32[8]{{0}} %fusion.10, s32[4,1]{{1,0}} %gte.2, f32[4]{{0}} %gte.3), kind=kCustom, calls=%fused_link_sum
  %copy.11 = f32[8]{{0}} copy(f32[8]{{0}} %fusion.11)
  ROOT %tuple.3 = (s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) tuple(s32[] %add.2, f32[8]{{0}} %copy.11, s32[4,1]{{1,0}} %gte.2, f32[4]{{0}} %gte.3)
}}

%cond (carg: (s32[], f32[8], s32[4,1], f32[4])) -> pred[] {{
  %carg = (s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) parameter(0)
  %cgte = s32[] get-tuple-element((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %carg), index=0
  %bound = s32[] constant(64)
  ROOT %lt.1 = pred[] compare(s32[] %cgte, s32[] %bound), direction=LT, metadata={{op_name="jit(sweep)/vmap(vmap(engine/chunk))/while/body/while/cond/lt"}}
}}

ENTRY %main.1 (x: f32[8], i: s32[4,1], u: f32[4], t: s32[16]) -> (f32[8], s32[4]) {{
  %x = f32[8]{{0}} parameter(0)
  %i = s32[4,1]{{1,0}} parameter(1)
  %u = f32[4]{{0}} parameter(2)
  %t = s32[16]{{0}} parameter(3)
  %zero = s32[] constant(0)
  %copy.0 = s32[] copy(s32[] %zero)
  %tuple.1 = (s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) tuple(s32[] %copy.0, f32[8]{{0}} %x, s32[4,1]{{1,0}} %i, f32[4]{{0}} %u)
  %while.1 = (s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) while((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %tuple.1), condition=%cond, body=%body, metadata={{op_name="jit(sweep)/vmap(vmap(engine/chunk))/while/body/while"}}
  %while.2 = (s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) while((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %tuple.1), condition=%cond, body=%body, metadata={{op_name="{SCOPE}/tick/assign/vmap(wam)/jit(searchsorted)/vmap()/while"}}, backend_config={{"known_trip_count":{{"n":"5"}},"known_init_step":{{"init":"0","step":"1"}}}}
  %fusion.12 = s32[4]{{0}} fusion(s32[16]{{0}} %t, s32[4,1]{{1,0}} %i), kind=kLoop, calls=%fused_select
  %gte.9 = f32[8]{{0}} get-tuple-element((s32[], f32[8]{{0}}, s32[4,1]{{1,0}}, f32[4]{{0}}) %while.1), index=1
  ROOT %tuple.2 = (f32[8]{{0}}, s32[4]{{0}}) tuple(f32[8]{{0}} %gte.9, s32[4]{{0}} %fusion.12)
}}
"""


def test_op_scopes_follow_fusions_and_loops():
    scopes = op_scopes([SCOPED_HLO])
    ops = scopes["ops"]
    assert ops["fusion.10"] == SCOPE + "/tick/fabric/delivery/scatter-add"
    # the root names nothing: its combiner's scope, not its operand's
    assert ops["fusion.11"] == "tick/fabric/link_sums/add"
    # looked through the bitcast at the root
    assert ops["fusion.12"] == SCOPE + "/vmap(tick)/assign/vmap(wam)/jit(searchsorted)/gather"
    # no metadata of its own: the loop's that holds it
    assert ops["copy.11"] == "jit(sweep)/vmap(vmap(engine/chunk))/while/body/while"
    assert ops["add.2"] == SCOPE + "/add"
    # stated by the condition (count from 0 by 1 below 64), by the backend
    assert scopes["trips"] == {"while.1": 64, "while.2": 5}
    assert scope_path(ops["fusion.12"]) == [
        "sweep", "engine", "chunk", "while", "body", "while", "body",
        "closed_call", "tick", "assign", "wam", "searchsorted", "gather"]
    assert scope_path(None) == []


def test_under_matches_whole_scopes_in_order():
    tr = ScopedTrace({}, [], op_kinds([SCOPED_HLO]), scopes=op_scopes([SCOPED_HLO]))
    assert tr.under("fusion.12", "tick/assign/wam")
    assert tr.under("fusion.12", "tick/assign")
    assert tr.under("fusion.12", "engine/chunk")
    assert not tr.under("fusion.12", "tick/fabric")
    assert not tr.under("fusion.12", "assign/tick")
    assert not tr.under("fusion.12", "tick/assig")
    assert tr.under("fusion.11", "tick/fabric/link_sums")
    assert not tr.under("fusion.11", "tick/fabric/signals")
    assert tr.under("copy.11", "engine/chunk") and not tr.under("copy.11", "tick")
    assert not tr.under("no.such.op", "tick")


CHUNK = "jit(sweep)/vmap(engine/chunk)/while/body/while"


def scoped():
    """Two chips over two calls of 5 s: each call runs chunk loops of 64
    ticks (three in the first call, one in the second), on both chips, and
    a loop of 5 inside the tick that is no chunk loop; ops under each of
    the tick's three components, under `engine/keys`, and one that names
    no scope."""
    scopes = {
        "ops": {
            "fusion.a": f"{CHUNK}/body/closed_call/vmap(tick)/assign/vmap(wam)/gather",
            "fusion.f": f"{CHUNK}/body/closed_call/tick/fabric/link_sums/scatter-add",
            "fusion.s": f"{CHUNK}/body/closed_call/tick/sender/select",
            "fusion.k": "jit(sweep)/vmap(engine/keys)/threefry2x32",
            "copy.1": None,
            "while.c": CHUNK,
            "while.o": "jit(sweep)/vmap(engine/chunk)/while",
            "while.t": f"{CHUNK}/body/closed_call/tick/assign/vmap(wam)/while",
        },
        "trips": {"while.c": 64, "while.t": 5},
    }
    kinds = {"fusion.a": "gather", "fusion.f": "scatter", "fusion.s": "select",
             "fusion.k": "xor", "copy.1": "copy", "while.c": "while",
             "while.o": "while", "while.t": "while"}

    def chip(scale):
        return [("fusion.k", 0.0, 0.5 * scale), ("while.o", 0.5, 3.0),
                ("while.c", 0.5, 1.0), ("fusion.a", 0.5, 0.4 * scale),
                ("while.t", 0.5, 0.4), ("fusion.f", 1.0, 0.3 * scale),
                ("while.c", 1.5, 1.0), ("fusion.s", 1.5, 0.2 * scale),
                ("while.c", 2.5, 1.0), ("copy.1", 3.6, 0.1 * scale),
                ("while.c", 5.5, 1.0), ("fusion.a", 5.5, 0.4 * scale)]

    ops = {"/device:TPU:0": chip(1.0), "/device:TPU:1": chip(0.5)}
    spans = [("dispatch", 0.0, 4.0), ("read_results", 4.0, 5.0),
             ("post_process", 5.0, 5.0), ("dispatch", 5.0, 9.0),
             ("read_results", 9.0, 10.0), ("post_process", 10.0, 10.0)]
    return ScopedTrace(ops, spans, kinds, scopes=scopes)


def kind_share(tr, kind):
    hit, total = tr.kind_seconds(kind)
    return 100.0 * hit / total


def test_scope_shares_partition_the_op_time():
    tr = scoped()
    # op time over both chips: 1.5 x (0.5 + 0.4 + 0.3 + 0.2 + 0.1 + 0.4)
    total = 1.5 * 1.9
    assert tr.scope_seconds("tick") == pytest.approx((1.5 * 1.3, total))
    shares = tr.shares()
    assert set(shares) == set(SHARES) | {OUTSIDE}
    assert shares == pytest.approx({
        "tick.assign_share": 100 * 0.8 / 1.9,
        "tick.fabric_share": 100 * 0.3 / 1.9,
        "tick.sender_share": 100 * 0.2 / 1.9,
        "engine.outside_tick_share": 100 * 0.6 / 1.9,
    })
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    assert tr.scope_seconds("tick/assign/wam") == pytest.approx((1.5 * 0.8, total))
    assert tr.scope_seconds("engine/keys") == pytest.approx((1.5 * 0.5, total))


def test_ticks_per_call_counts_chunk_loop_runs():
    tr = scoped()
    # the busiest chip runs the chunk loop 4 times, 64 ticks each, over 2
    # dispatches; the loop inside the tick and the outer loop are no chunks
    runs = tr.loop_runs(tr.busiest())
    assert sorted(runs) == [("while.c", 64)] * 4 + [("while.t", 5)]
    assert tr.ticks_per_call() == pytest.approx(4 * 64 / 2)


def test_condensed_trace_reads_the_same():
    tr = scoped()
    small = tr.condensed()
    assert sum(len(v) for v in small.ops.values()) < sum(len(v) for v in tr.ops.values())
    assert small.shares() == pytest.approx(tr.shares())
    assert small.ticks_per_call() == pytest.approx(tr.ticks_per_call())
    assert small.components() == pytest.approx(tr.components())
    for kind in ("gather", "scatter"):
        assert kind_share(small, kind) == pytest.approx(kind_share(tr, kind))
    again = ScopedTrace.from_json(json.loads(json.dumps(small.to_json())))
    assert again.scopes == small.scopes
    assert again.ticks_per_call() == pytest.approx(128.0)


def test_excerpt_keeps_the_scopes_of_its_ops():
    part = scoped().excerpt(5.0, 100)
    assert set(part.scopes["ops"]) == {"while.c", "fusion.a"}
    assert part.scopes["trips"] == {"while.c": 64}


def test_scope_readings_find_nothing_without_scopes():
    # the programs' HLO unknown
    unknown = scoped()
    unknown.scopes = None
    assert unknown.shares() is None and unknown.ticks_per_call() is None
    assert unknown.scope_seconds("tick") is None and unknown.components() == {}
    # a program whose ops carry no tick or engine scope (before the engine
    # named them): nothing is read, and nothing raises
    plain = scoped()
    plain.scopes = {"ops": {n: "jit(sweep)/while/body/add" for n in plain.scopes["ops"]},
                    "trips": {"while.c": 64}}
    assert plain.shares() is None and plain.ticks_per_call() is None
    # no dispatch span: no call to count the ticks of
    no_calls = scoped()
    no_calls.spans = [("read_results", 0.0, 10.0)]
    assert no_calls.ticks_per_call() is None


SCOPED_CHIP_TRACE = os.path.join(ROOT, "bench", "testdata",
                                 "ft8.permutation.scoped.trace.json.gz")


def test_recorded_chip_trace_by_scope():
    """Both traced calls of an `ft8.permutation` window on a TPU v5e
    (`--trace 1 --keep-trace`, seed 2147500111), condensed by
    `ScopedTrace.condensed` to one event per op and every loop run, with the
    scopes of the program's compiled HLO.  The chunk loop ran 16 times a
    call: the ECMP point never settles, so every call runs its whole
    1024-tick horizon."""
    tr = read(SCOPED_CHIP_TRACE)
    assert sum(1 for name, _, _ in tr.spans if name == "dispatch") == 2
    shares = tr.shares()
    assert shares == pytest.approx({
        "tick.assign_share": 65.3198172687,
        "tick.fabric_share": 22.5767849400,
        "tick.sender_share": 11.7691639075,
        "engine.outside_tick_share": 0.334233883771,
    }, rel=1e-6)
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.01)
    assert tr.ticks_per_call() == 1024.0
    runs = tr.loop_runs(tr.busiest())
    assert sum(1 for name, _ in runs if name == "while.635") == 32
    # the op time by kind reads what the whole trace read
    assert kind_share(tr, "gather") == pytest.approx(77.1573331417, rel=1e-6)
    assert kind_share(tr, "scatter") == pytest.approx(18.8934091426, rel=1e-6)
    # WAM's searchsorted gathers (`fusion.430`) and RAND_ADAPTIVE's
    # (`fusion.433`, a branch no point takes, run under the vmapped
    # switch) lie under `tick/assign`, which holds more besides
    ops = tr.op_seconds()
    searches = 100 * (ops["fusion.430"] + ops["fusion.433"]) / sum(ops.values())
    assert searches == pytest.approx(64.5784375764, rel=1e-6)
    assert shares["tick.assign_share"] >= searches
    assert tr.under("fusion.430", "tick/assign/wam")
    assert tr.under("fusion.389", "tick/fabric/link_sums")
    assert tr.under("fusion.397", "tick/fabric/delivery")


def test_ticks_per_call_counts_a_loop_that_starts_before_the_window():
    # the device's clock runs a little ahead of the host's: the first chunk
    # loop seems to start 0.6 s before the first dispatch, and still counts
    tr = scoped()
    tr.ops = {dev: [(n, s - 0.6, d) for n, s, d in evs] for dev, evs in tr.ops.items()}
    assert tr.ticks_per_call() == pytest.approx(4 * 64 / 2)
    # a loop that ends before the window opens is no run of a traced call
    tr.ops = {dev: [("while.c", -2.0, 1.0)] + evs for dev, evs in tr.ops.items()}
    assert tr.ticks_per_call() == pytest.approx(4 * 64 / 2)


def test_components_split_the_tick_and_the_engine():
    seconds = scoped().components()
    assert list(seconds)[:3] == ["tick", "tick/assign", "tick/assign/wam"]
    assert seconds == pytest.approx({
        "tick": 1.5 * 1.3, "tick/assign": 1.5 * 0.8, "tick/assign/wam": 1.5 * 0.8,
        "tick/fabric": 1.5 * 0.3, "tick/fabric/link_sums": 1.5 * 0.3,
        "tick/fabric/signals": 0.0, "tick/fabric/delivery": 0.0,
        "tick/fabric/feedback": 0.0, "tick/sender": 1.5 * 0.2,
        "engine/keys": 1.5 * 0.5, "engine/chunk": 1.5 * 1.3,
        "engine/settle": 0.0, "engine/result": 0.0})


def test_recording_reads_as_a_plain_trace_too():
    # the scopes ride beside what `bench.trace` reads, which leaves them be
    from bench.trace import read as read_plain

    plain, scoped_ = read_plain(SCOPED_CHIP_TRACE), read(SCOPED_CHIP_TRACE)
    assert plain.ops == scoped_.ops and plain.kinds == scoped_.kinds
    assert not hasattr(plain, "scopes")


def test_a_kept_run_is_read_with_its_programs_scopes(tmp_path, capsys):
    """A `--keep-trace` run of the tiny cell on the CPU: its programs'
    HLO carries the engine's scopes and the chunk loop's trip count.  (The
    CPU runs its ops on host threads, which the trace reduction does not
    count as a device, so no share is read here.)"""
    import io
    import time

    from bench.harness import run_cell
    from bench.scopes import CHUNK, load_kept, main
    from bench.tests.helpers import TINY_PERMUTATION, tiny_root

    root = tiny_root(tmp_path / "root")
    keep = str(tmp_path / "kept")
    run_cell(root, "tiny.permutation", 7, 0.1, True, t_start=time.time(),
             need_accelerator=False, compile_cache=False, keep_trace=keep,
             out=io.StringIO(), err=io.StringIO())
    tr = load_kept(keep)
    ops = tr.scopes["ops"]
    for component in ("tick/assign/wam", "tick/fabric/link_sums",
                      "tick/fabric/delivery", "tick/sender", "engine/settle"):
        assert any(tr.under(name, component) for name in ops), component
    chunks = {tr.scopes["trips"][n] for n in tr.scopes["trips"]
              if tr.under(n, CHUNK) and not tr.under(n, "tick")}
    assert chunks == {TINY_PERMUTATION["exit_chunk"]}

    saved = str(tmp_path / "kept.json.gz")
    assert main([keep, "--save", saved]) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert set(line) == {"shares", "ticks_per_call", "seconds"}
    assert read(saved).scopes == tr.condensed().scopes
    save(tr, saved)     # what `bench.trace` saves of a scoped trace
    assert read(saved).scopes == tr.scopes


PLAIN_CHIP_TRACE = os.path.join(ROOT, "bench", "testdata",
                                "ft8.permutation.trace.json.gz")


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_readers_read_the_recorded_chip_trace(name):
    tr = read(SCOPED_CHIP_TRACE)
    want = (tr.ticks_per_call() if name == "engine.ticks_per_call"
            else tr.shares()[name])
    assert reader(name)(tr) == want


def test_scope_share_readers_partition_the_op_time():
    tr = read(SCOPED_CHIP_TRACE)
    assert SCOPE_METRICS[:4] == (*SHARES, OUTSIDE)
    shares = [reader(name)(tr) for name in SCOPE_METRICS[:4]]
    assert sum(shares) == pytest.approx(100.0, abs=1e-9)
    assert min(shares) > 0


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_readers_find_nothing_without_scopes(name):
    # a recording from before the traced programs' HLO was kept
    unscoped = read(PLAIN_CHIP_TRACE)
    assert unscoped.scopes is None and reader(name)(unscoped) is None
    # scopes in which no op lies under `tick` or `engine/chunk`
    plain = scoped()
    plain.scopes = {"ops": {n: "jit(sweep)/while/body/add" for n in plain.scopes["ops"]},
                    "trips": {"while.c": 64}}
    assert reader(name)(plain) is None


@pytest.mark.parametrize("recording", [PLAIN_CHIP_TRACE, SCOPED_CHIP_TRACE])
@pytest.mark.parametrize("name", ["device.idle_share", "tick.gather_share",
                                  "tick.scatter_share", "mesh.collective_share"])
def test_kind_readers_read_the_same_with_the_scopes(name, recording):
    """What the harness hands the readers now, the trace with its scopes,
    reads as the plain trace read for the readers by kind and for the
    breakdown."""
    from bench.trace import read as read_plain

    plain, with_scopes = read_plain(recording), read(recording)
    assert isinstance(with_scopes, ScopedTrace)
    assert reader(name)(with_scopes) == reader(name)(plain)
    assert with_scopes.breakdown() == plain.breakdown()
    assert (with_scopes.busy_s(), with_scopes.window_s()) == (plain.busy_s(), plain.window_s())
