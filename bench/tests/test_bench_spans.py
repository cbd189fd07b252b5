"""The engine's spans put on the device's clock (`bench/spans.py`), the
`step_d2h_mb` reader, and the trace reducers that were there before them.

A small hand-built trace first: one device plane with two step programs
and a slice, and the host spans of two steps, every number chosen so the
expected values can be worked out by hand.  Then the trace recorded on a
TPU v5e by `bench/tools/record_span_trace.py`, and the trace of
`test_bench_trace_reduce.py`, on which every reducer returns what it
returned before this module existed."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import model, run, spans
from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data"
E = tr.Event

# Device clock (ns).  The prefill program runs [1000, 1400), a slice
# [1450, 1460), the decode program [2000, 2300).
OPS = [E("%prefill_op", 1000, 400), E("%slice", 1450, 10),
       E("%decode_op", 2000, 300)]
MODULES = [E("jit__prefill(1)", 1000, 400), E("jit_slice(2)", 1450, 10),
           E("jit__decode(3)", 2000, 300)]
# Host clock: the device's times plus a shift of 5000 to 5150.
SPANS = sorted([
    E("serve.step", 5000, 1000),
    E("serve.schedule", 5000, 100),
    E("serve.prefill.launch", 5100, 200),
    E("serve.prefill.dispatch", 5250, 50),     # 5250 - 1000: shift >= 4250
    E("serve.prefill.fetch", 5300, 600),       # 5900 - 1400: shift <= 4500
    E("serve.account", 5950, 50),
    E("serve.step", 6800, 700),
    E("serve.decode.launch", 6800, 250),
    E("serve.decode.dispatch", 7000, 50),      # 7000 - 2000: shift >= 5000
    E("serve.decode.fetch", 7050, 400),        # 7450 - 2300: shift <= 5150
    E("serve.sample", 7450, 50),
], key=lambda e: (e.start_ns, -e.dur_ns))


@pytest.fixture
def trace():
    return tr.Trace({"/device:TPU:0": list(OPS)},
                    {"/device:TPU:0": list(MODULES)}, [])


def test_span_offset_brackets_the_shift(trace):
    # lower: the larger of 4250 and 5000; upper: the smaller of 4500 and
    # 5150 — the prefill's fetch ends too soon for the decode's shift
    assert spans.span_offset(trace, SPANS) is None
    # without the prefill's fetch (a chunk that is not a prompt's last)
    # only the decode bounds it from above: slack 5150 - 5000
    unfetched = [s for s in SPANS if s.name != "serve.prefill.fetch"]
    assert spans.span_offset(trace, unfetched) == (5000, 150)


def test_runtime_events_narrow_the_bracket(trace):
    """One enqueue and one done per program run (the slice included) bound
    the shift too: enqueues at 6030, 6460, 7040 give 5030, 5010, 5040;
    dones at 6480, 6520, 7400 give 5080, 5060, 5100."""
    unfetched = [s for s in SPANS if s.name != "serve.prefill.fetch"]
    enqueued = [E(spans.ENQUEUED, t, 5) for t in (6030, 6460, 7040)]
    done = [E(spans.DONE, t, 5) for t in (6480, 6520, 7400)]
    assert spans.span_offset(trace, unfetched + enqueued + done) == (5040,
                                                                      20)
    # events that do not come one per run are not paired
    assert spans.span_offset(trace, unfetched + enqueued[:2] + done) == (
        5000, 60)


def test_span_offset_needs_pairs(trace):
    assert spans.span_offset(trace, []) is None
    no_decode = [s for s in SPANS if s.name != "serve.decode.dispatch"]
    assert spans.span_offset(trace, no_decode) is None    # 2 runs, 1 call
    no_fetch = [s for s in SPANS if not s.name.endswith(".fetch")]
    assert spans.span_offset(trace, no_fetch) is None     # no upper bound


def test_idle_in_step_by_innermost_span(trace):
    unfetched = [s for s in SPANS if s.name != "serve.prefill.fetch"]
    lo, hi = 5000, 7500
    # shift 5000: busy [6000, 6400), [6450, 6460), [7000, 7300)
    idle = dict(spans.idle_in_step(trace, unfetched, lo, hi, 5000))
    # idle: [5000, 6000), [6400, 6450), [6460, 7000), [7300, 7500).  In
    # step 1 the schedule [5000, 5100), the launch [5100, 5250) before its
    # dispatch [5250, 5300), the step itself [5300, 5950) and the account
    # [5950, 6000); between the steps "none"; in step 2 the launch
    # [6800, 7000) before the program starts, the fetch [7300, 7450)
    # after it ends, and the sample [7450, 7500).
    want = {"serve.schedule": 100, "serve.prefill.launch": 150,
            "serve.prefill.dispatch": 50, "serve.step": 650,
            "serve.account": 50, "none": 390, "serve.decode.launch": 200,
            "serve.decode.fetch": 150, "serve.sample": 50}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()},
                                 abs=1e-15)
    # every idle nanosecond of [lo, hi) is charged once
    assert sum(idle.values()) == pytest.approx(
        (hi - lo - 400 - 10 - 300) * 1e-9, abs=1e-15)


def test_host_idle_ms_per_step(trace):
    unfetched = [s for s in SPANS if s.name != "serve.prefill.fetch"]
    # the idle charged to serve.* spans (all but "none": 2500 - 710 - 390
    # = 1400 ns) over the two steps inside [5000, 7500)
    assert spans.host_idle_ms(trace, unfetched, 5000, 7500) == \
        pytest.approx(1400e-6 / 2)
    # a step that leaves the span does not count
    # (the fetch's idle is cut to [7300, 7400), the sample's falls out)
    assert spans.host_idle_ms(trace, unfetched, 5000, 7400) == \
        pytest.approx((1400 - 100) * 1e-6 / 1)
    assert spans.host_idle_ms(trace, [], 5000, 7500) is None


# ------------------------------------------------------------ step_d2h_mb
def _ctx(rows, on_chip=True):
    return SimpleNamespace(
        rows=rows, peaks=object() if on_chip else None,
        trace=tr.Trace({"/device:TPU:0": list(OPS)}, {}, []))


def test_step_d2h_mb_reads_the_ledger():
    read = model.load_module(run.BENCH / "metrics" / "step_d2h_mb.py").read
    rows = [{"d2h_bytes": 9_732_096}, {"d2h_bytes": 10_340_352},
            {"d2h_bytes": 0}]
    assert read(_ctx(rows)) == pytest.approx(
        (9_732_096 + 10_340_352) / 3 / 1e6)
    assert read(_ctx(rows, on_chip=False)) is None
    # an engine whose ledger has no such counter reports nothing
    assert read(_ctx([{"decode_tokens": 4}])) is None
    assert read(_ctx([])) is None


# ------------------------------------------- the reducers already there
def test_reducers_on_two_steps_are_unchanged():
    """Every reducer of `trace_reduce` on the two-step trace, as it read
    before the engine had spans (whole outputs, not only the top)."""
    t = tr.load(DATA / "two_steps.xplane.pb")
    assert tr.top_ops(t, 10) == [
        ["gpp_matmul", 1.3772e-05], ["paged_attn", 1.1177e-05],
        ["reshape bf16[17,16,256]", 4.463e-06],
        ["reshape bf16[4,2,4,128]", 7.5e-08],
        ["multiply_convert_fusion bf16[4,1024]", 2.3e-08],
        ["copy-start bf16[8,2048]", 1.2e-08],
        ["copy-done bf16[8,2048]", 5e-09]]
    starts = [a.start_ns for a in t.annotations if a.name == "engine.step"]
    offset = tr.device_offset(t, starts)
    assert offset == 865216.0
    assert tr.idle_gaps(t, 40169714, 47122262, offset) == [
        ["harness.poll", 0.00463374], ["engine.step", 0.002268852],
        ["none", 2.0429e-05]]
    assert tr.busy_ns(t) == 29527.0
    assert tr.staged_kernel_time(t, "paged_attn") == (15715.0, 2)
    assert tr.module_time(t, "step") == (30090.0, 2)
    # it carries no engine spans
    assert not [s for s in spans.load(DATA / "two_steps.xplane.pb")
                if s.name.startswith("serve.")]


# ------------------------------------------------ the recorded chip trace
@pytest.fixture(scope="module")
def recorded():
    path = DATA / "serve_spans.xplane.pb"
    return tr.load(path), spans.load(path)


def test_recorded_spans_nest_in_steps(recorded):
    trace, sp = recorded
    engine = [s for s in sp if s.name.startswith("serve.")]
    steps = [s for s in engine if s.name == "serve.step"]
    assert len(steps) == 6
    assert all(any(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns
                   for t in steps) for s in engine)
    # the step programs ran in the order their dispatches were made
    kinds = [spans.PROGRAMS[m.name.split("(")[0]]
             for m in trace.modules["/device:TPU:0"]
             if m.name.split("(")[0] in spans.PROGRAMS]
    calls = [s.name.split(".")[1] for s in engine
             if s.name.endswith(".dispatch")]
    assert kinds == calls == ["prefill"] * 3 + ["decode"] * 4
    # and the runtime left one enqueue and one done per program run
    assert sum(s.name == spans.ENQUEUED for s in sp) == 11
    assert sum(s.name == spans.DONE for s in sp) == 11


def test_recorded_offset(recorded):
    trace, sp = recorded
    engine = [s for s in sp if s.name.startswith("serve.")]
    # the engine's spans alone: the last decode's dispatch opened 738859 ns
    # (device clock) before its program started, and its fetch ended
    # 2022648 ns after the program ended (90861853 - 88839205), the least
    # of the six fetched calls
    assert spans.span_offset(trace, engine) == (738859, 2022648 - 738859)
    # with the runtime's events: the eleventh program's enqueue
    # (90171229 - 88820334 = 1350895) and done (90448259 - 88839205 =
    # 1609054) bound it closer
    assert spans.span_offset(trace, sp) == (1350895, 1609054 - 1350895)


def test_recorded_idle_in_step(recorded):
    trace, sp = recorded
    steps = [s for s in sp if s.name == "serve.step"]
    lo, hi = steps[0].start_ns, steps[-1].end_ns
    idle = dict(spans.idle_in_step(trace, sp, lo, hi, 1350895))
    # every idle nanosecond of the six steps is charged once: the span
    # less the device's 159581 busy ns
    assert tr.busy_ns(trace) == 159581
    assert sum(idle.values()) == pytest.approx((hi - lo - 159581) * 1e-9)
    # the recording slept 2 ms in each scheduler call and 1 ms between
    # steps, with the device idle
    assert idle["serve.schedule"] > 6 * 2e-3
    assert 6 * 1e-3 < idle["none"] < idle["serve.schedule"]
    assert idle == pytest.approx({
        "serve.schedule": 0.013911117, "none": 0.006947019,
        "serve.decode.launch": 0.00491036, "serve.prefill.fetch": 0.003843117,
        "serve.decode.fetch": 0.003292782,
        "serve.prefill.dispatch": 0.00327723,
        "serve.prefill.launch": 0.002192989,
        "serve.decode.dispatch": 0.001937809, "serve.step": 0.00053368,
        "serve.account": 0.000409598, "serve.sample": 0.000322641},
        abs=1e-12)
    assert spans.host_idle_ms(trace, sp, lo, hi) == pytest.approx(
        1e3 * (sum(idle.values()) - idle["none"]) / 6)
