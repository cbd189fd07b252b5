"""The serving engine's own spans in a profiler trace, and the device's idle
time inside a step put down to them.

`ServingEngine.step()` runs inside a `serve.step` span with one child span
per stage (`serve.schedule`, `serve.copy`, `serve.prefill.launch`,
`serve.prefill.fetch`, `serve.decode.launch`, `serve.decode.fetch`,
`serve.verify.launch`, `serve.verify.fetch`, `serve.sample`,
`serve.account`; `repro.obs.trace.phase`), and a `serve.*.dispatch` span
inside each launch covers the jitted call alone.  They are host events of
the same `.xplane.pb` that `bench/trace_reduce.py` reads.  In the trace of
a program that lacks them `span_offset` finds nothing to pair, and every
function below returns nothing.

The device's clock and the host's are not aligned in the trace.  Two kinds
of host event bracket the shift.  The engine's own: no step program starts
before its dispatch span starts, and none ends after its fetch span ends
(the launch span's start would bound it too, but loosely: it opens before
the step's inputs are built, a millisecond or more before the call).  And
the TPU runtime's, where the trace has one of each per program the device
ran: a program is enqueued (`DoEnqueueProgram`) before it can start, and
the runtime hears it is done (`tpu::System::Execute=>Done`) after it ends.
"""
from __future__ import annotations

import dataclasses

from bench import trace_reduce as tr

PREFIX = "serve."
# step program (the jitted function's module name) -> its spans' kind
PROGRAMS = {"jit__prefill": "prefill", "jit__decode": "decode",
            "jit__verify": "verify"}
ENQUEUED = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"


def load(path) -> "list[tr.Event]":
    """The engine's `serve.*` host events and the runtime's per-program
    `ENQUEUED` and `DONE` events in the trace at `path`, by start (the
    outer first where two start together)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(tr.find_xplane(path)))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [tr.Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name.startswith(PREFIX) or e.name in (ENQUEUED,
                                                                DONE)]
    return sorted(out, key=lambda e: (e.start_ns, -e.dur_ns))


def _engine(spans: "list[tr.Event]") -> "list[tr.Event]":
    return [s for s in spans if s.name.startswith(PREFIX)]


def span_offset(trace: tr.Trace, spans: "list[tr.Event]"
                ) -> "tuple[float, float] | None":
    """(shift, slack) in ns that put the first device plane's programs on
    the host's clock: the least shift under which no program starts before
    a host event that must precede it, and how far it could grow before a
    program would end after a host event that must follow it.  The runs of
    `jit__prefill`, `jit__decode` and `jit__verify`, in order, pair with
    the `serve.*.dispatch` spans, in order, and each fetched call with its
    fetch span (a prefill chunk that is not a prompt's last has none); the
    runtime's `ENQUEUED` and `DONE` events pair with every program run,
    where there is one of each per run.  None when the step programs and
    the dispatches do not pair, when nothing bounds the shift from above,
    or when the slack is negative."""
    mods = next(iter(trace.modules.values()), [])
    runs = [(PROGRAMS[m.name.split("(")[0]], m) for m in mods
            if m.name.split("(")[0] in PROGRAMS]
    calls, fetch_of = [], {}
    pending: "dict[str, int]" = {}
    for sp in _engine(spans):
        kind, _, stage = sp.name[len(PREFIX):].rpartition(".")
        if stage == "dispatch":
            pending[kind] = len(calls)
            calls.append((kind, sp))
        elif stage == "fetch" and kind in pending:
            fetch_of[pending.pop(kind)] = sp
    if not runs or [k for k, _ in runs] != [k for k, _ in calls]:
        return None
    lows = [sp.start_ns - run.start_ns
            for (_, run), (_, sp) in zip(runs, calls)]
    highs = [fetch_of[i].end_ns - runs[i][1].end_ns for i in fetch_of]
    enqueued = [s for s in spans if s.name == ENQUEUED]
    done = [s for s in spans if s.name == DONE]
    if len(enqueued) == len(mods):
        lows += [e.start_ns - m.start_ns for e, m in zip(enqueued, mods)]
    if len(done) == len(mods):
        highs += [d.start_ns - m.end_ns for d, m in zip(done, mods)]
    if not highs or min(highs) < max(lows):
        return None
    return max(lows), min(highs) - max(lows)


def idle_in_step(trace: tr.Trace, spans: "list[tr.Event]", lo_ns: float,
                 hi_ns: float, offset_ns: float) -> "list[list]":
    """[span, seconds]: the device's idle time in [lo, hi) (host clock)
    split by the innermost `serve.*` span open at each moment, "none"
    where none was, most first (`trace_reduce.idle_gaps` over the engine's
    spans in place of the harness's annotations)."""
    return tr.idle_gaps(
        dataclasses.replace(trace, annotations=_engine(spans)),
        lo_ns, hi_ns, offset_ns)


def host_idle_ms(trace: tr.Trace, spans: "list[tr.Event]", lo_ns: float,
                 hi_ns: float) -> "float | None":
    """The device's idle time charged to `serve.*` spans in [lo, hi), in
    ms per `serve.step` span that lies wholly inside it: how long the chip
    waits on the engine's host work in each step.  None without spans that
    pair with the device's programs."""
    found = span_offset(trace, spans)
    steps = sum(1 for s in spans if s.name == "serve.step"
                and lo_ns <= s.start_ns and s.end_ns <= hi_ns)
    if found is None or not steps:
        return None
    idle = idle_in_step(trace, spans, lo_ns, hi_ns, found[0])
    return 1e3 * sum(v for k, v in idle if k != "none") / steps
