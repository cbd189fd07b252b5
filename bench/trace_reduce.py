"""Reduce a `jax.profiler` trace (an `.xplane.pb`) to the numbers the
per-layer metrics read: device busy time and idle share, time per step
program, time per kernel, the device operations that took most time, and
the idle gaps by the host annotation that was open during each.

Only the device planes (`/device:TPU:<n>`, lines "XLA Ops" and "XLA
Modules") and the harness's own host annotations are read.  The device's
clock and the host's are not aligned in the trace: `device_offset` finds the
shift from the step log (a program cannot start before the host step that
launched it).
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path

# How each kernel is found among the device's operations, kept in this one
# place.  Every Pallas call is a `tpu_custom_call`.  A named call (the
# Pallas call's `name`, since the kernels carry one) is told by its
# instruction's name, `%gpp_matmul.12 = ...`.  An unnamed one is told by
# its operands: the paged attention kernel takes its block tables and
# positions as two int32 scalar-prefetch operands first; the GPP matmuls
# take their activations first, 3-D (experts, rows, K) for the grouped
# kernel and 2-D (rows, K) for the plain one.
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
_INSTR_NAME = re.compile(r"(?:ROOT )?%([\w\-]+)\S* = ")
_NAMED = {"gpp_matmul": "gpp_matmul",
          "gpp_matmul_grouped": "gpp_matmul_grouped",
          "paged_attention": "paged_attn"}
_TYPE = r"(\w+)\[([^\]]*)\]"
# a trace's op shows each operand's type in the call, `bf16[8,1024]{1,0:
# T(8,128)(2,1)} %x.1`; a compiled module's text only in its layout
# constraints, `operand_layout_constraints={bf16[8,1024]{1,0}, ...}`
_OPERANDS = (re.compile(rf"custom-call\({_TYPE}(?:\{{[^}}]*\}})? [^\s,]+"
                        rf"(?:, {_TYPE})?"),
             re.compile(rf"operand_layout_constraints=\{{{_TYPE}\{{[^}}]*\}}"
                        rf"(?:, {_TYPE})?"))


def _first_operands(op: str) -> "list[tuple[str, int]]":
    """(dtype, rank) of a call's first two operands (fewer if it has
    fewer, none where its text shows no types)."""
    for pattern in _OPERANDS:
        m = pattern.search(op)
        if m:
            dts, dims = m.groups()[0::2], m.groups()[1::2]
            return [(dt, d.count(",") + 1 if d else 0)
                    for dt, d in zip(dts, dims) if dt is not None]
    return []


def kernel_of(op: str) -> "str | None":
    """The kernel an operation's HLO text is a call of, or None."""
    if CUSTOM_CALL not in op:
        return None
    m = _INSTR_NAME.match(op)
    if m and m.group(1) in _NAMED:
        return _NAMED[m.group(1)]
    first = _first_operands(op)
    if [dt for dt, _ in first] == ["s32", "s32"]:
        return "paged_attn"
    return ("gpp_matmul_grouped" if first and first[0][1] == 3
            else "gpp_matmul")


KERNELS = {k: (lambda op, k=k: kernel_of(op) == k)
           for k in ("paged_attn", "gpp_matmul", "gpp_matmul_grouped")}

HARNESS_ANNOTATIONS = ("harness.submit", "engine.step", "harness.poll",
                       "harness.wait")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: "dict[str, list[Event]]"       # device plane -> its XLA Ops
    modules: "dict[str, list[Event]]"   # device plane -> its program runs
    annotations: "list[Event]"          # the harness's host annotations


def find_xplane(path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(find_xplane(path)))
    ops, modules, notes = {}, {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = sorted(evs, key=lambda e: e.start_ns)
                elif line.name == "XLA Modules":
                    modules[plane.name] = sorted(evs,
                                                 key=lambda e: e.start_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                notes += [Event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name in HARNESS_ANNOTATIONS]
    return Trace(ops, modules, sorted(notes, key=lambda e: e.start_ns))


def union(intervals) -> "list[tuple[float, float]]":
    """Merged, sorted (start, end) intervals."""
    out: "list[list[float]]" = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace) -> float:
    """Time in which an operation ran, averaged over the device planes."""
    if not trace.ops:
        return 0.0
    per = [sum(e - s for s, e in union((ev.start_ns, ev.end_ns)
                                       for ev in evs))
           for evs in trace.ops.values()]
    return sum(per) / len(per)


def module_time(trace: Trace, program: str) -> "tuple[float, int]":
    """(total ns, runs) of the step program `program` (the jitted
    function's name: its module is `jit_<name>(<hash>)`)."""
    tot, n = 0.0, 0
    for evs in trace.modules.values():
        for ev in evs:
            if ev.name.split("(")[0] == f"jit_{program}":
                tot += ev.dur_ns
                n += 1
    return tot, n


def kernel_time(trace: Trace, kernel: str) -> "tuple[float, int]":
    """(total ns, calls) of `kernel`'s device operations."""
    match = KERNELS[kernel]
    tot, n = 0.0, 0
    for evs in trace.ops.values():
        for ev in evs:
            if match(ev.name):
                tot += ev.dur_ns
                n += 1
    return tot, n


_INSTR = re.compile(r"%([\w.\-]+) = ")
_VMEM_OPERAND = re.compile(r"S\(1\)\} %([\w.\-]+)")


def _operands_in_vmem(hlo: str) -> "list[str]":
    """The names of a custom call's operands that live in VMEM (memory
    space S(1)): XLA put them there with an op of their own before the
    call."""
    start = hlo.find("custom-call(")
    end = hlo.find(CUSTOM_CALL)
    if start < 0 or end < 0:
        return []
    return _VMEM_OPERAND.findall(hlo[start:end])


def staged_kernel_time(trace: Trace, kernel: str) -> "tuple[float, int]":
    """(total ns, calls) of `kernel`, with the time of each op that staged
    one of its operands into VMEM (the latest run of that instruction
    before the call) added once.  Where XLA copies a weight or a pool into
    VMEM ahead of the call, that copy does the call's HBM reads, so a
    roofline share of the call alone would count them in no time at all."""
    match = KERNELS[kernel]
    tot, n = 0.0, 0
    for evs in trace.ops.values():
        last: "dict[str, int]" = {}
        staged: "set[int]" = set()
        for i, ev in enumerate(evs):
            if match(ev.name):
                tot += ev.dur_ns
                n += 1
                staged.update(last[op] for op in _operands_in_vmem(ev.name)
                              if op in last)
            m = _INSTR.match(ev.name)
            if m:
                last[m.group(1)] = i
        tot += sum(evs[i].dur_ns for i in staged)
    return tot, n


def self_times(evs: "list[Event]") -> "list[tuple[Event, float]]":
    """Each operation with its own time: its duration less that of the
    operations nested in it (a loop's body runs inside the loop's op)."""
    order = sorted(range(len(evs)),
                   key=lambda i: (evs[i].start_ns, -evs[i].dur_ns))
    own = [ev.dur_ns for ev in evs]
    stack: "list[int]" = []
    for i in order:
        while stack and evs[stack[-1]].end_ns <= evs[i].start_ns:
            stack.pop()
        if stack:
            own[stack[-1]] -= evs[i].dur_ns
        stack.append(i)
    return [(evs[i], own[i]) for i in range(len(evs))]


def op_label(hlo: str) -> str:
    """A short, stable label for an op's HLO text: the kernel's name for a
    kernel, else the instruction's name without its number and its result
    type, e.g. `copy bf16[1025,16,16,64]`."""
    kernel = kernel_of(hlo)
    if kernel:
        return kernel
    m = re.match(r"%([A-Za-z_\-]+?)(?:\.\d+)? = (\S+?\[[^\]]*\])?", hlo)
    if not m:
        return hlo[:60]
    return f"{m.group(1)} {(m.group(2) or '').lstrip('(')}".strip()


def top_ops(trace: Trace, n: int = 10) -> "list[list]":
    """[label, seconds] of the labels whose operations took most of the
    device's own time, averaged over the device planes."""
    tot: "collections.Counter[str]" = collections.Counter()
    for evs in trace.ops.values():
        for ev, own in self_times(evs):
            tot[op_label(ev.name)] += own / len(trace.ops)
    return [[k, v / 1e9] for k, v in tot.most_common(n)]


def device_offset(trace: Trace, launches_ns: "list[float]") -> float:
    """The shift that puts device time on the host's clock: the least one
    under which no program starts before the host call that launched it.
    `launches_ns[j]` is the host time at which the j-th program run of the
    first device plane was launched (or any earlier time in the same host
    call)."""
    mods = next(iter(trace.modules.values()), [])
    pairs = list(zip(launches_ns, mods))
    if not pairs:
        return 0.0
    return max(h - ev.start_ns for h, ev in pairs)


def idle_gaps(trace: Trace, lo_ns: float, hi_ns: float, offset_ns: float
              ) -> "list[list]":
    """[annotation, seconds]: the device's idle time in [lo, hi) (host
    clock) split by the innermost harness annotation open at each moment,
    "none" where none was, most first.  Averaged over device planes."""
    out: "collections.Counter[str]" = collections.Counter()
    notes = sorted(trace.annotations, key=lambda e: (e.start_ns, -e.dur_ns))
    for evs in trace.ops.values():
        busy = union((ev.start_ns + offset_ns, ev.end_ns + offset_ns)
                     for ev in evs)
        gaps, t = [], lo_ns
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, hi_ns)))
            t = max(t, e)
            if t >= hi_ns:
                break
        if t < hi_ns:
            gaps.append((t, hi_ns))
        for gs, ge in gaps:
            _split_gap(gs, ge, notes, out, len(trace.ops))
    return [[k, v / 1e9] for k, v in out.most_common()]


def _split_gap(gs, ge, notes, out, planes) -> None:
    """Charge [gs, ge) to the innermost annotation open at each instant."""
    cuts = {gs, ge}
    for n in notes:
        if n.start_ns >= ge:
            break
        if n.end_ns > gs:
            cuts.update(t for t in (n.start_ns, n.end_ns) if gs < t < ge)
    cuts = sorted(cuts)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        inner = None
        for n in notes:
            if n.start_ns > mid:
                break
            if n.end_ns > mid and (inner is None or n.dur_ns < inner.dur_ns):
                inner = n
        out[inner.name if inner else "none"] += (b - a) / planes
