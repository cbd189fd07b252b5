"""The trace reduction, on a trace recorded on a TPU v5e
(bench/tools/record_test_trace.py): two annotated steps, each one
gpp_matmul call and one paged_attention call, with a 2 ms host sleep in a
harness.poll annotation after each.  The expected numbers were worked out
by hand from the trace's events (integer nanoseconds)."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "two_steps.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(DATA)


def test_planes_and_annotations(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    assert len(trace.ops["/device:TPU:0"]) == 16
    assert [a.name for a in trace.annotations] == [
        "engine.step", "harness.poll", "engine.step", "harness.poll"]


def test_busy_is_the_union_of_ops(trace):
    # step 1: 6951+6+11+38+1066+1166+5587+2, step 2: 6821+6+12+37+1283+948
    # +5590+3 (no two ops overlap; two only touch)
    assert tr.busy_ns(trace) == 14827 + 14700


def test_kernels_and_programs(trace):
    assert tr.kernel_time(trace, "gpp_matmul") == (6951 + 6821, 2)
    assert tr.kernel_time(trace, "paged_attn") == (5587 + 5590, 2)
    assert tr.module_time(trace, "step") == (15107 + 14983, 2)
    assert tr.module_time(trace, "_decode") == (0.0, 0)


def test_staged_kernel_time_adds_the_copies_into_vmem(trace):
    # the paged call's q and both pools are reshaped into VMEM (S(1)) just
    # before it; gpp_matmul's x and w stay in HBM
    assert tr.staged_kernel_time(trace, "paged_attn") == (
        5587 + 38 + 1066 + 1166 + 5590 + 37 + 1283 + 948, 2)
    assert tr.staged_kernel_time(trace, "gpp_matmul") == (6951 + 6821, 2)


def test_top_ops_by_own_time(trace):
    top = dict(tr.top_ops(trace, 3))
    assert top == pytest.approx({
        "gpp_matmul": 13772e-9, "paged_attn": 11177e-9,
        "reshape bf16[17,16,256]": (1066 + 1166 + 1283 + 948) * 1e-9})


def test_nested_ops_count_their_own_time():
    outer = tr.Event("%while.1 = loop", 0, 100)
    inner = tr.Event("%dot.2 = f32[2]", 10, 30)
    after = tr.Event("%copy.3 = f32[2]", 100, 5)
    own = dict((e.name, t) for e, t in tr.self_times([outer, inner, after]))
    assert own == {"%while.1 = loop": 70, "%dot.2 = f32[2]": 30,
                   "%copy.3 = f32[2]": 5}


def test_idle_gaps_by_annotation(trace):
    starts = [a.start_ns for a in trace.annotations if a.name == "engine.step"]
    # each program starts no earlier than its step: the shift is the larger
    # of 40169714-39426323 and 43857293-42992077
    offset = tr.device_offset(trace, starts)
    assert offset == 865216
    lo, hi = 40169714, 47122262
    gaps = dict(tr.idle_gaps(trace, lo, hi, offset))
    assert gaps == pytest.approx({
        # before, inside (1-2 ns between ops) and after each step's ops
        "engine.step": (122094 + 10 + 1177338 + 272 + 9 + 969129) * 1e-9,
        "harness.poll": (2365550 + 2268190) * 1e-9,
        # between the annotations, and after the last
        "none": (3450 + 4310 + 4020 + 8649) * 1e-9}, abs=1e-12)
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo - 29527) * 1e-9, abs=1e-12)


# What each recorded trace gave before `gpp_matmul_grouped` had an entry of
# its own: (kernel_time, staged_kernel_time) of each kernel.  Neither trace
# holds a grouped call.
BEFORE = {
    "two_steps": {"gpp_matmul": ((13772.0, 2), (13772.0, 2)),
                  "paged_attn": ((11177.0, 2), (15715.0, 2)),
                  "gpp_matmul_grouped": ((0.0, 0), (0.0, 0))},
    "serve_spans": {k: ((0.0, 0), (0.0, 0)) for k in tr.KERNELS},
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_recorded_traces_read_as_before(name):
    t = tr.load(DATA.parent / f"{name}.xplane.pb")
    assert {k: (tr.kernel_time(t, k), tr.staged_kernel_time(t, k))
            for k in tr.KERNELS} == BEFORE[name]
