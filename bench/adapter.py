"""Everything the harness reads from inside the serving engine, in one
place, so that a rename in the program breaks these functions and nothing
else.  The engine has no public accessor for a request's progress or for
the plan of a step yet; both are read here from its scheduler.
"""
from __future__ import annotations


def progress(engine, rids) -> "dict[int, int]":
    """rid -> tokens produced so far, for each of `rids` the engine holds
    or has finished (a rid it does not know is left out)."""
    s = engine.scheduler
    held = {r.rid: len(r.produced) for r in s.running.values()}
    held.update((r.rid, len(r.produced)) for r in s.waiting)
    out = {}
    for rid in rids:
        if rid in held:
            out[rid] = held[rid]
        else:
            done = engine.result(rid)
            if done is not None:
                out[rid] = len(done)
    return out


def finished(engine, rid) -> "list[int] | None":
    """The tokens of a finished request, or None while it runs."""
    return engine.result(rid)


def record_plans(engine, sink: list) -> None:
    """Append to `sink`, for every step the engine plans from now on, what
    its calls compute: the prefill chunk (start position, rows, real
    tokens) and the context of each decode lane.  The plan is returned
    unchanged."""
    sched = engine.scheduler
    plan_fn = sched.schedule

    def schedule():
        plan = plan_fn()
        if plan is not None:
            pre = plan.prefill
            sink.append({
                "prefill": (None if pre is None else
                            (pre.start_pos, len(pre.tokens), pre.real_tokens)),
                "decode": [sched.request_at(l).decode_pos + 1
                           for l in plan.decode_lanes],
            })
        return plan

    sched.schedule = schedule
