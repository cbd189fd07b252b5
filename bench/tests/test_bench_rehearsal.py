"""The harness end to end on the CPU: `--cpu-rehearsal` prints a result
line to the contract, a run without a TPU prints none and fails, and a
broken timed path makes `correct` false."""
import json

import numpy as np
import pytest

from bench import run

ARGS = ["--seed", "3000000019", "--seconds", "5", "--cpu-rehearsal"]


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]) if out else None


def test_rehearsal_prints_the_contract_line(capsys):
    assert run.main(["--workload", "qwen2-7b-14L.chat", "--trace", "0",
                     *ARGS]) == 0
    res = _last_line(capsys)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_rehearsal_traced_reads_no_device_metric(capsys):
    assert run.main(["--workload", "qwen1.5-0.5b.longctx", "--trace", "1",
                     *ARGS]) == 0
    res = _last_line(capsys)
    assert res["correct"] is True
    assert res["device"]["platform"] == "cpu"
    # a CPU run reports no device number, only the engine's count
    assert set(res["metrics"]) == {"decode_lanes"}
    assert res["device"]["busy_s"] == 0.0


def test_no_tpu_no_result(capsys):
    assert run.main(["--workload", "qwen2-7b-14L.chat", "--seed", "1",
                     "--seconds", "2", "--trace", "0"]) != 0
    assert _last_line(capsys) is None


def _altered_token(monkeypatch):
    import repro.serving.engine as eng
    right = eng.sample_token

    def wrong(serve, rid, idx, row):
        return (right(serve, rid, idx, row) + 1) % np.shape(row)[-1]

    monkeypatch.setattr(eng, "sample_token", wrong)


def _state_unchanged(monkeypatch):
    from repro.models import transformer as tf
    step = tf.decode_step_paged

    def stale(params, cfg, tokens, caches, *a):
        logits, _ = step(params, cfg, tokens, caches, *a)
        return logits, caches

    monkeypatch.setattr(tf, "decode_step_paged", stale)


def _half_batch(monkeypatch):
    from repro.models import transformer as tf
    step = tf.decode_step_paged

    def half(params, cfg, tokens, caches, *a):
        logits, caches = step(params, cfg, tokens, caches, *a)
        h = logits.shape[0] // 2      # the low lanes get the high lanes'
        return logits.at[:h].set(logits[h:2 * h]), caches

    monkeypatch.setattr(tf, "decode_step_paged", half)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = run.run(["--workload", "qwen2-7b-14L.chat", *ARGS])
    assert res["correct"] is False
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_the_control_is_not_correct():
    """The reference in fp8 (the precision below bf16) in the program's
    place comes out not correct, through the harness's own comparison,
    while the program's answers in the same window read under the limit."""
    res = run.run(["--workload", "qwen2-7b-14L.chat", *ARGS], control=True)
    gap = res["checks"]["logit_gap_max"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]
    assert res["harness"]["program_gap_max"] <= gap["limit"]
