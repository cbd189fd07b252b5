"""The `gpp_pad_mb` reader: the weight bytes the step programs copied into
padded kernel operands, from the engine's ledger rows."""
from types import SimpleNamespace

import pytest

from bench import model, run
from bench import trace_reduce as tr


def _read(rows, on_chip=True):
    read = model.load_module(run.BENCH / "metrics" / "gpp_pad_mb.py").read
    ops = [tr.Event("%gpp_matmul", 0, 100)]
    return read(SimpleNamespace(
        rows=rows, peaks=object() if on_chip else None,
        trace=tr.Trace({"/device:TPU:0": ops}, {}, [])))


def test_gpp_pad_mb_is_the_mean_per_step():
    # a step with a prefill chunk and a decode call, one with a decode
    # call, one with neither
    rows = [{"gpp_pad_bytes": 14_680_064_000},
            {"gpp_pad_bytes": 7_340_032_000}, {"gpp_pad_bytes": 0}]
    assert _read(rows) == pytest.approx(22_020_096_000 / 3 / 1e6)
    # a CPU rehearsal reads nothing
    assert _read(rows, on_chip=False) is None


def test_gpp_pad_mb_reads_nothing_without_the_counter():
    # an engine whose ledger has no such counter (the parent's) reports
    # nothing, and so does a window without steps
    assert _read([{"decode_tokens": 4}, {"decode_tokens": 4}]) is None
    assert _read([{"gpp_pad_bytes": 0}, {"decode_tokens": 4}]) is None
    assert _read([]) is None


def test_gpp_pad_mb_reads_zero_when_no_weight_is_padded():
    assert _read([{"gpp_pad_bytes": 0}, {"gpp_pad_bytes": 0}]) == 0.0
