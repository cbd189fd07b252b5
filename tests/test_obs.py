"""Telemetry subsystem (repro.obs): percentile/histogram math, Chrome
trace-event round-trips, ring-buffer drop accounting, the typed bandwidth
ledger (shared step schema + HBM-byte reconciliation + retention rollup),
and the serving-engine integration contract — obs ON never changes the
token stream, obs OFF records nothing."""
import json
import math

import numpy as np
import pytest

import jax

from repro.models import registry
from repro.models import transformer as tf
from repro.obs import make_telemetry
from repro.obs.ledger import STEP_SCHEMA, BandwidthLedger, step_row
from repro.obs.metrics import (Histogram, MetricsRegistry, RequestTracker,
                               percentile)
from repro.obs.trace import (NULL_TRACE, PID_REQUESTS, PID_SERVING,
                             TraceRecorder, phase)
from repro.serving import DenseServingEngine, ServeConfig, ServingEngine

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------- metrics
class TestPercentile:
    def test_matches_numpy_linear_interpolation(self):
        rng = np.random.default_rng(7)
        xs = rng.normal(size=101).tolist()
        for q in (0, 1, 25, 50, 73.5, 90, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), abs=1e-12)

    def test_edges(self):
        assert math.isnan(percentile([], 50))
        assert percentile([4.0], 99) == 4.0
        with pytest.raises(ValueError):
            percentile([1.0, 2.0], 101)


class TestHistogram:
    def test_exact_aggregates_survive_decimation(self):
        h = Histogram(max_samples=64)
        xs = list(range(1000))
        for x in xs:
            h.observe(float(x))
        s = h.summary()
        assert s["count"] == 1000
        assert s["min"] == 0.0 and s["max"] == 999.0
        assert s["mean"] == pytest.approx(np.mean(xs))
        # retained samples were decimated, never grown past the cap
        assert 0 < s["retained_samples"] <= 64
        # quantiles of the decimated reservoir still track the stream
        assert h.quantile(50) == pytest.approx(float(np.percentile(xs, 50)),
                                               rel=0.1)

    def test_quantile_exact_below_cap(self):
        h = Histogram(max_samples=64)
        for x in (5.0, 1.0, 9.0, 3.0):
            h.observe(x)
        assert h.quantile(50) == pytest.approx(
            float(np.percentile([5, 1, 9, 3], 50)))


class TestRequestTracker:
    def test_ttft_and_tpot_math(self):
        t = {"now": 0.0}
        rt = RequestTracker(MetricsRegistry(), clock=lambda: t["now"])
        rt.on_submit(0)
        t["now"] = 0.5
        rt.on_first_token(0)
        t["now"] = 0.7                # duplicate first-token (preemption
        rt.on_first_token(0)          # resume) must NOT move TTFT
        t["now"] = 2.5
        rt.on_finish(0, tokens=5)
        s = rt.summary()
        assert s["ttft"]["count"] == 1
        assert s["ttft"]["p50"] == pytest.approx(0.5)
        # (finish - first) / (tokens - 1) = (2.5 - 0.5) / 4
        assert s["tpot"]["p50"] == pytest.approx(0.5)

    def test_single_token_request_has_no_tpot(self):
        rt = RequestTracker(MetricsRegistry(), clock=lambda: 1.0)
        rt.on_submit(0)
        rt.on_first_token(0)
        rt.on_finish(0, tokens=1)
        assert rt.summary()["tpot"]["count"] == 0


# ------------------------------------------------------------------ trace
class TestTraceRecorder:
    def _fake_clock(self):
        t = {"now": 0.0}

        def clock():
            t["now"] += 0.001
            return t["now"]

        return clock

    def test_chrome_json_round_trip(self, tmp_path):
        tr = TraceRecorder(capacity=128, clock=self._fake_clock())
        tr.name_process(PID_SERVING, "serving")
        tr.complete("step", tr.now_us(), 500.0, pid=PID_SERVING, tid=0,
                    cat="step", args={"tokens": 3})
        tr.instant("admit", pid=PID_SERVING, tid=10, cat="sched")
        tr.counter("hbm", {"total": 123.0}, pid=PID_SERVING)
        tr.async_begin("req 0", 0, pid=PID_REQUESTS)
        tr.async_end("req 0", 0, pid=PID_REQUESTS)
        path = tmp_path / "trace.json"
        tr.write(str(path))
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        assert {e["ph"] for e in evs} == {"M", "X", "i", "C", "b", "e"}
        for e in evs:
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            if e["ph"] != "M":
                assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        x = next(e for e in evs if e["ph"] == "X")
        assert x["dur"] == 500.0 and x["args"]["tokens"] == 3
        b = next(e for e in evs if e["ph"] == "b")
        e_ = next(e for e in evs if e["ph"] == "e")
        # async spans pair on (cat, id, name)
        assert (b["id"], b["name"]) == (e_["id"], e_["name"])
        assert doc["otherData"]["dropped_events"] == 0

    def test_ring_drops_oldest_and_counts(self):
        tr = TraceRecorder(capacity=4, clock=self._fake_clock())
        tr.name_process(1, "p")       # metadata is exempt from the ring
        for i in range(10):
            tr.instant(f"e{i}", pid=1)
        assert len(tr) == 4
        assert tr.dropped == 6
        doc = tr.to_chrome()
        assert doc["otherData"]["dropped_events"] == 6
        names = [e["name"] for e in doc["traceEvents"]]
        assert "process_name" in names           # meta survived
        assert names[-4:] == ["e6", "e7", "e8", "e9"]

    def test_span_contextmanager(self):
        tr = TraceRecorder(clock=self._fake_clock())
        with tr.span("work", pid=1, tid=2, args={"k": 1}):
            pass
        (ev,) = tr.events
        assert ev["ph"] == "X" and ev["dur"] > 0 and ev["args"]["k"] == 1

    def test_phase_records_only_with_telemetry(self):
        """`phase` is a profiler annotation either way; the recorder gets
        the same span as an X event only when telemetry is on."""
        with phase(NULL_TRACE, "serve.schedule", lanes=2):
            pass
        assert len(NULL_TRACE) == 0
        tr = TraceRecorder(clock=self._fake_clock())
        with phase(tr, "serve.decode.launch", tid=11, lanes=2):
            pass
        (ev,) = tr.events
        assert (ev["name"], ev["ph"], ev["cat"]) == (
            "serve.decode.launch", "X", "phase")
        assert (ev["pid"], ev["tid"]) == (PID_SERVING, 11)
        assert ev["args"] == {"lanes": 2} and ev["dur"] > 0

    def test_null_trace_is_inert(self):
        assert not NULL_TRACE.enabled
        assert len(NULL_TRACE) == 0
        NULL_TRACE.instant("x")       # all emitters are no-ops
        NULL_TRACE.complete("x", 0.0, 1.0)
        assert len(NULL_TRACE) == 0
        with pytest.raises(RuntimeError):
            NULL_TRACE.write("/dev/null")


# ----------------------------------------------------------------- ledger
class TestLedger:
    def test_step_row_zero_fill_and_derived(self):
        row = step_row(tokens=4, param_bytes=100, kv_write_bytes=40,
                       kv_read_bytes=60, drafted_tokens=8,
                       accepted_tokens=6)
        assert set(row) == set(STEP_SCHEMA)
        assert row["hbm_bytes"] == 200          # params + writes + reads
        assert row["acceptance_rate"] == pytest.approx(0.75)
        # param_bytes counts every program's weight stream, so the weight
        # streams accepted drafts saved are the engine's to pass
        assert row["spec_saved_bytes"] == 0
        assert row["prefill_tokens"] == 0       # unset fields zero-fill
        assert row["d2h_bytes"] == 0
        with pytest.raises(ValueError):
            step_row(not_a_field=1)

    def test_reconciles_with_seed_byte_formula(self):
        """Regression: the ledger's derived hbm_bytes must equal the seed
        engines' hand-built accounting, `param_bytes + tokens *
        kv_token_bytes + read_tokens * kv_token_bytes`, exactly."""
        param_bytes, kv_token_bytes = 1_000_000, 2_048
        for tokens, read_tokens in ((1, 7), (5, 123), (32, 0)):
            row = step_row(tokens=tokens, param_bytes=param_bytes,
                           kv_write_bytes=tokens * kv_token_bytes,
                           kv_read_bytes=read_tokens * kv_token_bytes)
            seed = (param_bytes + tokens * kv_token_bytes
                    + read_tokens * kv_token_bytes)
            assert row["hbm_bytes"] == seed

    def test_retention_rollup_keeps_lifetime_totals(self):
        led = BandwidthLedger(retention=4)
        for i in range(10):
            led.record(tokens=i, param_bytes=100)
        assert len(led) == 4                    # ring held at retention
        assert led.steps == 10
        assert led.rolled_up_steps == 6
        assert [r["step"] for r in led] == [6, 7, 8, 9]
        assert led.total("tokens") == sum(range(10))
        assert led.total("hbm_bytes") == 10 * 100
        s = led.summary()
        assert s["total_tokens"] == 45 and s["rolled_up_steps"] == 6
        # list compatibility the engines' callers rely on
        assert led[0]["step"] == 6 and len(led[-2:]) == 2

    def test_unbounded_by_default(self):
        led = BandwidthLedger()
        for _ in range(100):
            led.record(tokens=1)
        assert len(led) == 100 and led.rolled_up_steps == 0

    def test_utilization_report_shape(self):
        led = BandwidthLedger()
        rng = np.random.default_rng(0)
        for _ in range(8):
            led.record(tokens=2, param_bytes=1000,
                       kv_write_bytes=int(rng.integers(50, 80)),
                       kv_read_bytes=int(rng.integers(100, 300)))
        rep = led.utilization_report()
        assert 0 < rep["measured_bw_utilization"] <= 1
        assert 0 < rep["predicted_bw_utilization"] <= 1
        assert rep["steps_measured"] == 8


# ----------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def qwen():
    cfg = registry.get_config("qwen1.5-0.5b", smoke=True)
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, n=3):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab_size, size=l).tolist()
            for l in (5, 11, 3)[:n]]


def _run(engine_cls, cfg, params, obs, **kw):
    eng = engine_cls(cfg, params,
                     ServeConfig(slots=2, max_len=64, obs=obs, **kw))
    rids = [eng.submit(p, max_new_tokens=6) for p in _prompts(cfg)]
    res = eng.run()
    return [res[r] for r in rids], eng


class TestEngineIntegration:
    @pytest.mark.parametrize("engine_cls", (ServingEngine,
                                            DenseServingEngine))
    def test_obs_never_changes_tokens(self, qwen, engine_cls):
        cfg, params = qwen
        off, eng_off = _run(engine_cls, cfg, params, obs=False)
        on, eng_on = _run(engine_cls, cfg, params, obs=True)
        assert on == off
        # disabled path recorded nothing and spent no wall-clock calls
        assert len(eng_off.obs.trace) == 0
        assert all(m["step_wall_us"] == 0 for m in eng_off.metrics)
        assert len(eng_on.obs.trace) > 0
        assert all(m["step_wall_us"] > 0 for m in eng_on.metrics)

    def test_engines_share_one_step_schema(self, qwen):
        """The satellite contract: dense rows are no longer hand-synced
        parity zeros — both engines emit exactly STEP_SCHEMA."""
        cfg, params = qwen
        _, paged = _run(ServingEngine, cfg, params, obs=False)
        _, dense = _run(DenseServingEngine, cfg, params, obs=False)
        for eng in (paged, dense):
            assert eng.metrics, "engine recorded no steps"
            for row in eng.metrics:
                assert set(row) == set(STEP_SCHEMA)
        # dense byte columns are real measurements now, not parity zeros
        assert dense.metrics.total("param_bytes") > 0
        assert dense.metrics.total("kv_read_bytes") > 0

    @pytest.mark.parametrize("engine_cls", (ServingEngine,
                                            DenseServingEngine))
    def test_ledger_rows_reconcile(self, qwen, engine_cls):
        cfg, params = qwen
        _, eng = _run(engine_cls, cfg, params, obs=False)
        for m in eng.metrics:
            assert m["hbm_bytes"] == (m["param_bytes"] + m["kv_write_bytes"]
                                      + m["kv_read_bytes"])

    def test_trace_covers_requests_steps_and_kernel_lanes(self, qwen):
        """Requests, steps and the engine's phases are recorded; the
        modelled kernel lanes are gone (no track but serving and
        requests)."""
        cfg, params = qwen
        streams, eng = _run(ServingEngine, cfg, params, obs=True)
        evs = eng.obs.trace.events
        steps = [e for e in evs if e["ph"] == "X" and e["name"] == "step"]
        assert len(steps) == len(eng.metrics)
        assert all(e["pid"] == PID_SERVING for e in steps)
        begins = [e for e in evs if e["ph"] == "b" and e["pid"] == PID_REQUESTS]
        ends = [e for e in evs if e["ph"] == "e" and e["pid"] == PID_REQUESTS]
        assert len(begins) == len(streams) and len(ends) == len(streams)
        phases = {e["name"] for e in evs if e.get("cat") == "phase"}
        assert phases == {"serve.schedule", "serve.prefill.launch",
                          "serve.prefill.dispatch", "serve.prefill.fetch",
                          "serve.decode.launch", "serve.decode.dispatch",
                          "serve.decode.fetch", "serve.sample",
                          "serve.account"}
        launch = next(e for e in evs if e["name"] == "serve.prefill.launch")
        assert set(launch["args"]) == {"rid", "rows"}
        assert {e["pid"] for e in evs} == {PID_SERVING, PID_REQUESTS}
        assert not any(e.get("cat") == "modeled" for e in evs)
        # trace JSON is loadable end-to-end
        doc = json.loads(json.dumps(eng.obs.trace.to_chrome()))
        assert doc["traceEvents"]
        ttft = eng.obs.requests.summary()["ttft"]
        assert ttft["count"] == len(streams)
        assert math.isfinite(ttft["p50"]) and ttft["p50"] > 0

    def test_metrics_retention_knob_reaches_engine(self, qwen):
        cfg, params = qwen
        _, full = _run(ServingEngine, cfg, params, obs=False)
        _, eng = _run(ServingEngine, cfg, params, obs=False,
                      metrics_retention=2)
        assert len(eng.metrics) == 2
        assert eng.metrics.steps > 2                   # rollup happened
        # totals stay lifetime-exact: identical workload, identical sums
        assert eng.metrics.totals() == full.metrics.totals()

    def test_telemetry_factory(self):
        t_on = make_telemetry(True, trace_capacity=8)
        t_off = make_telemetry(False)
        assert t_on.enabled and t_on.trace.enabled
        assert not t_off.enabled and t_off.trace is NULL_TRACE
        with pytest.raises(RuntimeError):
            t_off.write_metrics("/dev/null")


# ------------------------------------------------ ledger counters, per step
def _programs(row) -> int:
    """Model programs a paged-engine step launched, from its row."""
    return sum(bool(row[k]) for k in ("prefill_tokens", "decode_tokens",
                                      "verify_tokens"))


class TestStepCounters:
    def test_param_bytes_count_each_program(self, qwen):
        """A step that runs a prefill chunk and a decode call streams the
        weights twice, and the ledger charges them twice."""
        cfg, params = qwen
        _, eng = _run(ServingEngine, cfg, params, obs=False)
        rows = list(eng.metrics)
        assert any(_programs(r) == 2 for r in rows)
        for r in rows:
            assert r["param_bytes"] == _programs(r) * eng._param_bytes

    def test_spec_saved_bytes_one_stream_per_accepted_token(self, qwen):
        cfg, params = qwen
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=96, speculation=True, draft_len=3))
        for p in ([7, 9] * 8, [3, 4, 5] * 5):
            eng.submit(p, max_new_tokens=12)
        eng.run()
        rows = list(eng.metrics)
        assert sum(r["accepted_tokens"] for r in rows) > 0
        for r in rows:
            assert r["param_bytes"] == _programs(r) * eng._param_bytes
            assert r["spec_saved_bytes"] == (r["accepted_tokens"]
                                             * eng._param_bytes)

    def test_dense_param_bytes_count_each_program(self, qwen):
        cfg, params = qwen
        _, eng = _run(DenseServingEngine, cfg, params, obs=False)
        counts = [r["param_bytes"] // eng._param_bytes for r in eng.metrics]
        assert all(r["param_bytes"] == n * eng._param_bytes
                   for r, n in zip(eng.metrics, counts))
        # step 0 prefills both lanes and decodes them: at least 3 programs
        assert counts[0] >= 3 and min(counts) >= 1

    @pytest.mark.parametrize("engine_cls", (ServingEngine,
                                            DenseServingEngine))
    def test_d2h_bytes_count_the_logits_read_back(self, qwen, engine_cls):
        """Greedy steps read back float32 logits: one row per request's
        first token, plus every lane of each decode call (paged), or one
        row per sampled token (dense)."""
        cfg, params = qwen
        streams, eng = _run(engine_cls, cfg, params, obs=False)
        row_bytes = cfg.vocab_size * 4
        rows = list(eng.metrics)
        if engine_cls is ServingEngine:
            decode_calls = sum(bool(r["decode_tokens"]) for r in rows)
            want = (len(streams) + 2 * decode_calls) * row_bytes
        else:
            want = sum(len(s) for s in streams) * row_bytes
        assert sum(r["d2h_bytes"] for r in rows) == want
        assert all(r["d2h_bytes"] % row_bytes == 0 for r in rows)

    def test_temperature_counts_the_drawn_ids(self, qwen):
        cfg, params = qwen
        _, greedy = _run(ServingEngine, cfg, params, obs=False)
        streams, hot = _run(ServingEngine, cfg, params, obs=False,
                            temperature=0.8)
        drawn = sum(len(s) for s in streams)
        assert hot.metrics.total("d2h_bytes") == (
            greedy.metrics.total("d2h_bytes") + 4 * drawn)


    def test_live_blocks_match_blocks_for(self):
        """The vectorized live-block count behind `attn_bytes_stream` equals
        the loop over `blocks_for` it replaced, through window releases,
        prefix shares and finished lanes."""
        cfg = registry.get_config("gemma3-12b", smoke=True)
        eng = ServingEngine(cfg, tf.init_params(cfg, jax.random.PRNGKey(0)),
                            ServeConfig(slots=2, max_len=96, block_size=8,
                                        prefill_chunk=16, prefix_cache=True))
        system = list(range(1, 30))
        for tail, n in (([40, 41], 5), ([50], 6), ([40, 41, 7], 4)):
            eng.submit(system + tail, max_new_tokens=n)
        while eng.pending:
            eng.step()
            want = [[len(g.blocks_for(lane)) for g in eng.kv.groups]
                    for lane in range(2)]
            assert eng._live_blocks().tolist() == want
        assert eng.metrics.total("prefix_hit_tokens") > 0


# ------------------------------------------- spans on the profiler's clock
STEP_ORDER = ("serve.schedule", "serve.copy", "serve.prefill.launch",
              "serve.prefill.dispatch", "serve.prefill.fetch",
              "serve.sample", "serve.decode.launch", "serve.decode.dispatch",
              "serve.decode.fetch", "serve.sample", "serve.verify.launch",
              "serve.verify.dispatch", "serve.verify.fetch", "serve.sample",
              "serve.account")


def _serve_spans(logdir) -> list:
    """(name, start_ns, end_ns, stats) of every `serve.*` host event in the
    profile written under `logdir`, by start."""
    (path,) = logdir.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


class TestProfilerSpans:
    def test_steps_and_stages_on_the_profiler_clock(self, qwen, tmp_path):
        cfg, params = qwen
        off, eng_off = _run(ServingEngine, cfg, params, obs=False)
        with jax.profiler.trace(str(tmp_path)):
            on, eng_on = _run(ServingEngine, cfg, params, obs=False)
        # the profiler changes neither the tokens nor the ledger
        assert on == off
        assert list(eng_on.metrics) == list(eng_off.metrics)

        spans = _serve_spans(tmp_path)
        steps = [e for e in spans if e[0] == "serve.step"]
        rows = list(eng_on.metrics)
        assert [e[3]["step_num"] for e in steps] == [r["step"] for r in rows]
        children = [e for e in spans if e[0] != "serve.step"]
        for (_, s0, s1, _), row in zip(steps, rows):
            inner = [e for e in children if s0 <= e[1] and e[2] <= s1]
            names = [e[0] for e in inner]
            # one after another, in the order the step runs them; a
            # dispatch lies inside its launch
            for a, b in zip(inner, inner[1:]):
                nested = (b[0].endswith(".dispatch")
                          and a[0] == b[0].replace(".dispatch", ".launch"))
                assert (b[2] <= a[2]) if nested else (a[2] <= b[1])
            at = 0
            for n in names:             # raises when a stage is out of order
                at = STEP_ORDER.index(n, at) + 1
            assert names[0] == "serve.schedule"
            assert names[-1] == "serve.account"
            assert ("serve.prefill.launch" in names) == bool(
                row["prefill_tokens"])
            assert ("serve.decode.launch" in names) == bool(
                row["decode_tokens"])
            assert names.count("serve.decode.fetch") == bool(
                row["decode_tokens"])
            if row["decode_tokens"]:
                (launch,) = [e for e in inner
                             if e[0] == "serve.decode.launch"]
                assert launch[3]["lanes"] == row["decode_tokens"]
        # every stage span lies inside a step
        assert sum(len([e for e in children if s0 <= e[1] and e[2] <= s1])
                   for _, s0, s1, _ in steps) == len(children)
