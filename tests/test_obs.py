"""Observability subsystem: tracer, metrics registry, run log, facade.

Covers the PR 6 acceptance surface:
* histogram quantile interpolation + negative-max fix (deterministic;
  the hypothesis properties live in test_obs_properties.py)
* span nesting, thread tracks, flow pairing, Chrome trace schema
* ServingMetrics facade parity (snapshot keys unchanged, registry gauges
  read live state)
* simulate_async smoke: one schema-versioned JSONL record per step
"""
import json
import threading

import pytest

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_registry)
from repro.obs.runlog import (RUNLOG_SCHEMA_VERSION, STEP_REQUIRED_KEYS,
                              RunLogger, read_jsonl)
from repro.obs import tracing
from repro.obs.tracing import (PHASE_SPANS, SpanTracer, install_tracer,
                               phase_breakdown, span)


@pytest.fixture
def tracer():
    t = install_tracer(SpanTracer())
    yield t
    install_tracer(None)


# ----------------------------------------------------------------- histogram
class TestHistogram:
    def test_quantile_interpolates_within_bucket(self):
        h = Histogram((0, 10, 20))
        for v in (1, 2, 3, 4, 5, 6, 7, 8):  # all land in (0, 10]
            h.observe(v)
        # p50 target = 4th of 8 obs in bucket (0,10]: 0 + 4/8 * 10 = 5
        assert h.quantile(0.5) == pytest.approx(5.0)
        # the old implementation returned the raw upper bound (10.0)
        assert h.quantile(0.5) < 10.0

    def test_quantile_all_zeros(self):
        h = Histogram((0, 1, 2, 4))
        for _ in range(32):
            h.observe(0.0)
        assert h.quantile(0.5) == pytest.approx(0.0)
        assert h.quantile(0.99) == pytest.approx(0.0)

    def test_quantile_overflow_interpolates_to_max(self):
        h = Histogram((0, 1))
        h.observe(100.0)
        assert h.max == 100.0
        assert 1.0 <= h.quantile(0.5) <= 100.0
        assert h.quantile(1.0) == pytest.approx(100.0)

    def test_negative_max(self):
        h = Histogram((-10, -1, 0, 1))
        h.observe(-5.0)
        h.observe(-2.0)
        assert h.max == pytest.approx(-2.0)  # was 0.0 before the fix

    def test_empty_max_is_zero(self):
        assert Histogram((0, 1)).max == 0.0
        assert Histogram((0, 1)).quantile(0.5) == 0.0

    def test_merge(self):
        a, b = Histogram((0, 1, 2)), Histogram((0, 1, 2))
        for v in (0.5, 1.5):
            a.observe(v)
        for v in (2.5, 0.25):
            b.observe(v)
        a.merge(b)
        assert a.total == 4
        assert a.sum == pytest.approx(4.75)
        assert a.max == pytest.approx(2.5)

    def test_merge_bounds_mismatch(self):
        with pytest.raises(AssertionError):
            Histogram((0, 1)).merge(Histogram((0, 2)))

    def test_snapshot_keys(self):
        s = Histogram((0, 1), name="lat").snapshot()
        assert set(s) == {"lat_mean", "lat_p50", "lat_p99", "lat_max",
                          "lat_count"}


# ------------------------------------------------------------------ registry
class TestRegistry:
    def test_counter_gauge_get_or_create(self):
        r = MetricsRegistry()
        c = r.counter("reqs_total")
        c.inc()
        assert r.counter("reqs_total") is c
        r.gauge("depth").set(3)
        snap = r.snapshot()
        assert snap["reqs_total"] == 1.0
        assert snap["depth"] == 3.0

    def test_labels_make_distinct_children(self):
        r = MetricsRegistry()
        r.counter("hits", engine="a").inc(2)
        r.counter("hits", engine="b").inc(5)
        snap = r.snapshot()
        assert snap['hits{engine="a"}'] == 2.0
        assert snap['hits{engine="b"}'] == 5.0

    def test_kind_collision_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_callback_gauge_reads_live(self):
        r = MetricsRegistry()
        state = {"v": 1.0}
        r.gauge("live", fn=lambda: state["v"])
        assert r.snapshot()["live"] == 1.0
        state["v"] = 7.0
        assert r.snapshot()["live"] == 7.0

    def test_prometheus_text_cumulative_buckets(self):
        r = MetricsRegistry()
        h = r.histogram("lat", (1.0, 2.0))
        for v in (0.5, 1.5, 5.0):
            h.observe(v)
        text = r.prometheus_text()
        assert '# TYPE lat histogram' in text
        assert 'lat_bucket{le="1"} 1' in text
        assert 'lat_bucket{le="2"} 2' in text
        assert 'lat_bucket{le="+Inf"} 3' in text
        assert Counter and Gauge  # exported names


# -------------------------------------------------------------------- tracer
class TestTracer:
    def test_noop_when_uninstalled(self):
        install_tracer(None)
        s = span("anything", k=1)
        with s as sp:
            sp.set(more=2)  # must not raise

    def test_span_nesting_and_attrs(self, tracer):
        with span("outer", step=0):
            with span("inner") as sp:
                sp.set(tokens=42)
        evs = [e for e in tracer.events() if e["ph"] == "X"]
        names = [e["name"] for e in evs]
        # inner closes first (exit order)
        assert names == ["inner", "outer"]
        inner, outer = evs
        assert inner["args"]["tokens"] == 42
        assert outer["args"]["step"] == 0
        # nesting: inner's interval is contained in outer's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6

    def test_thread_tracks(self, tracer):
        def work():
            with span("worker_span"):
                pass
        t = threading.Thread(target=work, name="obs-test-worker")
        with span("main_span"):
            t.start()
            t.join()
        evs = tracer.events()
        tids = {e["name"]: e["tid"] for e in evs if e["ph"] == "X"}
        assert tids["main_span"] != tids["worker_span"]
        thread_names = {e["args"]["name"] for e in evs
                        if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "obs-test-worker" in thread_names

    def test_flow_pairing_and_unmatched_end_dropped(self, tracer):
        tracer.flow_end("publish", 99)  # no matching start -> dropped
        with span("publish_span"):
            tracer.flow_start("publish", 7)
        with span("resume_span"):
            tracer.flow_end("publish", 7)
        flows = [(e["ph"], e["id"]) for e in tracer.events()
                 if e["ph"] in ("s", "f")]
        assert flows == [("s", 7), ("f", 7)]

    def test_export_schema(self, tracer, tmp_path):
        with span("a"):
            pass
        tracer.instant("marker", note="x")
        tracer.counter("queue_depth", depth=3)
        path = tracer.export(str(tmp_path / "trace.json"))
        doc = json.loads(open(path).read())
        assert isinstance(doc["traceEvents"], list)
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "M", "s", "f", "i", "C")
            assert "pid" in ev and "tid" in ev and "name" in ev
            if ev["ph"] == "X":
                assert ev["dur"] > 0 and ev["ts"] >= 0
        from repro.obs.validate import validate_trace
        assert validate_trace(path, expect_spans=["a"]) == []

    def test_span_lands_on_the_profiler_host_plane(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        install_tracer(SpanTracer(), annotate_jax=True)
        try:
            jax.profiler.start_trace(str(tmp_path))
            with span("x"):
                with span("x_child"):
                    jax.numpy.ones(4).block_until_ready()
            jax.profiler.stop_trace()
        finally:
            install_tracer(None)
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        host = {e.name: e for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:") for ln in p.lines
                for e in ln.events}
        assert {"x", "x_child"} <= set(host)
        x, kid = host["x"], host["x_child"]
        assert x.start_ns <= kid.start_ns
        assert kid.start_ns + kid.duration_ns <= x.start_ns + x.duration_ns

    def test_no_tracer_returns_the_shared_noop_and_no_annotation(
            self, monkeypatch):
        import jax

        opened = []
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda name: opened.append(name))
        install_tracer(None)
        sp = span("x", k=1)
        assert sp is tracing._NOOP
        with sp:
            pass
        # a tracer without annotate_jax records the span, annotates nothing
        t = install_tracer(SpanTracer())
        try:
            with span("y"):
                pass
        finally:
            install_tracer(None)
        assert opened == []
        assert [e["name"] for e in t.events() if e["ph"] == "X"] == ["y"]

    def test_train_step_children_nest_in_train_update(self, tracer):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from repro.configs.base import RLConfig
        from repro.configs.registry import get_config
        from repro.training.trainer import TrainBatch, Trainer

        cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32")
        trainer = Trainer(cfg, RLConfig(group_size=2, num_minibatches=2),
                          "a3po")
        state = trainer.init_state(jax.random.PRNGKey(0))
        B, T = 4, 8
        mask = jnp.ones((B, T - 1), jnp.float32)
        batch = TrainBatch(tokens=jnp.full((B, T), 5, jnp.int32),
                           response_mask=mask, behav_logp=-mask,
                           versions=jnp.zeros((B,), jnp.int32),
                           rewards=jnp.arange(B, dtype=jnp.float32))
        trainer.step(state, batch)
        ev = {e["name"]: e for e in tracer.events() if e["ph"] == "X"}
        outer = ev["train_update"]
        kids = [ev[n] for n in ("train_dispatch", "train_fetch",
                                "train_metrics")]
        for k in kids:
            assert outer["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        # in order, each starting after the one before it ends
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-3

    def test_phase_breakdown_counts_only_leaf_spans(self, tracer):
        with span("train_step", step=0):     # wrapper: not a phase span
            with span("train_update"):
                pass
        with span("weight_publish"):
            pass
        phases = phase_breakdown(tracer.events())
        assert set(phases) == {"train", "publish"}
        assert phases["train"]["count"] == 1.0
        assert PHASE_SPANS["decode_horizon"] == "decode"


# ------------------------------------------------------------ serving facade
EXPECTED_SERVING_KEYS = {
    "staleness_mean", "staleness_p50", "staleness_p99", "staleness_max",
    "staleness_count",
    "queue_delay_s_mean", "queue_delay_s_p50", "queue_delay_s_p99",
    "queue_delay_s_max", "queue_delay_s_count",
    "page_util_mean", "page_util_p50", "page_util_p99", "page_util_max",
    "page_util_count",
    "ttft_s_mean", "ttft_s_p50", "ttft_s_p99", "ttft_s_max",
    "ttft_s_count",
    "prefix_hit_rate", "prefix_hit_tokens", "prefill_tokens_computed",
    "prefill_chunks", "prefill_time_s", "prefill_compiles",
    "prefill_tokens_per_s",
    "decode_tokens", "decode_host_syncs", "decode_launches",
    "decode_time_s", "host_syncs_per_token", "decode_tokens_per_s",
    "interrupts", "resumed_sequences", "preemptions",
    "preemptions_staleness", "preemptions_slo", "drops",
    "drops_staleness_budget", "drops_max_preempts", "drops_slo_shed",
    "admitted", "completed", "cow_forks", "oom_sheds", "nan_drops",
}


class TestServingFacade:
    def test_snapshot_keys_preserved(self):
        from repro.serving.metrics import ServingMetrics
        m = ServingMetrics(register=False)
        assert set(m.snapshot()) == EXPECTED_SERVING_KEYS

    def test_registry_gauges_read_live_fields(self):
        from repro.serving.metrics import ServingMetrics
        m = ServingMetrics()  # registers into the global registry
        m.interrupts += 3
        m.decode_tokens = 100
        m.decode_time_s = 2.0
        m.staleness.observe(4.0)
        snap = get_registry().snapshot()
        assert snap["serving_interrupts"] == 3.0
        assert snap["serving_decode_tokens_per_s"] == pytest.approx(50.0)
        assert snap["serving_staleness_count"] == 1.0

    def test_latest_instance_wins(self):
        from repro.serving.metrics import ServingMetrics
        a = ServingMetrics()
        a.drops += 5
        b = ServingMetrics()  # re-registers: registry now reads b
        assert get_registry().snapshot()["serving_drops"] == 0.0
        b.drops += 1
        assert get_registry().snapshot()["serving_drops"] == 1.0

    def test_mutable_dataclass_surface(self):
        from repro.serving.metrics import ServingMetrics
        m = ServingMetrics(register=False)
        m.observe_request(prompt_tokens=10, prefix_hit=4, queue_delay_s=0.01)
        m.observe_finished(staleness_values=[0, 1, 2])
        s = m.snapshot()
        assert s["admitted"] == 1.0
        assert s["completed"] == 1.0
        assert s["prefix_hit_rate"] == pytest.approx(0.4)
        assert s["staleness_count"] == 3.0


# ------------------------------------------------------------------- run log
class TestRunLog:
    def test_step_record_schema(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with RunLogger(path) as log:
            log.log_event("meta", arch="toy-2m")
            log.log_step({"step": 0, "reward": 0.5, "loss": 0.1,
                          "staleness_mean": 1.0, "rollout_time_s": 0.2,
                          "train_time_s": 0.3, "wall_time_s": 0.6,
                          "serving": {"drops": 0}})
        steps = read_jsonl(path)
        assert len(steps) == 1
        rec = steps[0]
        assert rec["schema"] == RUNLOG_SCHEMA_VERSION
        for k in STEP_REQUIRED_KEYS:
            assert k in rec, k
        assert rec["serving"] == {"drops": 0}
        metas = read_jsonl(path, kind="meta")
        assert metas[0]["arch"] == "toy-2m"

    def test_missing_required_key_asserts(self, tmp_path):
        log = RunLogger(str(tmp_path / "r.jsonl"))
        with pytest.raises(AssertionError):
            log.log_step({"step": 0})
        log.close()

    def test_quiet_suppresses_stdout(self, capsys):
        log = RunLogger(None, quiet=True)
        log.print("should not appear")
        assert capsys.readouterr().out == ""
        log2 = RunLogger(None)
        log2.print("visible")
        assert "visible" in capsys.readouterr().out


# ------------------------------------------------------- orchestrator smoke
class TestOrchestratorSmoke:
    def test_simulate_async_one_record_per_step(self, tmp_path):
        from repro.async_rl.orchestrator import simulate_async
        from repro.configs.base import RLConfig
        from repro.configs.registry import get_config
        from repro.data.tasks import ArithmeticTask

        cfg = get_config("toy-2m")
        rl = RLConfig(group_size=2)
        jsonl = str(tmp_path / "run.jsonl")
        trace = str(tmp_path / "trace.json")
        tracer = install_tracer(SpanTracer())
        try:
            with RunLogger(jsonl, quiet=True) as log:
                simulate_async(cfg, rl, ArithmeticTask(max_operand=9),
                               "a3po", num_steps=3, n_prompts=2,
                               max_new_tokens=4, staleness=1,
                               run_logger=log)
                assert log.steps_logged == 3
            tracer.export(trace)
        finally:
            install_tracer(None)

        steps = read_jsonl(jsonl)
        assert [r["step"] for r in steps] == [0, 1, 2]
        assert all(r["schema"] == RUNLOG_SCHEMA_VERSION for r in steps)

        from repro.obs.validate import validate_jsonl, validate_trace
        assert validate_jsonl(jsonl, min_steps=3) == []
        assert validate_trace(trace, expect_spans=[
            "rollout_generate", "train_update", "weight_publish"]) == []

        names = {e["name"] for e in json.load(open(trace))["traceEvents"]
                 if e["ph"] == "X"}
        assert {"rollout", "train_step"} <= names
        phases = phase_breakdown(json.load(open(trace))["traceEvents"])
        assert {"rollout", "train", "publish"} <= set(phases)
