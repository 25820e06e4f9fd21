"""Unified telemetry plane tests (ISSUE 13).

The acceptance bar: a routed request over a real 2-shard TCP fleet
produces ONE connected trace (router -> both shards' frontends ->
batcher dispatch) with exact parent/child nesting, exportable as Chrome
trace-event JSON; the frontend's ``{"op": "metrics"}`` serves a live
registry snapshot whose counters reconcile with the exit metrics.json;
the flight recorder's ring is bounded, dumps atomically on SIGTERM and
on swap/rollback transitions, and ``check_conservation()`` passes on a
fully-served batcher run and fails on an injected drop. The interleave
schedule family drives concurrent span emission + swap events + dumps:
no deadlocks, no torn dumps, sequence numbers strictly increasing.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from photon_ml_tpu.game.data import build_game_dataset
from photon_ml_tpu.obs import ObsSession
from photon_ml_tpu.obs.flight_recorder import (
    FlightRecorder,
    flight_recorder,
    reset_flight_recorder,
)
from photon_ml_tpu.obs.registry import (
    MetricsRegistry,
    SnapshotWriter,
)
from photon_ml_tpu.obs.trace import (
    PARENT_KEY,
    TRACE_KEY,
    NULL_SPAN,
    Tracer,
    chrome_trace_events,
    expand_spans,
    export_chrome_trace,
    start_span,
    tracer,
    tracing_enabled,
    tracing_scope,
    wire_context,
)
from photon_ml_tpu.serving import (
    MicroBatcher,
    ServingFrontend,
    ServingMetrics,
    ServingModel,
    ServingPrograms,
    requests_from_dataset,
)
from photon_ml_tpu.testing.interleave import InterleaveScheduler, explore
from tests.test_serving import (
    SHARDS,
    batch_reference_scores,
    make_bank,
    synth_model,
    synth_records,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- trace core ---------------------------------------------------------------


class TestTraceCore:
    def test_ring_is_bounded_and_drops_are_counted(self):
        t = Tracer(max_spans=8)
        for i in range(20):
            t.start(f"s{i}").end()
        assert len(t) == 8
        assert t.dropped == 12
        names = [s.name for s in t.snapshot()]
        assert names == [f"s{i}" for i in range(12, 20)]  # oldest evicted

    def test_disabled_tracing_is_free_and_silent(self):
        """The switch is the request path's: off, ``start_span`` hands out
        the one no-op span and ``record_span`` files nothing. The training
        side's ``span()`` has no switch: it files, parents to the span
        open around it, and ``current_span()`` is it."""
        from photon_ml_tpu.obs.trace import current_span, record_span, span

        assert not tracing_enabled()
        t0 = len(tracer())
        s = start_span("noop")
        assert s is NULL_SPAN
        s.end()
        record_span("noop.dispatch", 0.0, 1.0)
        assert len(tracer()) == t0
        with span("outer") as outer:
            assert current_span().span_id == outer.span_id
            with span("inner") as inner:
                assert current_span().span_id == inner.span_id
        assert current_span() is None
        filed = {s.name: s for s in tracer().snapshot()[t0:]}
        assert filed["inner"].parent_id == filed["outer"].span_id
        assert filed["inner"].trace_id == filed["outer"].trace_id
        assert filed["outer"].parent_id is None

    def test_span_nesting_ids_and_wire_context(self):
        t = Tracer()
        root = t.start("root")
        child = t.start(
            "child", trace_id=root.trace_id, parent_id=root.span_id
        )
        child.end()
        root.end()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert child.span_id != root.span_id
        obj = {TRACE_KEY: root.trace_id, PARENT_KEY: root.span_id}
        assert wire_context(obj) == (root.trace_id, root.span_id)
        assert wire_context({}) == (None, None)

    def test_chrome_export_is_atomic_valid_and_complete(self, tmp_path):
        t = Tracer()
        root = t.start("router.request", attrs={"uid": "r1"})
        t.start(
            "frontend.request",
            trace_id=root.trace_id,
            parent_id=root.span_id,
        ).end()
        root.end()
        path = str(tmp_path / "trace.json")
        n = export_chrome_trace(path, t.snapshot())
        assert n == 2
        data = json.load(open(path))
        evs = data["traceEvents"]
        assert all(e["ph"] == "X" for e in evs)
        assert all(e["dur"] > 0 for e in evs)
        by_name = {e["name"]: e for e in evs}
        assert (
            by_name["frontend.request"]["args"]["parent_span"]
            == by_name["router.request"]["args"]["span_id"]
        )
        assert (
            by_name["frontend.request"]["args"]["trace_id"]
            == by_name["router.request"]["args"]["trace_id"]
        )
        # an unfinished span never exports (no torn events)
        open_span = t.start("open")
        assert len(chrome_trace_events(t.snapshot())) == 2
        open_span.end()


# -- trace propagation over a real 2-shard TCP fleet -------------------------


class TestFleetTracePropagation:
    def test_one_connected_trace_per_routed_request(self, rng):
        """frontend-minted ids, carried on the wire, propagated by the
        router into every sub-request and by the shard's batcher into
        dispatch spans: every routed request yields ONE trace whose
        parent/child nesting is exactly router.request ->
        router.subrequest -> frontend.request -> serving.score."""
        from tests.test_shard_routing import (
            build_fleet,
            build_router,
            close_fleet,
        )

        recs = synth_records(rng, n=24)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        lm = synth_model(rng)
        with tracing_scope(True):
            tracer().clear()
            servers = build_fleet(lm, ds, 2)
            router = None
            try:
                router = build_router(servers, lm, cache_entries=0)
                for rec in recs[:10]:
                    out = router.score_record(rec)
                    assert not out.degraded
            finally:
                close_fleet(servers, router)
            # expand batch-level dispatch spans into their per-request
            # serving.score leaves (the hot path records one span per
            # dispatch; the leaves materialize at export)
            spans = expand_spans(tracer().snapshot())
        by_trace = {}
        for s in spans:
            by_trace.setdefault(s.trace_id, []).append(s)
        roots = [s for s in spans if s.name == "router.request"]
        assert len(roots) == 10
        uids = {s.attrs["uid"] for s in roots}
        assert uids == {r["uid"] for r in recs[:10]}
        for root in roots:
            family = by_trace[root.trace_id]
            names = sorted(s.name for s in family)
            by_id = {s.span_id: s for s in family}
            # exactly one root, and it is parentless
            assert [s for s in family if s.parent_id is None] == [root]
            subs = [s for s in family if s.name == "router.subrequest"]
            fronts = [s for s in family if s.name == "frontend.request"]
            scores = [s for s in family if s.name == "serving.score"]
            assert subs and fronts and scores, names
            # nesting exact: sub -> root, front -> sub, score -> front
            for s in subs:
                assert s.parent_id == root.span_id
            for f in fronts:
                assert by_id[f.parent_id].name == "router.subrequest"
            for sc in scores:
                assert by_id[sc.parent_id].name == "frontend.request"
                assert sc.attrs["dispatch_span"]
            assert len(fronts) == len(subs)
            assert len(scores) == len(fronts)
            # every span in the family is reachable from the root
            for s in family:
                hop, seen = s, 0
                while hop.parent_id is not None and seen < 10:
                    hop = by_id[hop.parent_id]
                    seen += 1
                assert hop is root

    def test_every_dispatch_has_a_span(self, rng):
        """Trace completeness: dispatches counted by ServingMetrics ==
        serving.dispatch spans recorded by the batcher."""
        recs = synth_records(rng, n=16)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        bank = make_bank(synth_model(rng), ds)
        programs = ServingPrograms((1, 8))
        programs.ensure_compiled(bank)
        metrics = ServingMetrics()
        with tracing_scope(True):
            tracer().clear()
            with MicroBatcher(lambda: bank, programs, metrics) as mb:
                for r in requests_from_dataset(ds, bank):
                    mb.score(r)
            dispatch_spans = [
                s for s in tracer().snapshot()
                if s.name == "serving.dispatch"
            ]
        assert len(dispatch_spans) == metrics.snapshot()["dispatches"]
        assert all(
            s.attrs["generation"] == 1 and s.attrs["shape"] in (1, 8)
            for s in dispatch_spans
        )


# -- metrics registry ---------------------------------------------------------


class TestMetricsRegistry:
    def test_counters_gauges_histograms(self):
        r = MetricsRegistry()
        c = r.counter("reqs")
        c.inc()
        c.inc(2, shard="1")
        assert c.value() == 1
        assert c.value(shard="1") == 2
        assert c.total() == 3
        g = r.gauge("depth")
        g.set(4)
        g.set(7)
        assert g.value() == 7
        h = r.histogram("lat", bounds=(0.01, 0.1))
        h.observe(0.005)
        h.observe(0.05)
        h.observe(5.0)
        assert h.count() == 3
        snap = r.snapshot()["metrics"]
        assert snap["reqs"]["kind"] == "counter"
        assert snap["lat"]["values"][""]["buckets"] == [1, 1, 1]

    def test_same_name_same_instrument_kind_clash_raises(self):
        r = MetricsRegistry()
        assert r.counter("x") is r.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("x")

    def test_label_cardinality_is_capped(self):
        r = MetricsRegistry(max_label_sets=4)
        c = r.counter("leaky")
        for i in range(50):
            c.inc(uid=f"u{i}")  # a uid smuggled into a label
        series = c.series()
        assert len(series) <= 5  # 4 real + the overflow slot
        assert series[("__overflow__",)] == 46
        assert c.total() == 50  # nothing lost, resolution degraded

    def test_views_merge_and_failing_view_is_isolated(self):
        r = MetricsRegistry()
        r.register_view("ok_view", lambda: {"a": 1})

        def bad():
            raise RuntimeError("wedged subsystem")

        r.register_view("bad_view", bad)
        snap = r.snapshot()
        assert snap["ok_view"] == {"a": 1}
        assert snap["bad_view"] == {"error": "wedged subsystem"}

    def test_prometheus_text_exposition(self):
        r = MetricsRegistry()
        r.counter("reqs").inc(3, shard="0")
        r.histogram("lat", bounds=(0.5,)).observe(0.1)
        r.register_view("serving", lambda: {"dispatches": 7, "qps": 1.5})
        text = r.prometheus()
        assert "# TYPE reqs counter" in text
        assert 'reqs{shard="0"} 3' in text
        assert 'lat_bucket{le="0.5"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "serving_dispatches 7" in text

    def test_histogram_bucket_lines_keep_their_label_set(self):
        """Satellite (ISSUE 15): two label sets of one histogram used
        to emit colliding unlabeled {le=...} bucket samples — buckets
        must merge the series labels with le, consistent with
        _count/_sum."""
        r = MetricsRegistry()
        h = r.histogram("lat", bounds=(0.5,))
        h.observe(0.1, shard="0")
        h.observe(0.9, shard="1")
        text = r.prometheus()
        assert 'lat_bucket{shard="0",le="0.5"} 1' in text
        assert 'lat_bucket{shard="0",le="+Inf"} 1' in text
        assert 'lat_bucket{shard="1",le="0.5"} 0' in text
        assert 'lat_bucket{shard="1",le="+Inf"} 1' in text
        # no unlabeled bucket line survives
        assert 'lat_bucket{le="' not in text

    def test_prometheus_exposition_is_well_formed(self):
        """Strict line-grammar check over a POPULATED registry (labeled
        histograms included): every sample parses, no duplicate sample
        name per label set, buckets cumulative and monotone, +Inf
        bucket == _count, _count/_sum label-consistent with their
        buckets."""
        import re

        r = MetricsRegistry()
        c = r.counter("reqs")
        c.inc(3)
        c.inc(2, shard="0")
        c.inc(7, shard="1", route="a")
        g = r.gauge("depth")
        g.set(4.5)
        g.set(2.0, shard="0")
        h = r.histogram("lat", bounds=(0.01, 0.1, 1.0))
        for v, n in ((0.005, 3), (0.05, 2), (0.5, 4), (5.0, 1)):
            for _ in range(n):
                h.observe(v)
                h.observe(v * 2, shard="1")
        r.register_view(
            "serving", lambda: {"dispatches": 7, "nested": {"qps": 1.5}}
        )
        text = r.prometheus()
        line_re = re.compile(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
            r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")"
            r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*)\})?"
            r" (-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|inf))$"
        )
        samples = {}
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            m = line_re.match(line)
            assert m, f"malformed exposition line: {line!r}"
            name, labels, value = m.group(1), m.group(2) or "", m.group(3)
            key = (name, tuple(sorted(labels.split(","))) if labels
                   else ())
            assert key not in samples, (
                f"duplicate sample for {name} with labels {labels!r}"
            )
            samples[key] = float(value)
        # histogram invariants per label set: buckets cumulative and
        # monotone, +Inf == _count, _count/_sum present with the SAME
        # label set as their buckets
        by_series = {}
        for (name, labels), v in samples.items():
            if not name.startswith("lat_bucket"):
                continue
            le = next(p for p in labels if p.startswith('le="'))
            rest = tuple(p for p in labels if not p.startswith('le="'))
            by_series.setdefault(rest, []).append((le, v))
        assert len(by_series) == 2  # unlabeled + shard="1"
        for rest, buckets in by_series.items():
            order = {f'le="{b}"': i for i, b in
                     enumerate(("0.01", "0.1", "1.0", "+Inf"))}
            buckets.sort(key=lambda bv: order[bv[0]])
            values = [v for _le, v in buckets]
            assert values == sorted(values), (rest, values)
            count = samples[("lat_count", rest)]
            assert values[-1] == count, (rest, values, count)
            assert ("lat_sum", rest) in samples
        assert samples[("serving_dispatches", ())] == 7
        assert samples[("serving_nested_qps", ())] == 1.5

    def test_snapshot_writer_writes_atomically(self, tmp_path):
        r = MetricsRegistry()
        r.counter("n").inc(5)
        w = SnapshotWriter(r, str(tmp_path), period_s=0.05).start()
        time.sleep(0.2)
        w.stop()
        assert w.writes >= 1
        snap = json.load(open(tmp_path / "metrics_snapshot.json"))
        assert snap["metrics"]["n"]["values"][""] == 5


# -- the {"op": "metrics"} wire exposition ------------------------------------


class _Client:
    def __init__(self, port, timeout=15.0):
        self.sock = socket.create_connection(
            ("127.0.0.1", port), timeout=timeout
        )
        self.reader = self.sock.makefile("rb")

    def ask(self, obj):
        self.sock.sendall((json.dumps(obj) + "\n").encode())
        return json.loads(self.reader.readline())

    def close(self):
        try:
            self.reader.close()
            self.sock.close()
        except OSError:
            pass


@pytest.fixture
def obs_stack(rng):
    """frontend + batcher with a live metrics registry and a fresh
    flight recorder, torn down in drain order."""
    recs = synth_records(rng)
    ds = build_game_dataset(recs, SHARDS, ["userId"])
    lm = synth_model(rng)
    bank = make_bank(lm, ds)
    sm = ServingModel(bank, ServingPrograms((1, 8)))
    metrics = ServingMetrics()
    registry = MetricsRegistry()
    registry.register_view("serving", metrics.snapshot)
    rec = reset_flight_recorder()
    registry.register_view(
        "flight", lambda: {"conservation": rec.check_conservation()}
    )
    batcher = MicroBatcher(sm.current, sm.programs, metrics)
    fe = ServingFrontend(
        batcher, sm, SHARDS, metrics=metrics, port=0,
        metrics_registry=registry,
    ).start()
    yield recs, ds, lm, metrics, registry, fe
    fe.stop_accepting()
    batcher.drain(10.0)
    fe.close()
    batcher.close()


class TestMetricsOp:
    def test_live_snapshot_reconciles_with_exit_metrics_json(
        self, obs_stack, tmp_path
    ):
        recs, ds, lm, metrics, registry, fe = obs_stack
        ref = batch_reference_scores(lm, ds)
        c = _Client(fe.port)
        try:
            for i in range(8):
                resp = c.ask(recs[i])
                assert resp["status"] == "ok"
                assert np.float32(resp["score"]) == ref[i]
            live = c.ask({"op": "metrics", "uid": "m1"})
        finally:
            c.close()
        assert live["status"] == "ok" and live["uid"] == "m1"
        serving_live = live["metrics"]["serving"]
        assert serving_live["requests"] == 8
        assert live["metrics"]["flight"]["conservation"]["ok"]
        # the live op and the exit artifact are the SAME accumulator:
        # traffic has stopped, so every counter reconciles exactly
        # response accounting happens on the connection writer thread
        # AFTER the bytes go out — wait for it to settle (8 score
        # responses + the metrics-op reply) before comparing artifacts
        from tests.test_serving import _wait_until

        _wait_until(
            lambda: metrics.snapshot().get("responses", {}).get("ok", 0)
            >= 9,
        )
        out = str(tmp_path / "metrics.json")
        metrics.write(out)
        final = json.load(open(out))["serving"]
        for key in ("requests", "dispatches", "sheds",
                    "generation_dispatches"):
            assert final[key] == serving_live[key], key
        # the metrics-op reply is one more wire response than whatever
        # the live snapshot had seen at op time
        assert final["responses"]["ok"] >= serving_live.get(
            "responses", {}
        ).get("ok", 0)
        assert final["responses"]["ok"] == 9

    def test_prometheus_format_and_fallback(self, obs_stack, rng):
        recs, ds, lm, metrics, registry, fe = obs_stack
        c = _Client(fe.port)
        try:
            resp = c.ask({"op": "metrics", "format": "prometheus"})
            assert resp["status"] == "ok"
            assert "serving_requests" in resp["text"]
        finally:
            c.close()
        # a frontend WITHOUT a registry still answers (accumulator
        # fallback) — the op is always available
        bank = make_bank(lm, ds)
        sm2 = ServingModel(bank, ServingPrograms((1,)))
        m2 = ServingMetrics()
        b2 = MicroBatcher(sm2.current, sm2.programs, m2)
        fe2 = ServingFrontend(b2, sm2, SHARDS, metrics=m2, port=0).start()
        c2 = _Client(fe2.port)
        try:
            resp = c2.ask({"op": "metrics"})
            assert resp["status"] == "ok"
            assert "serving" in resp["metrics"]
            bad = c2.ask({"op": "metrics", "format": "prometheus"})
            assert bad["status"] == "error"
            assert bad["error"] == "BAD_REQUEST"
        finally:
            c2.close()
            fe2.stop_accepting()
            b2.drain(5.0)
            fe2.close()
            b2.close()

    def test_flight_op_serves_ring_and_conservation(self, obs_stack):
        recs, ds, lm, metrics, registry, fe = obs_stack
        flight_recorder().record("swap.commit", generation=2)
        c = _Client(fe.port)
        try:
            resp = c.ask({"op": "flight", "uid": "f1"})
        finally:
            c.close()
        assert resp["status"] == "ok" and resp["uid"] == "f1"
        kinds = [e["kind"] for e in resp["flight"]["events"]]
        assert "swap.commit" in kinds
        assert resp["conservation"]["ok"]
        # dump_flight without a configured path is a named refusal
        c = _Client(fe.port)
        try:
            resp = c.ask({"op": "dump_flight"})
        finally:
            c.close()
        assert resp["status"] == "error"
        assert resp["error"] == "BAD_REQUEST"


# -- flight recorder ----------------------------------------------------------


class TestFlightRecorder:
    def test_ring_bounds_and_counters_survive_eviction(self):
        rec = FlightRecorder(capacity=16)
        for i in range(100):
            rec.record("request.shed", i=i)
        snap = rec.snapshot()
        assert snap["retained"] == 16
        assert snap["recorded"] == 100
        assert snap["dropped"] == 84
        seqs = [e["seq"] for e in snap["events"]]
        assert seqs == list(range(85, 101))  # newest 16, ordered

    def test_conservation_positive_over_a_real_batcher_run(self, rng):
        recs = synth_records(rng, n=20)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        bank = make_bank(synth_model(rng), ds)
        programs = ServingPrograms((1, 8))
        programs.ensure_compiled(bank)
        rec = reset_flight_recorder()
        with MicroBatcher(lambda: bank, programs) as mb:
            for r in requests_from_dataset(ds, bank):
                mb.score(r)
        cons = rec.check_conservation()
        assert cons["ok"], cons
        assert cons["admitted"] == 20
        assert cons["terminal"] == {"ok": 20}
        assert cons["terminal_by_generation"] == {"1": 20}

    def test_conservation_negative_on_injected_drop(self, rng):
        recs = synth_records(rng, n=6)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        bank = make_bank(synth_model(rng), ds)
        programs = ServingPrograms((1, 8))
        programs.ensure_compiled(bank)
        rec = reset_flight_recorder()
        with MicroBatcher(lambda: bank, programs) as mb:
            for r in requests_from_dataset(ds, bank):
                mb.score(r)
        # the injected drop: an admitted request whose terminal outcome
        # never happened (the exact bug class the invariant exists for)
        rec.note_admitted()
        cons = rec.check_conservation()
        assert not cons["ok"]
        assert cons["in_flight"] == 1

    def test_conservation_conserved_across_swaps(self, rng):
        """Generation flips mid-traffic must not lose requests: the
        per-generation terminal split re-sums to admitted."""
        recs = synth_records(rng, n=16)
        ds = build_game_dataset(recs, SHARDS, ["userId"])
        lm = synth_model(rng)
        bank = make_bank(lm, ds)
        sm = ServingModel(bank, ServingPrograms((1, 8)))
        rec = reset_flight_recorder()
        reqs = requests_from_dataset(ds, bank)
        with MicroBatcher(sm.current, sm.programs) as mb:
            for r in reqs[:8]:
                mb.score(r)
            sm.swap_to_bank(make_bank(lm, ds, device=False))
            for r in reqs[8:]:
                mb.score(r)
        cons = rec.check_conservation()
        assert cons["ok"], cons
        assert cons["admitted"] == 16
        assert cons["terminal_by_generation"] == {"1": 8, "2": 8}
        # the swap transition itself is on the ring
        kinds = [e["kind"] for e in rec.events("swap.")]
        assert "swap.commit" in kinds

    def test_auto_dump_on_swap_transition(self, tmp_path):
        rec = FlightRecorder(capacity=32)
        path = str(tmp_path / "flight.json")
        rec.set_auto_dump(path)
        rec.record("request.shed", reason="x")  # not a transition
        assert not os.path.exists(path)
        rec.record("swap.commit", generation=2)
        dump = json.load(open(path))
        kinds = [e["kind"] for e in dump["events"]]
        assert kinds == ["request.shed", "swap.commit"]

    def test_sigterm_dumps_atomically_then_terminates(self, tmp_path):
        """install_signal_dump chains the dump ONTO SIGTERM: the dump
        lands (valid, complete JSON) and the default disposition still
        terminates the process."""
        dump = str(tmp_path / "flight.json")
        script = (
            "import sys, time\n"
            "from photon_ml_tpu.obs.flight_recorder import ("
            "flight_recorder, install_signal_dump)\n"
            "rec = flight_recorder()\n"
            "rec.record('swap.commit', generation=2)\n"
            "rec.note_admitted(3)\n"
            "rec.note_terminal('ok', generation=2, n=3)\n"
            "install_signal_dump(sys.argv[1])\n"
            "print('READY', flush=True)\n"
            "while True:\n"
            "    time.sleep(0.05)\n"
        )
        p = subprocess.Popen(
            [sys.executable, "-c", script, dump],
            cwd=REPO, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        try:
            assert p.stdout.readline().strip() == "READY"
            p.send_signal(signal.SIGTERM)
            p.wait(timeout=30)
        finally:
            if p.poll() is None:
                p.kill()
        assert p.returncode == -signal.SIGTERM
        data = json.load(open(dump))
        assert data["reason"] == f"signal {signal.SIGTERM}"
        kinds = [e["kind"] for e in data["events"]]
        assert "swap.commit" in kinds and "signal" in kinds
        assert data["conservation"]["ok"]

    def test_event_emitter_folds_into_the_recorder(self):
        """Satellite: ONE structured-event path — the legacy emitter's
        sends land on the flight ring, and the compat shim still
        exports everything."""
        from photon_ml_tpu import events as shim
        from photon_ml_tpu.obs import events as folded

        assert shim.EventEmitter is folded.EventEmitter
        assert shim.ScheduleCacheEvent is folded.ScheduleCacheEvent
        rec = reset_flight_recorder()
        seen = []

        class L(shim.EventListener):
            def on_event(self, e):
                seen.append(e)

        em = shim.EventEmitter()
        em.register(L())
        em.send(shim.TrainingStartEvent("job-1"))
        em.send(shim.PhotonOptimizationLogEvent(reg_weight=0.5))
        assert len(seen) == 2
        kinds = [e["kind"] for e in rec.events("event.")]
        assert kinds == [
            "event.TrainingStartEvent",
            "event.PhotonOptimizationLogEvent",
        ]
        ev = rec.events("event.TrainingStart")[0]
        assert ev["fields"]["job_name"] == "job-1"
        em.close()


# -- ObsSession ---------------------------------------------------------------


class TestObsSession:
    def test_disabled_session_noops(self):
        sess = ObsSession(None)
        assert not sess.enabled
        sess.record("swap.commit")
        assert sess.finish() is None

    def test_session_wires_views_and_exports_on_finish(self, tmp_path):
        from photon_ml_tpu.obs.registry import reset_default_registry
        from photon_ml_tpu.obs.trace import set_tracing, span

        reset_default_registry()
        reset_flight_recorder()
        obs_dir = str(tmp_path / "obs")
        sess = ObsSession(obs_dir, snapshot_period_s=60, signal_dump=False)
        try:
            assert tracing_enabled()
            with span("cd.iteration", iteration=1):
                pass
            sess.record("swap.commit", generation=2)
            summary = sess.finish()
        finally:
            set_tracing(False)
        assert summary["conservation"]["ok"]
        trace = json.load(open(summary["trace_path"]))
        assert any(
            e["name"] == "cd.iteration" for e in trace["traceEvents"]
        )
        flight = json.load(open(summary["flight_path"]))
        assert any(e["kind"] == "swap.commit" for e in flight["events"])
        snap = json.load(open(os.path.join(obs_dir, "metrics_snapshot.json")))
        for view in ("host_timings", "reliability", "readbacks", "flight"):
            assert view in snap, view
        assert sess.finish() is None  # idempotent


# -- interleave schedule family: span emit x swap x dump ---------------------


class TestObsInterleave:
    def _scenario(self, sched):
        rec = None
        t = None
        dumps = []

        def emitter(tag):
            def body():
                for i in range(10):
                    s = t.start(f"req.{tag}")
                    rec.record("request.shed", tag=tag, i=i)
                    s.end()
            return body

        def swapper():
            for g in (2, 3):
                rec.record("swap.commit", generation=g)
                rec.note_admitted(2)
                rec.note_terminal("ok", generation=g, n=2)

        def dumper():
            for _ in range(4):
                snap = rec.snapshot()
                dumps.append(snap)

        with sched.patched():
            # recorder/tracer constructed in the patched window: their
            # locks are cooperative, so the scheduler owns every
            # preemption point
            rec = FlightRecorder(capacity=64)
            t = Tracer(max_spans=256)
            sched.spawn(emitter("a"), name="emit-a")
            sched.spawn(emitter("b"), name="emit-b")
            sched.spawn(swapper, name="swap")
            sched.spawn(dumper, name="dump")

        def verify():
            # no torn dumps: every snapshot's sequence numbers are
            # strictly increasing and consistent with its own count
            for snap in dumps:
                seqs = [e["seq"] for e in snap["events"]]
                assert seqs == sorted(seqs)
                assert len(set(seqs)) == len(seqs)
                assert snap["retained"] == len(snap["events"])
            final = rec.snapshot()
            assert final["recorded"] == 22  # 2x10 sheds + 2 swaps
            assert rec.check_conservation()["ok"]
            assert len(t) == 20

        return verify

    def test_span_emit_swap_dump_schedules(self):
        explore(self._scenario, seeds=range(25))
