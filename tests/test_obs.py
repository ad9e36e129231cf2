"""Units for ``repro.obs``: registry semantics, streaming-histogram
quantile accuracy, trace export/validation, Prometheus exposition
round-trip, and the uniform snapshot schema."""
import json

import numpy as np
import pytest

from repro.obs import (BUCKETS_PER_DECADE, NULL_SPAN, LatencySeries,
                       MetricsRegistry, Observability, Tracer, bucket_label,
                       parse_prometheus, stats_snapshot, to_prometheus,
                       validate_trace, write_json_snapshot,
                       write_prometheus)
from repro.obs.registry import RESERVOIR_CAP, Histogram

#: half-bucket relative error bound of the log-bucketed quantiles
QERR = 10.0 ** (0.5 / BUCKETS_PER_DECADE) - 1.0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_get_or_create_identity():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "help", mode="a")
    b = reg.counter("x_total", mode="a")
    c = reg.counter("x_total", mode="b")
    assert a is b and a is not c
    a.inc()
    a.add(2)
    assert b.value == 3 and c.value == 0
    # same name, different kind → loud error, not silent shadowing
    with pytest.raises(TypeError):
        reg.gauge("x_total", mode="a")


def test_counter_negative_delta_and_gauge_ratchet():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.add(5)
    c.add(-2)                            # the serving cancel path unwinds
    assert c.value == 3
    g = reg.gauge("peak")
    g.max(4)
    g.max(2)
    assert g.value == 4
    g.set(1)
    g.inc()
    assert g.value == 2


def test_histogram_quantiles_within_bucket_error():
    h = Histogram("lat")
    rng = np.random.RandomState(0)
    xs = np.abs(rng.lognormal(mean=-3.0, sigma=1.5, size=5000))
    for v in xs:
        h.observe(float(v))
    assert h.count == len(xs)
    assert h.sum == pytest.approx(float(xs.sum()))
    assert h.min == pytest.approx(float(xs.min()))
    assert h.max == pytest.approx(float(xs.max()))
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.percentile(xs, 100 * q,
                                    method="inverted_cdf"))
        got = h.quantile(q)
        assert abs(got - exact) <= (QERR + 1e-9) * exact + 1e-12, \
            f"q={q}: {got} vs exact {exact}"


def test_histogram_zero_and_negative_samples():
    h = Histogram("lat")
    for v in (0.0, -1.0, 0.5, 2.0):
        h.observe(v)
    assert h.quantile(0.25) <= 0.0       # zero bucket sorts below positives
    assert h.quantile(1.0) == pytest.approx(2.0)
    assert h.count == 4


def test_histogram_memory_is_bounded():
    h = Histogram("lat")
    for i in range(50_000):
        h.observe(1e-6 * (1 + (i % 1000)))
    # samples span 4 decades max → bucket dict stays tiny; reservoir capped
    assert len(h._buckets) <= 4 * BUCKETS_PER_DECADE
    assert len(h.recent) == RESERVOIR_CAP
    assert h.count == 50_000


def test_latency_series_list_compat():
    s = LatencySeries(Histogram("lat"))
    assert not s                         # falsy when empty (like a list)
    s.append(0.5)
    s.extend([1.0, 2.0])
    assert len(s) == 3 and bool(s)
    assert list(s) == [0.5, 1.0, 2.0]
    assert s[0] == 0.5 and s[-1] == 2.0
    assert np.asarray(s).tolist() == [0.5, 1.0, 2.0]
    assert float(np.percentile(np.asarray(s), 99)) > 0
    assert s.mean == pytest.approx(3.5 / 3)
    assert max(s) == 2.0
    assert all(v > 0 for v in s)


def test_bucket_label_pow2():
    assert bucket_label(3, 24, 96) == "4x32x128"
    assert bucket_label(1) == "1"


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def test_tracer_disabled_is_noop():
    t = Tracer(enabled=False)
    sp = t.begin("x", "engine")
    assert sp is NULL_SPAN
    sp.annotate(a=1)
    sp.end()
    t.instant("i")
    assert t.events == []


def test_tracer_spans_nest_and_validate(tmp_path):
    t = Tracer(enabled=True)
    with t.span("outer", "engine", {"k": 1}):
        with t.span("inner", "engine"):
            pass
        t.instant("mark", "engine")
    sp = t.begin("req", "req/0")
    sp.end(tokens=3)
    sp.end(tokens=9)                     # idempotent: second end ignored
    obj = t.to_json()
    assert validate_trace(obj) == 3      # outer, inner, req ("i" not counted)
    names = {e["name"] for e in obj["traceEvents"] if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= names
    # same track → same tid; different track → different tid
    by_name = {e["name"]: e for e in t.events}
    assert by_name["outer"]["tid"] == by_name["inner"]["tid"]
    assert by_name["req"]["tid"] != by_name["outer"]["tid"]
    assert by_name["req"]["args"] == {"tokens": 3}
    # inner is contained within outer (how Perfetto renders nesting)
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    p = tmp_path / "trace.json"
    t.export(str(p))
    assert validate_trace(str(p)) == 3
    assert validate_trace(p.read_text()) == 3


def test_engine_spans_are_profiler_annotations(tmp_path):
    """Engine-track spans, enabled tracer or not, are written into an
    active profiler trace as ``engine.<name>`` host events, nested as
    they were opened; other tracks are not."""
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        for t in (Tracer(enabled=False), Tracer(enabled=True)):
            with t.span("step"):
                with t.span("admit.launch"):
                    t.begin("queue", "req/7").end()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    got = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in jax.profiler.ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("engine.") or e.name == "queue"]
    names = sorted(n for n, _, _ in got)
    assert names == ["engine.admit.launch"] * 2 + ["engine.step"] * 2
    steps = [g for g in got if g[0] == "engine.step"]
    for _, a, b in (g for g in got if g[0] == "engine.admit.launch"):
        assert any(s <= a and b <= e for _, s, e in steps)
    # no profiler trace active: a disabled tracer hands out the no-op span
    assert Tracer(enabled=False).begin("step") is NULL_SPAN


def test_tracer_event_cap():
    t = Tracer(enabled=True, max_events=3)
    for k in range(10):
        t.begin(f"s{k}").end()
    assert len(t.events) == 3 and t.dropped == 7


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"notTraceEvents": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "X", "pid": 0, "tid": 1,
                                         "ts": 0.0}]})   # X without dur
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "i", "pid": 0}]})


def test_phase_stack():
    from repro.obs import current_phase, phase_scope
    assert current_phase() == "other"
    with phase_scope("prefill"):
        assert current_phase() == "prefill"
        with phase_scope("decode"):
            assert current_phase() == "decode"
        assert current_phase() == "prefill"
    assert current_phase() == "other"


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


def _toy_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests", mode="sync").add(7)
    reg.counter("reqs_total", mode="async").add(2)
    reg.gauge("inflight", "in flight").set(3)
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.001, 0.01, 0.1, 1.0):
        h.observe(v)
    # label values that need escaping must round-trip
    reg.counter("odd_total", label='a"b\\c').inc()
    return reg


def test_prometheus_roundtrip():
    reg = _toy_registry()
    text = to_prometheus(reg)
    got = parse_prometheus(text)
    assert got["repro_reqs_total"] == [({"mode": "sync"}, 7.0),
                                       ({"mode": "async"}, 2.0)]
    assert got["repro_inflight"] == [({}, 3.0)]
    # histogram → summary: quantile series + _sum/_count
    qs = {r[0]["quantile"] for r in got["repro_lat_seconds"]}
    assert qs == {"0.5", "0.95", "0.99"}
    assert got["repro_lat_seconds_count"] == [({}, 4.0)]
    assert got["repro_lat_seconds_sum"][0][1] == pytest.approx(1.111)
    assert got["repro_odd_total"][0][0]["label"] == 'a\\"b\\\\c'
    # HELP/TYPE lines present and the format self-describes as summary
    assert "# TYPE repro_lat_seconds summary" in text
    assert "# HELP repro_reqs_total requests" in text


def test_prometheus_parser_is_strict():
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line\n")
    with pytest.raises(ValueError):
        parse_prometheus('x{bad-label="1"} 2\n')
    with pytest.raises(ValueError):
        parse_prometheus('x{a="unterminated} 2\n')


def test_write_prometheus_and_json(tmp_path):
    reg = _toy_registry()
    p = tmp_path / "m.prom"
    text = write_prometheus(str(p), reg)
    assert p.read_text() == text
    parse_prometheus(p.read_text())
    j = tmp_path / "m.json"
    snap = write_json_snapshot(str(j), reg)
    loaded = json.loads(j.read_text())
    assert loaded == json.loads(json.dumps(snap))
    assert loaded["lat_seconds"][0]["count"] == 4
    assert "p99" in loaded["lat_seconds"][0]


# ---------------------------------------------------------------------------
# snapshot schema + EngineStats back-compat (no engine needed)
# ---------------------------------------------------------------------------


def test_stats_snapshot_schema():
    from repro.serving import EngineStats
    s = EngineStats()
    s.prefills += 3
    s.tokens_out += 30
    s.wall_s += 2.0
    s.ttft_s.extend([0.1, 0.2, 0.4])
    s.itl_s.extend([0.01] * 30)
    snap = stats_snapshot(s)
    assert snap["schema"] == "repro.obs/v1"
    assert snap["prefills"] == 3 and snap["tokens_out"] == 30
    assert snap["tokens_per_s"] == pytest.approx(15.0)
    for blk in ("ttft", "ttft_queue", "ttft_compute", "itl"):
        assert set(snap[blk]) == {"mean_s", "p50_s", "p95_s", "p99_s",
                                  "count"}
    assert snap["ttft"]["count"] == 3
    assert snap["ttft"]["mean_s"] == pytest.approx(0.7 / 3)
    assert snap["itl"]["p50_s"] == pytest.approx(0.01, rel=2 * QERR)
    assert stats_snapshot(s, wall_s=1.0)["tokens_per_s"] == \
        pytest.approx(30.0)
    assert json.loads(json.dumps(snap)) == snap      # JSON-able
    # s.snapshot() is the method spelling of the same thing
    assert s.snapshot() == snap


def test_engine_stats_mutation_compat():
    """Every mutation idiom the serving engine uses must keep working on
    the registry-backed EngineStats."""
    from repro.serving import EngineStats
    s = EngineStats()
    s.prefills += 2
    s.prefills -= 1                      # cancel_pending unwinds
    s.prefill_inflight_peak = max(s.prefill_inflight_peak, 5)
    s.wall_s += 0.25
    s.ttft_s.append(0.1)
    s.itl_s.extend([0.02, 0.03])
    assert s.prefills == 1
    assert s.prefill_inflight_peak == 5
    assert s.wall_s == pytest.approx(0.25)
    assert s.mean_ttft_s == pytest.approx(0.1)
    assert s.mean_itl_s == pytest.approx(0.025)
    assert len(s.itl_s) == 2
    # two engines' stats are isolated (per-engine registries)
    s2 = EngineStats()
    assert s2.prefills == 0
    # metrics visible via the registry under serving_* names
    names = {m.name for m in s.registry.metrics()}
    assert {"serving_prefills", "serving_ttft_seconds",
            "serving_wall_seconds"} <= names


def test_observability_bundle():
    obs = Observability()
    assert not obs.trace_enabled
    assert obs.tracer.begin("x") is NULL_SPAN
    obs2 = Observability(trace=True)
    assert obs2.trace_enabled
    assert obs.registry is not obs2.registry


def test_compile_watch_counts_real_compiles():
    """The jax.monitoring listener sees one backend-compile event per real
    XLA compile, attributed to the active phase; jit-cache hits add none."""
    import jax
    import jax.numpy as jnp
    from repro.obs import GLOBAL, install_compile_watch, phase_scope
    install_compile_watch()

    def get():
        for m in GLOBAL.metrics():
            if m.name == "jit_compiles_total" \
                    and m.labels.get("phase") == "obs-test":
                return m.value
        return 0

    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7, dtype=jnp.float32)
    before = get()
    with phase_scope("obs-test"):
        f(x).block_until_ready()
    after_compile = get()
    with phase_scope("obs-test"):
        f(x).block_until_ready()         # cache hit: no new compile
    assert after_compile == before + 1
    assert get() == after_compile
