"""repro.obs: the unified metrics/tracing/profiling layer.

Covers, in order: registry instrument semantics (labels, kinds, the
disabled null path), the histogram quantile bracketing property
(hypothesis: the estimate always lands in the bucket containing the
exact order statistic), exporters (JSONL event sink, Prometheus text,
snapshot files), per-request trace lifecycle completeness under
randomized scheduler traffic, the retrace metric catching a genuine
mid-serve recompile, the ISSUE's acceptance snapshot (one registry,
mixed spec+paged+multi-tenant serve: quantiles, prefix ratios,
acceptance rate, bank evictions, zero retraces), the obs wiring in the
training loop and profiling helpers, the model step's device scopes, and
the serving tick's host phase spans (their cover of the tick, slow-tick
events, the prefill compile count).
"""
import json
import math
import re
from bisect import bisect_left

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import tiny_cfg
from repro.common.types import AdapterCfg
from repro.models import model as M
from repro.obs import (DEFAULT_BUCKETS, Histogram, JsonlSink, MetricsRegistry,
                       NULL_TRACE, format_key, render_prometheus,
                       write_snapshot)
from repro.serving import (MultiTaskEngine, Request, ServeEngine,
                           ServingConfig, make_scheduler)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# registry instruments
# ---------------------------------------------------------------------------


def test_registry_labels_key_distinct_series():
    reg = MetricsRegistry()
    a = reg.counter("hits_total", tenant="a")
    b = reg.counter("hits_total", tenant="b")
    assert a is not b
    assert a is reg.counter("hits_total", tenant="a")  # stable identity
    a.inc(3)
    b.inc()
    snap = reg.snapshot()
    assert snap["counters"]["hits_total{tenant=a}"] == 3
    assert snap["counters"]["hits_total{tenant=b}"] == 1
    # label order never matters: sorted into the key
    assert reg.counter("x_total", b="2", a="1") is \
        reg.counter("x_total", a="1", b="2")


def test_registry_rejects_kind_mismatch():
    reg = MetricsRegistry()
    reg.counter("serve_ticks_total")
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.histogram("serve_ticks_total")


def test_disabled_registry_is_shared_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("a_total")
    h = reg.histogram("b_s")
    assert c is reg.gauge("anything")  # one shared null instrument
    c.inc(5)
    h.observe(1.0)
    assert c.value == 0 and h.count == 0
    reg.event("retrace", fn="decode")
    assert not reg.events and reg.events_of("retrace") == []
    assert reg.tracer.start(1) is NULL_TRACE
    assert reg.snapshot()["counters"] == {}


def test_derived_metrics_evaluate_at_snapshot_time():
    reg = MetricsRegistry()
    hits = reg.counter("hits_total")
    reg.add_derived("hit_ratio", lambda: hits.value / 10)
    hits.inc(3)
    assert reg.snapshot()["derived"]["hit_ratio"] == pytest.approx(0.3)
    hits.inc(4)
    assert reg.snapshot()["derived"]["hit_ratio"] == pytest.approx(0.7)


# ---------------------------------------------------------------------------
# histogram quantile bracketing (hypothesis)
# ---------------------------------------------------------------------------


def _bucket_of(edges, v):
    return bisect_left(edges, v)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 400),
       q=st.sampled_from([0.5, 0.95, 0.99]))
def test_histogram_percentile_brackets_exact_quantile(seed, n, q):
    """The order-statistic estimate must land in the SAME fixed bucket as
    the exact rank-ceil(q*n) order statistic, and inside the observed
    range - the accuracy contract the p50/p95/p99 report keys rest on.
    Values are log-uniform across (and beyond) the bucket layout, so the
    underflow (< first edge) and overflow (> last edge) buckets are
    exercised too."""
    rs = np.random.RandomState(seed)
    vals = np.exp(rs.uniform(np.log(1e-5), np.log(200.0), size=n))
    h = Histogram()
    for v in vals:
        h.observe(float(v))

    exact = float(np.sort(vals)[max(1, math.ceil(q * n)) - 1])
    est = h.percentile(q)
    assert _bucket_of(DEFAULT_BUCKETS, est) == \
        _bucket_of(DEFAULT_BUCKETS, exact), (q, exact, est)
    assert vals.min() <= est <= vals.max()


def test_histogram_degenerate_and_empty():
    h = Histogram()
    assert h.percentile(0.5) == 0.0 and h.summary()["count"] == 0
    for _ in range(9):
        h.observe(0.42)
    # all mass at one point: clamping makes every quantile exact
    assert h.percentile(0.5) == pytest.approx(0.42)
    assert h.percentile(0.99) == pytest.approx(0.42)
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram(buckets=(1.0, 1.0))


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_jsonl_sink_and_events(tmp_path):
    path = tmp_path / "events.jsonl"
    reg = MetricsRegistry()
    reg.add_sink(JsonlSink(str(path)))
    reg.event("retrace", fn="decode", count=1)
    reg.event("bank_evict", victim="task0")
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [e["event"] for e in lines] == ["retrace", "bank_evict"]
    assert lines[0]["fn"] == "decode" and "t_unix" in lines[0]
    assert len(reg.events_of("retrace")) == 1


def test_prometheus_rendering_cumulative_buckets():
    reg = MetricsRegistry()
    reg.counter("serve_tokens_total", sched="paged").inc(7)
    h = reg.histogram("serve_ttft_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = render_prometheus(reg)
    assert '# TYPE serve_tokens_total counter' in text
    assert 'serve_tokens_total{sched="paged"} 7' in text
    # bucket counts are cumulative and end at +Inf == count
    assert 'serve_ttft_s_bucket{le="0.1"} 1' in text
    assert 'serve_ttft_s_bucket{le="1"} 2' in text
    assert 'serve_ttft_s_bucket{le="+Inf"} 3' in text
    assert 'serve_ttft_s_count 3' in text


def test_write_snapshot_json_and_prom(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total").inc(2)
    snap = write_snapshot(reg, str(tmp_path / "m.json"))
    assert snap["schema"] == "repro-obs-v1"
    assert json.loads((tmp_path / "m.json").read_text())["counters"] == \
        {"a_total": 2}
    write_snapshot(reg, str(tmp_path / "m.prom"))
    assert "a_total 2" in (tmp_path / "m.prom").read_text()


def _parse_prom_labels(s):
    """Strict label-body parser: quoted values with the three escapes the
    text exposition format defines (backslash, quote, newline)."""
    out = {}
    i = 0
    while i < len(s):
        eq = s.index("=", i)
        key = s[i:eq]
        assert s[eq + 1] == '"', s
        i = eq + 2
        buf = []
        while s[i] != '"':
            if s[i] == "\\":
                buf.append({"\\": "\\", '"': '"', "n": "\n"}[s[i + 1]])
                i += 2
            else:
                buf.append(s[i])
                i += 1
        out[key] = "".join(buf)
        i += 1
        if i < len(s):
            assert s[i] == ","
            i += 1
    return out


def _parse_prom(text):
    """Parse a v0.0.4 exposition into ({name: kind}, [(name, labels,
    value)]), asserting structure: exactly one TYPE line per metric name,
    every sample line well-formed."""
    import re

    name_re = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
    typed, samples = {}, []
    assert text.endswith("\n")
    for line in text[:-1].split("\n"):
        assert line
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert name_re.match(name)
            assert kind in ("counter", "gauge", "histogram")
            assert name not in typed, f"duplicate TYPE for {name}"
            typed[name] = kind
            continue
        assert not line.startswith("#")
        if "{" in line:
            name, rest = line.split("{", 1)
            body, val = rest.rsplit("} ", 1)
            labels = _parse_prom_labels(body)
        else:
            name, val = line.rsplit(" ", 1)
            labels = {}
        assert name_re.match(name)
        samples.append((name, labels, float(val)))
    return typed, samples


def test_prometheus_label_escaping_round_trip():
    """A label value carrying a backslash, quotes and a newline must not
    corrupt the scrape: one physical line, escaped per the format, and a
    strict parser recovers the original value exactly."""
    reg = MetricsRegistry()
    nasty = 'ten\\ant "a"\nsecond line'
    reg.counter("bank_hits_total", tenant=nasty).inc(3)
    text = render_prometheus(reg)
    (line,) = [l for l in text.splitlines()
               if l.startswith("bank_hits_total{")]
    assert "\\\\" in line and '\\"' in line and "\\n" in line
    _typed, samples = _parse_prom(text)
    ((_name, labels, value),) = [s for s in samples
                                 if s[0] == "bank_hits_total"]
    assert labels == {"tenant": nasty}
    assert value == 3


# ---------------------------------------------------------------------------
# trace lifecycle completeness under randomized traffic
# ---------------------------------------------------------------------------


def _tasks_world():
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    base = M.init_params(KEY, cfg)
    from repro.core.hadamard import perturb_adapters

    tasks = [perturb_adapters(base, jax.random.fold_in(KEY, 60 + t),
                              scale=0.01) for t in range(2)]
    return cfg, MultiTaskEngine(cfg, tasks)


_WORLD = {}


def _world():
    if not _WORLD:
        _WORLD["cfg"], _WORLD["eng"] = _tasks_world()
    return _WORLD["cfg"], _WORLD["eng"]


@pytest.mark.parametrize("serve_kw", [
    dict(num_slots=2, max_len=32),
    dict(num_slots=2, max_len=32, paged=True, page_size=8, spec_k=2),
])
def test_trace_lifecycle_complete_under_fuzz(serve_kw):
    """Every completed request's trace must tell the whole story: starts
    with submit, admits exactly once (deferred admissions mark `defer`,
    never a second admit), one prefill with a hit kind, first_token
    present, retire last with the completion's reason and token count -
    and mark times monotone."""
    cfg, eng = _world()
    obs = MetricsRegistry()
    sched = make_scheduler(eng, ServingConfig(**serve_kw), obs=obs)
    rs = np.random.RandomState(7)
    reqs = [Request(prompt=rs.randint(0, 97, size=(int(rs.randint(2, 9)),)),
                    max_new_tokens=int(rs.randint(1, 7)), task_id=i % 2)
            for i in range(9)]

    ids, t = [None] * len(reqs), 0
    while None in ids or sched.pending or sched.active:
        for i, r in enumerate(reqs):
            if ids[i] is None and int(rs.randint(0, 2)):
                ids[i] = sched.submit(r)
        sched.step()
        t += 1
        assert t < 500, "fuzz episode failed to drain"
    done = {i: sched.completions.pop(i) for i in ids}

    spec = serve_kw.get("spec_k", 0) > 0
    for rid, c in done.items():
        tr = obs.tracer.find(rid)
        assert tr is not None, rid
        names = tr.names()
        assert names[0] == "submit" and names[-1] == "retire"
        assert tr.count("admit") == 1
        assert tr.count("prefill") == 1
        assert tr.count("first_token") == 1
        assert tr.attrs_of("retire")["tokens"] == len(c.tokens)
        assert tr.count("token") == 0  # no per-token marks
        assert tr.attrs_of("retire")["reason"] == c.finish_reason
        assert tr.attrs_of("admit")["queue_s"] >= 0.0
        kind = tr.attrs_of("prefill")["kind"]
        assert kind in ("cold", "full_hit", "partial_hit")
        dts = [dt for _, dt, _ in tr.events]
        assert dts == sorted(dts)
    assert len(obs.tracer.active) == 0  # every trace was finished
    if spec:
        assert any(tr.count("verify") for tr in
                   (obs.tracer.find(r) for r in ids))
        assert sched.spec_stats["drafted"] > 0


# ---------------------------------------------------------------------------
# retrace metric: a genuine mid-serve recompile must get loud
# ---------------------------------------------------------------------------


def test_retrace_metric_catches_mid_serve_recompile(capsys):
    """A second scheduler with a different slot count over the SAME engine
    forces a real shape-change recompile of the decode tick. The first
    scheduler - still mid-serve - must surface it: counter bumped, event
    recorded, stderr warning. Its own first compile must NOT count."""
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    eng = ServeEngine(cfg, M.init_params(KEY, cfg))
    obs = MetricsRegistry()
    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=32),
                           obs=obs)
    sched.submit(Request(prompt=np.arange(4, dtype=np.int32),
                         max_new_tokens=12))
    sched.step()  # first decode compile: inside the allowance
    assert obs.events_of("retrace") == []

    other = make_scheduler(eng, ServingConfig(num_slots=3, max_len=32))
    other.run([Request(prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2)])  # recompiles decode at B=3

    sched.step()  # the watching scheduler notices on its next tick
    events = obs.events_of("retrace")
    assert len(events) == 1 and events[0]["fn"] == "decode"
    assert obs.snapshot()["counters"][
        "serve_retrace_events_total{sched=contiguous}"] == 1
    assert "recompiled mid-serve" in capsys.readouterr().err
    sched.step()  # no new violation: must not re-fire
    assert len(obs.events_of("retrace")) == 1
    while sched.pending or sched.active:
        sched.step()


# ---------------------------------------------------------------------------
# the acceptance snapshot: one registry across the whole stack
# ---------------------------------------------------------------------------


def test_mixed_serve_snapshot_has_every_series(tmp_path):
    """ISSUE 9 acceptance: a mixed spec+paged+multi-tenant serve feeding
    ONE registry must snapshot TTFT/TPOT p50/p95/p99, prefix-cache hit
    ratios, spec acceptance, bank evictions - with zero retrace events -
    machine-readably."""
    import tempfile

    from repro.core.hadamard import extract_delta, perturb_adapters
    from repro.serving import AdapterBank, AdapterRegistry

    cfg, eng = _world()
    obs = MetricsRegistry()
    sched = make_scheduler(eng, ServingConfig(
        num_slots=2, max_len=32, paged=True, page_size=8, spec_k=2),
        obs=obs)
    rs = np.random.RandomState(3)
    pool = [rs.randint(0, 97, size=(16,)).astype(np.int32)
            for _ in range(2)]
    partial = pool[0].copy()
    partial[8:] = rs.randint(0, 97, size=(8,))
    # KV is cached per task row: the partial-prefix prompt must run under
    # the same task as the pool[0] requests whose first page it shares
    reqs = [Request(prompt=pool[i % 2], max_new_tokens=6, task_id=i % 2)
            for i in range(7)]
    reqs.append(Request(prompt=partial, max_new_tokens=6, task_id=0))
    done, report = sched.run(reqs)
    assert len(done) == 8

    # same registry, hot-swap bank episode: 1 row, 2 tenants -> evictions
    base = M.init_params(KEY, cfg)
    with tempfile.TemporaryDirectory() as adir:
        registry = AdapterRegistry(adir)
        for t in range(2):
            registry.publish(f"tenant{t}", extract_delta(perturb_adapters(
                base, jax.random.fold_in(KEY, 70 + t), scale=0.01)))
        bank = AdapterBank(cfg, base, 1, registry)
        bsched = make_scheduler(MultiTaskEngine(cfg, bank),
                                ServingConfig(num_slots=1, max_len=32),
                                obs=obs)
        bdone, _ = bsched.run(
            [Request(prompt=pool[0], max_new_tokens=3,
                     adapter=f"tenant{i % 2}") for i in range(4)])
        assert len(bdone) == 4

    snap = write_snapshot(obs, str(tmp_path / "serve_metrics.json"))
    assert json.loads((tmp_path / "serve_metrics.json").read_text()) == snap

    ttft = snap["histograms"]["serve_ttft_s{sched=spec_paged}"]
    tpot = snap["histograms"]["serve_tpot_s{sched=spec_paged}"]
    for s in (ttft, tpot):
        assert s["count"] > 0
        assert 0 <= s["p50"] <= s["p95"] <= s["p99"]
    # report carries the same quantiles
    assert report["ttft_p50_s"] == pytest.approx(ttft["p50"])
    assert report["tpot_p99_s"] == pytest.approx(tpot["p99"])

    c = snap["counters"]
    assert c["serve_prefix_hits_total{tier=full}"] > 0
    assert c["serve_prefix_hits_total{tier=partial}"] > 0
    assert 0.0 < snap["derived"]["prefix_hit_ratio_full"] < 1.0
    assert snap["derived"]["spec_acceptance_rate"] == \
        pytest.approx(sched.acceptance_rate)
    assert c["bank_evictions_total"] > 0
    assert c["bank_loads_total"] > c["bank_hits_total"] >= 0
    assert snap["events_by_kind"].get("retrace", 0) == 0
    assert snap["events_by_kind"]["bank_evict"] == c["bank_evictions_total"]

    # per-tenant latency series exist alongside the aggregates
    assert any(k.startswith("serve_ttft_s{") and "tenant=" in k
               for k in snap["histograms"])
    # the old stat surfaces are now views over these counters
    assert sched.stats["full_hits"] == c["serve_prefix_hits_total{tier=full}"]
    assert sched.spec_stats["drafted"] == c["serve_spec_drafted_total"]
    assert bank.evictions == c["bank_evictions_total"]


def test_prometheus_round_trip_under_real_serve():
    """Render a registry fed by a real spec+paged serve and re-parse the
    exposition strictly: every sample maps to a TYPE line, histogram
    buckets are cumulative with a +Inf bucket equal to _count, and
    counter values match the machine snapshot exactly."""
    cfg, eng = _world()
    obs = MetricsRegistry()
    sched = make_scheduler(eng, ServingConfig(
        num_slots=2, max_len=32, paged=True, page_size=8, spec_k=2),
        obs=obs)
    rs = np.random.RandomState(5)
    done, _ = sched.run([
        Request(prompt=rs.randint(0, 97, size=(8,)), max_new_tokens=4,
                task_id=i % 2) for i in range(5)])
    assert len(done) == 5

    typed, samples = _parse_prom(render_prometheus(obs))

    def base_of(name):
        if name in typed:
            return name, None
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name[: -len(suf)] in typed:
                return name[: -len(suf)], suf
        raise AssertionError(f"sample {name!r} has no TYPE line")

    hist_groups = {}
    for name, labels, value in samples:
        base, suf = base_of(name)
        if suf is None:
            assert typed[base] in ("counter", "gauge")
            continue
        assert typed[base] == "histogram"
        key = (base, tuple(sorted(
            (k, v) for k, v in labels.items() if k != "le")))
        g = hist_groups.setdefault(key, {"buckets": []})
        if suf == "_bucket":
            g["buckets"].append((float(labels["le"]), value))
        else:
            g[suf[1:]] = value

    assert any(b == "serve_ttft_s" for b, _ in hist_groups)
    for (base, labkey), g in hist_groups.items():
        assert "count" in g and "sum" in g, (base, labkey)
        les = [le for le, _ in g["buckets"]]
        assert les == sorted(les) and les[-1] == math.inf, (base, labkey)
        cums = [c for _, c in g["buckets"]]
        assert all(a <= b for a, b in zip(cums, cums[1:]))
        assert cums[-1] == g["count"]
        if g["count"]:
            assert g["sum"] > 0.0

    # every counter series round-trips to its snapshot value
    snap = obs.snapshot()
    rendered = {format_key(name, tuple(sorted(labels.items()))): value
                for name, labels, value in samples
                if typed.get(name) == "counter"}
    assert snap["counters"]
    for k, v in snap["counters"].items():
        assert rendered[k] == v, k


# ---------------------------------------------------------------------------
# training loop + profiling hooks
# ---------------------------------------------------------------------------


def test_run_train_reports_into_registry():
    from repro.train.loop import StepWatchdog, run_train

    obs = MetricsRegistry()
    state = {"step": jnp.zeros((), jnp.int32),
             "opt": {"m": jnp.zeros((4, 4))}}

    def step(state, batch):
        return dict(state, step=state["step"] + 1), \
            {"loss": jnp.float32(0.0), "grad_norm": jnp.float32(0.0)}

    batches = iter([{"x": jnp.zeros((1,))}] * 5)
    run_train(state, step, batches, steps=5,
              watchdog=StepWatchdog(factor=100.0), obs=obs, log=lambda s: s)
    snap = obs.snapshot()
    assert snap["histograms"]["train_step_s"]["count"] == 5
    assert snap["gauges"]["train_opt_state_bytes"] == 4 * 4 * 4


def test_profile_scope_and_profiled_ticks(tmp_path):
    """Scopes are transparent; a capture holds the spans of exactly the
    ticks it covered, on the profiler's clock, with their attrs (those
    set after the span opened too) as the event's stats."""
    from jax.profiler import ProfileData

    from repro.obs.profile import (ProfiledTicks, annotate, phase_counters,
                                   scope, span)

    @scope("repro.test_op")
    def f(x):
        return x + 1

    assert int(f(jnp.int32(1))) == 2  # named_scope is transparent
    with annotate("tick"):  # no-op outside a capture
        pass

    obs = MetricsRegistry()
    admit = phase_counters(obs, "test", "admit")
    pt = ProfiledTicks(str(tmp_path / "prof"), n=2)
    for i in range(4):
        with span(admit, "serve.admit", request_id=i) as s:
            jnp.zeros((2,)).block_until_ready()
            s.set_metadata(kind="cold")
        pt.tick()
    pt.stop()  # idempotent after auto-stop at n ticks
    files = list((tmp_path / "prof").rglob("*.xplane.pb"))
    assert files, "no profiler output"
    events = [e for pl in ProfileData.from_file(str(files[0])).planes
              for ln in pl.lines for e in ln.events
              if e.name == "serve.admit"]
    assert sorted(dict(e.stats)["request_id"] for e in events) == [0, 1]
    assert all(dict(e.stats)["kind"] == "cold" for e in events)
    assert obs.counter("serve_phase_calls_total", sched="test",
                       phase="admit").value == 4


_DECODE_SCOPES = ("repro.kv_write", "repro.kv_gather", "repro.attn_core",
                  "repro.hadamard_adapter", "repro.mlp", "repro.lm_head")


def _lowered(program):
    """Lowered text (with op names) of one of the model's programs at
    test size: the paged decode step, a prefill, a train step."""
    from repro.common.types import OptimCfg
    from repro.core import peft
    from repro.train.steps import build_train_step, make_state

    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    params = M.init_params(KEY, cfg)
    if program == "decode":
        # five pages of 4: a 20-entry view, padded to kv chunks of 8
        pool = M.init_paged_pool(cfg, 9, 4, None)
        low = jax.jit(lambda p, pool, t, pos, tbl: M.decode_lm_paged(
            p, cfg, pool, t, pos, tbl)).lower(
            params, pool, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 5), jnp.int32))
    elif program == "prefill":
        low = jax.jit(lambda p, t: M.prefill_lm(p, cfg, t, cache_len=16)
                      ).lower(params, jnp.zeros((1, 16), jnp.int32))
    else:
        ocfg = OptimCfg()
        state = make_state(KEY, cfg, peft.strategy("hadamard"), ocfg,
                           params=params)
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "labels": jnp.zeros((2, 16), jnp.int32)}
        low = jax.jit(build_train_step(cfg, ocfg)).lower(state, batch)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("program,scopes", [
    ("decode", _DECODE_SCOPES),
    ("prefill", _DECODE_SCOPES[2:]),
    ("train", _DECODE_SCOPES[2:]),
])
def test_model_scopes_name_the_step_parts(program, scopes):
    text = _lowered(program)
    for name in scopes:  # as `repro.x/op`, or `jvp(repro.x)/op` under grad
        assert re.search(re.escape(name) + r"\)*/", text), (program, name)
    if program != "decode":  # no cache write or gather outside decode
        assert "repro.kv_write" not in text


def test_span_counts_seconds_and_calls():
    import time

    from repro.obs.profile import phase_counters, span

    obs = MetricsRegistry()
    emit = phase_counters(obs, "paged", "emit")
    for _ in range(3):
        with span(emit, "serve.emit") as s:
            time.sleep(0.002)
        assert s.seconds >= 0.002
    snap = obs.snapshot()["counters"]
    assert snap["serve_phase_calls_total{phase=emit,sched=paged}"] == 3
    assert snap["serve_phase_seconds_total{phase=emit,sched=paged}"] >= 0.006

    off = MetricsRegistry(enabled=False)
    with span(phase_counters(off, "paged", "emit"), "serve.emit") as s:
        time.sleep(0.001)
    assert s.seconds >= 0.001  # the reading is still taken
    assert off.snapshot()["counters"] == {}
    assert off.counter("serve_phase_calls_total", sched="paged",
                       phase="emit").value == 0


# ---------------------------------------------------------------------------
# host phases of the serving tick
# ---------------------------------------------------------------------------


_PHASE_FLAVOURS = [
    dict(num_slots=2, max_len=32),
    dict(num_slots=2, max_len=32, paged=True, page_size=8),
    dict(num_slots=2, max_len=32, spec_k=2),
    dict(num_slots=2, max_len=32, paged=True, page_size=8, spec_k=2),
]


def _phase_totals(obs, kind):
    c = obs.snapshot()["counters"]
    sec = {p: c[f"serve_phase_seconds_total{{phase={p},sched={kind}}}"]
           for p in ("tick", "admit", "prefill_wait", "plan", "decode",
                     "sample_wait", "emit")}
    calls = c[f"serve_phase_calls_total{{phase=tick,sched={kind}}}"]
    return sec, calls


@pytest.mark.parametrize("serve_kw", _PHASE_FLAVOURS,
                         ids=["contiguous", "paged", "spec", "spec_paged"])
def test_tick_phases_cover_the_tick(serve_kw):
    """The disjoint phases (admit, plan, decode, sample_wait, emit) fill
    90-100% of `serve.tick`, prefill_wait lies inside admit, and every
    step() call - idle ones too - is one tick span."""
    cfg, eng = _world()
    obs = MetricsRegistry()
    sched = make_scheduler(eng, ServingConfig(**serve_kw), obs=obs)
    rs = np.random.RandomState(11)
    sched.run([Request(prompt=rs.randint(0, 97, size=(6,)),
                       max_new_tokens=2, task_id=i % 2) for i in range(2)])
    obs.reset()  # compiles are behind us: measure a warm serve
    sched = make_scheduler(eng, ServingConfig(**serve_kw), obs=obs)
    for i in range(5):
        sched.submit(Request(prompt=rs.randint(0, 97, size=(6,)),
                             max_new_tokens=int(rs.randint(2, 7)),
                             task_id=i % 2))
    steps = 0
    while sched.pending or sched.active:
        sched.step()
        steps += 1
    sched.step()  # an idle tick
    steps += 1
    sec, calls = _phase_totals(obs, sched._sched_kind)
    assert calls == steps
    parts = sum(sec[p] for p in ("admit", "plan", "decode", "sample_wait",
                                 "emit"))
    assert 0.9 * sec["tick"] <= parts <= sec["tick"]
    assert 0 < sec["prefill_wait"] <= sec["admit"]
    hist = obs.snapshot()["histograms"][
        f"serve_tick_s{{sched={sched._sched_kind}}}"]
    assert hist["count"] == steps
    assert hist["sum"] == pytest.approx(sec["tick"])


def test_slow_tick_event_names_the_phase():
    """A stream callback that sleeps on one tick makes that tick slow:
    exactly one `slow_tick` event, its time in `emit`."""
    import time

    cfg, eng = _world()
    obs = MetricsRegistry()
    sleep_at = {"n": 0}

    def stream(rid, tok):
        sleep_at["n"] += 1
        if sleep_at["n"] == 30:
            time.sleep(0.5)

    kw = dict(num_slots=2, max_len=48, paged=True, page_size=8)
    reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=24,
                    task_id=i % 2) for i in range(2)]
    make_scheduler(eng, ServingConfig(**kw)).run(reqs)  # compiles
    sched = make_scheduler(eng, ServingConfig(**kw, stream=stream), obs=obs)
    sched.run(reqs)
    # a busy test machine may flag a short stall elsewhere: the sleep
    # itself must land in exactly one event
    events = [e for e in obs.events_of("slow_tick") if e["seconds"] >= 0.5]
    assert len(events) == 1, obs.events_of("slow_tick")
    ev = events[0]
    assert ev["admissions"] == 0
    assert max(ev["phases"], key=ev["phases"].get) == "emit"
    assert ev["phases"]["emit"] >= 0.5
    assert obs.snapshot()["counters"][
        "serve_slow_ticks_total{sched=paged}"] == len(
            obs.events_of("slow_tick"))


def test_prefill_compiles_are_counted_not_warned(capsys):
    """A prefill shape first seen mid-serve is counted in
    `serve_prefill_compiles_total`, with no retrace event or warning."""
    cfg = tiny_cfg(adapter=AdapterCfg(kind="hadamard"))
    eng = ServeEngine(cfg, M.init_params(KEY, cfg))
    obs = MetricsRegistry()
    sched = make_scheduler(eng, ServingConfig(num_slots=2, max_len=32),
                           obs=obs)
    key = "serve_prefill_compiles_total{sched=contiguous}"
    sched.run([Request(prompt=np.arange(n, dtype=np.int32),
                       max_new_tokens=3) for n in (4, 5, 4)])
    assert obs.snapshot()["counters"][key] == 2  # lengths 4 and 5
    sched.run([Request(prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3)])
    assert obs.snapshot()["counters"][key] == 2  # no new shape
    assert obs.events_of("retrace") == []
    assert "recompiled" not in capsys.readouterr().err
