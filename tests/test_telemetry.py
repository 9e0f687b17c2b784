"""Flight-recorder telemetry: recorder semantics, Chrome-trace schema,
oracle reconciliation, router drop-log bounds, the BENCH-summary plumbing,
and the round's named scopes (read as ``bench/scopes.py`` reads them) and
profiler-clock spans — single-process tests plus the launchers for the
multi-device workers (_telemetry_worker.py — 8 forced host devices;
_scope_worker.py — 2)."""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from repro import telemetry
from repro.core.relation import Relation
from repro.core.schedule import ring
from repro.groundseg import routing

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------- recorder core
def test_counters_default_on_spans_off():
    rec = telemetry.Recorder()
    rec.counter("a")
    rec.counter("a", 2)
    rec.counter("b", 0.5)
    assert rec.counters == {"a": 3, "b": 0.5}
    # spans/events are no-ops without tracing — nothing recorded, and the
    # span context yields None (no args dict is built)
    with rec.span("s", cat="x", k=1) as sp:
        assert sp is None
    rec.event("e", cat="x", k=2)
    assert rec.spans == [] and rec.events == []


def test_tracing_records_spans_and_events():
    rec = telemetry.Recorder(tracing=True)
    with rec.span("outer", cat="test", fixed=1) as sp:
        sp["result"] = 42
        rec.event("mark", cat="test", at="inside")
    assert len(rec.spans) == 1 and len(rec.events) == 1
    s = rec.spans[0]
    assert s.name == "outer" and s.args == {"fixed": 1, "result": 42}
    assert s.dur_us >= 0 and s.t_start_us >= 0
    e = rec.events[0]
    assert s.t_start_us <= e.t_us <= s.t_start_us + s.dur_us


def test_buffers_bounded_with_drop_counters(monkeypatch):
    monkeypatch.setattr(telemetry.recorder, "MAX_SPANS", 2)
    monkeypatch.setattr(telemetry.recorder, "MAX_EVENTS", 2)
    rec = telemetry.Recorder(tracing=True)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
        rec.event(f"e{i}")
    assert len(rec.spans) == 2 and len(rec.events) == 2
    assert rec.counters["telemetry.dropped_spans"] == 3
    assert rec.counters["telemetry.dropped_events"] == 3
    # drop-OLDEST (dropped_log_max idiom): the tail of a long run survives,
    # which is the part a post-mortem wants
    assert [s.name for s in rec.spans] == ["s3", "s4"]
    assert [e.name for e in rec.events] == ["e3", "e4"]


def test_buffer_bounds_per_recorder_ctor_args():
    rec = telemetry.Recorder(tracing=True, max_spans=3, max_events=1)
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
        rec.event(f"e{i}")
    assert [s.name for s in rec.spans] == ["s3", "s4", "s5"]
    assert [e.name for e in rec.events] == ["e5"]
    assert rec.counters["telemetry.dropped_spans"] == 3
    assert rec.counters["telemetry.dropped_events"] == 5


def test_record_scope_isolation_and_inheritance():
    outer = telemetry.get_recorder()
    outer_counters = dict(outer.counters)
    with telemetry.record_scope(tracing=True) as rec:
        assert telemetry.get_recorder() is rec
        assert telemetry.tracing_enabled()
        rec.counter("scoped", 7)
        # nested scope inherits flags from the ENCLOSING recorder
        with telemetry.record_scope() as inner:
            assert inner.tracing
            inner.counter("inner_only")
        assert "inner_only" not in rec.counters
    assert telemetry.get_recorder() is outer
    assert outer.counters == outer_counters  # nothing leaked out


def test_pop_counters_prefix_reset():
    rec = telemetry.Recorder()
    rec.counter("fused.spec_cache.hits", 3)
    rec.counter("fused.spec_cache.misses", 1)
    rec.counter("other", 9)
    popped = rec.pop_counters("fused.spec_cache")
    assert popped == {"fused.spec_cache.hits": 3, "fused.spec_cache.misses": 1}
    assert rec.counters == {"other": 9}


def test_span_stats_aggregates():
    rec = telemetry.Recorder(tracing=True)
    for _ in range(3):
        with rec.span("work"):
            pass
    stats = rec.span_stats()
    assert stats["work"]["count"] == 3
    assert stats["work"]["total_ms"] >= 0
    assert stats["work"]["max_ms"] <= stats["work"]["total_ms"]
    assert stats["work"]["mean_ms"] == pytest.approx(
        stats["work"]["total_ms"] / 3
    )


def test_spec_cache_counters_scoped_per_run():
    # the old module-global _SPEC_CACHE_STATS leaked across runs; recorder
    # scopes must isolate the counts
    import jax.numpy as jnp

    from repro.core import fused

    fused.clear_spec_cache()
    tree = {"a": jnp.zeros((3,))}
    with telemetry.record_scope():
        fused.cached_spec(tree, block=32)
        fused.cached_spec(tree, block=32)
        inside = fused.spec_cache_stats()
        assert inside["misses"] == 1 and inside["hits"] == 1
    outside = fused.spec_cache_stats()
    assert outside["hits"] == 0 and outside["misses"] == 0
    fused.clear_spec_cache()


# ------------------------------------------------------- chrome trace schema
def _trace_roundtrip(rec):
    """Serialize + reparse, as a trace viewer would."""
    return json.loads(json.dumps(telemetry.chrome_trace(rec)))


def test_chrome_trace_schema_valid_and_monotonic(tmp_path):
    with telemetry.record_scope(tracing=True) as rec:
        for i in range(4):
            with rec.span(f"round{i}", cat="slot", round=i):
                rec.event("mid", cat="slot", round=i)
        rec.counter("rounds", 4)
        doc = _trace_roundtrip(rec)
        out = telemetry.write_trace(tmp_path / "trace.json", rec)
    assert json.loads(out.read_text()) == doc
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "otherData"}
    evs = doc["traceEvents"]
    assert evs, "trace must not be empty"
    # schema: every event has the required Chrome-trace keys per phase
    last_ts = None
    for ev in evs:
        assert ev["ph"] in ("M", "X", "i", "C")
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
            assert isinstance(ev["tid"], int)
        if ev["ph"] == "i":
            assert ev["s"] == "t"
        # timestamps are sorted (monotonic) across the exported list
        if last_ts is not None:
            assert ev["ts"] >= last_ts
        last_ts = ev["ts"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    assert sum(ev["ph"] == "X" for ev in evs) == 4
    assert sum(ev["ph"] == "i" for ev in evs) == 4
    counter_evs = [ev for ev in evs if ev["ph"] == "C"]
    assert {ev["name"] for ev in counter_evs} >= {"rounds"}
    assert doc["otherData"]["counters"]["rounds"] == 4


def test_metrics_snapshot_shape(tmp_path):
    with telemetry.record_scope(tracing=True) as rec:
        with rec.span("w"):
            pass
        rec.counter("c", 2)
        snap = telemetry.metrics_snapshot(rec)
        out = telemetry.write_metrics(tmp_path / "m.json", rec)
    assert json.loads(out.read_text()) == json.loads(json.dumps(snap))
    assert snap["counters"] == {"c": 2}
    assert snap["n_spans"] == 1 and snap["spans"]["w"]["count"] == 1


def test_trace_scope_writes_on_exit(tmp_path):
    path = tmp_path / "t.json"
    with telemetry.trace_scope(path) as rec:
        assert rec.tracing
        with rec.span("s"):
            pass
    doc = json.loads(path.read_text())
    assert any(ev["ph"] == "X" for ev in doc["traceEvents"])
    # no path -> no tracing, no file
    with telemetry.trace_scope(None) as rec:
        assert not rec.tracing


# ----------------------------------------------------------- reconciliation
FAKE_HLO = "\n".join(
    [
        "%p0 = f32[8]{0} parameter(0)",
        "%cp1 = f32[8]{0} collective-permute(%p0), source_target_pairs={{0,1}}",
        "%cp2 = f32[8]{0} collective-permute(%cp1), source_target_pairs={{1,0}}",
        "%ar = f32[8]{0} all-reduce(%cp2), to_apply=%add",
    ]
)


def test_compiled_collective_counts_from_hlo_text():
    counts = telemetry.compiled_collective_counts(FAKE_HLO)
    assert counts == {"collective-permute": 2, "all-reduce": 1}


def test_compare_only_judges_oracle_kinds():
    rep = telemetry.compare(
        {"collective-permute": 2},
        {"collective-permute": 2, "all-gather": 5},
        context="x",
    )
    assert rep.ok and rep.mismatches == ()
    bad = telemetry.compare(
        {"collective-permute": 3}, {"collective-permute": 2}, context="x"
    )
    assert not bad.ok and bad.mismatches == ("collective-permute",)
    assert "expected 3" in bad.describe()


def test_check_compiled_strict_raises_and_counts():
    with telemetry.record_scope(tracing=True) as rec:
        rep = telemetry.check_compiled(
            FAKE_HLO,
            {"collective-permute": 2, "all-reduce": 1},
            context="good",
        )
        assert rep.ok
        with pytest.raises(telemetry.ReconciliationError):
            telemetry.check_compiled(
                FAKE_HLO, {"collective-permute": 99}, context="bad"
            )
        rep2 = telemetry.check_compiled(
            FAKE_HLO, {"collective-permute": 99}, context="bad", strict=False
        )
        assert not rep2.ok
        assert rec.counters["reconcile.checked"] == 3
        assert rec.counters["reconcile.mismatched"] == 2
        assert [e.args["ok"] for e in rec.events if e.name == "reconcile"] == [
            True,
            False,
            False,
        ]


def test_expected_tdm_collectives_math():
    from repro.core import tdm

    rel = ring(8)
    m = len(tdm.edge_coloring(rel))
    assert telemetry.expected_tdm_collectives(rel, 1) == {
        "collective-permute": m
    }
    assert telemetry.expected_tdm_collectives(rel, 2) == {
        "collective-permute": 2 * m
    }
    # int8 ships payload + scales (2 per matching); fused top-k packs
    # values + indices into ONE int32 payload (1 per matching)
    assert telemetry.expected_tdm_collectives(rel, 1, compression="int8") == {
        "collective-permute": 2 * m
    }
    assert telemetry.expected_tdm_collectives(rel, 1, compression="topk") == {
        "collective-permute": m
    }
    assert telemetry.expected_tdm_collectives(rel, 3, compression="topk") == {
        "collective-permute": 3 * m
    }
    empty = Relation.empty(range(4))
    assert telemetry.expected_tdm_collectives(empty, 3) == {
        "collective-permute": 0
    }


def test_expected_hierarchical_collectives_math():
    from repro.core import tdm

    intra = Relation.clique(list(range(4)))
    inter = ring(2)
    mi = len(tdm.edge_coloring(intra))
    mo = len(tdm.edge_coloring(inter))
    assert telemetry.expected_hierarchical_collectives(intra, inter, 1) == {
        "collective-permute": mi + mo
    }
    assert telemetry.expected_hierarchical_collectives(
        intra, inter, 2, compression="int8"
    ) == {"collective-permute": 2 * 2 * (mi + mo)}
    with pytest.raises(ValueError):
        telemetry.expected_hierarchical_collectives(
            intra, inter, 1, compression="topk"
        )


def test_round_fn_cache_oracle_covers_mixed_dtype_compressed():
    """RoundFnCache.expected_collectives no longer skips mixed-dtype
    compressed params: the per-bucket count is uniform, so every fused
    getMeas TDM config gets a real oracle (reconcile never counts a skip)."""
    import ml_dtypes
    import numpy as np

    from repro.core import tdm
    from repro.launch import fl_train

    rel = ring(8)
    m = len(tdm.edge_coloring(rel))
    state = {
        "params": {
            "w": np.zeros((4, 4), np.float32),
            "h": np.zeros((8,), ml_dtypes.bfloat16),
            "b": np.zeros((3,), np.float32),
        }
    }
    per = {"none": 1, "int8": 2, "topk": 1}
    for comp, p in per.items():
        fl_cfg = fl_train.FLConfig(mode="tdm", compression=comp, fused=True)
        cache = fl_train.RoundFnCache(None, None, None, 8, fl_cfg)
        exp = cache.expected_collectives(rel, state)
        assert exp == {"collective-permute": p * m * 2}, (comp, exp)
    # non-fused / get1meas configs still have no proven oracle
    for fl_cfg in (
        fl_train.FLConfig(mode="tdm", fused=False),
        fl_train.FLConfig(mode="tdm", comm="get1meas"),
        fl_train.FLConfig(mode="centralized"),
    ):
        cache = fl_train.RoundFnCache(None, None, None, 8, fl_cfg)
        assert cache.expected_collectives(rel, state) is None


# ------------------------------------------------- router dropped_log bounds
def _isolated_slots(n=4):
    # satellite 0 never reaches the sink (3); 1 and 2 do
    return [Relation.from_edges([(1, 3), (2, 3)], nodes=range(n))]


def test_dropped_log_exact_ages_at_horizon():
    K = 2
    router = routing.MultiWindowRouter(4, [3], max_staleness_windows=K)
    slots = _isolated_slots()
    for _ in range(K + 1):
        wp = router.plan_window(slots)
        assert not wp.dropped  # ages 0..K are all within the horizon
    assert router.pending()[0] == K
    wp = router.plan_window(slots)  # age would become K+1 -> drop
    assert wp.dropped == {0: K + 1}
    assert router.dropped_total == 1
    assert [
        (d.source, d.age, d.window) for d in router.dropped_log
    ] == [(0, K + 1, K + 1)]
    # the dropping satellite re-snapshots the SAME window
    assert 0 in wp.injected and wp.ages[0] == 0


def test_dropped_log_growth_bound_over_many_windows():
    cap = 5
    router = routing.MultiWindowRouter(
        4, [3], max_staleness_windows=0, dropped_log_max=cap
    )
    slots = _isolated_slots()
    windows = 20
    for _ in range(windows):
        router.plan_window(slots)
    # satellite 0 drops once per window after the first
    assert router.dropped_total == windows - 1
    assert len(router.dropped_log) == cap
    # the retained entries are the MOST RECENT drops, in order
    assert [d.window for d in router.dropped_log] == list(
        range(windows - cap, windows)
    )
    assert all(d.age == 1 and d.source == 0 for d in router.dropped_log)


def test_dropped_log_reset_contract():
    router = routing.MultiWindowRouter(4, [3], max_staleness_windows=0)
    slots = _isolated_slots()
    for _ in range(3):
        router.plan_window(slots)
    assert router.dropped_total == 2 and len(router.dropped_log) == 2
    drained = router.reset_dropped_log()
    assert len(drained) == 2
    assert router.dropped_log == []
    assert router.dropped_total == 2  # lifetime count survives the drain
    router.plan_window(slots)
    assert len(router.dropped_log) == 1 and router.dropped_total == 3


def test_dropped_log_max_validation():
    with pytest.raises(ValueError):
        routing.MultiWindowRouter(4, [3], dropped_log_max=-1)


# -------------------------------------------------- optimizer race outcomes
def test_optimizer_race_telemetry():
    import random

    from proptest import st_contact_plan
    from repro.constellation.optimizer import optimize_schedule

    plan = st_contact_plan(max_nodes=8, max_steps=3, p=0.6).draw(
        random.Random(0)
    )
    with telemetry.record_scope(tracing=True) as rec:
        res = optimize_schedule(plan, antennas=2, payload_bytes=1 << 16)
        assert rec.counters["optimizer.races"] == 1
        assert rec.counters[f"optimizer.winner.{res.strategy}"] == 1
        races = [e for e in rec.events if e.name == "optimizer.race"]
        assert len(races) == 1
        args = races[0].args
        assert args["winner"] == res.strategy
        assert set(args["costs_s"]) == set(res.costs)
        assert args["costs_s"][res.strategy] == res.chosen.time_s
        # the optimizer provably never loses to greedy — the recorded race
        # outcome must agree
        assert args["speedup"] >= 1.0 - 1e-12
        assert args["margin_vs_greedy_s"] >= -1e-9


# --------------------------------------------- BENCH summaries + trend files
def test_run_py_parse_and_summary(tmp_path):
    from benchmarks import run as bench_run

    lines = [
        "noise",
        'BENCH {"bench": "x", "metric": 1.0}',
        "BENCH not-json",
        'TELEMETRY {"fl.rounds": 3}',
    ]
    rows, counters = bench_run._parse_lines(lines)
    assert rows == [{"bench": "x", "metric": 1.0}]
    assert counters == {"fl.rounds": 3}
    bench_run._write_summary(tmp_path, "x", rows, counters)
    doc = json.loads((tmp_path / "BENCH_x.json").read_text())
    assert doc == {"bench": "x", "rows": rows, "telemetry": counters}


def test_check_regression_reads_summaries_and_dirs(tmp_path):
    from benchmarks import check_regression

    rows = [{"bench": "b", "cell": "c", "permutes": 4}]
    (tmp_path / "BENCH_a.json").write_text(
        json.dumps({"bench": "a", "rows": rows, "telemetry": {}})
    )
    (tmp_path / "plain.json").write_text(json.dumps(rows))
    assert check_regression.load_rows(str(tmp_path / "BENCH_a.json")) == rows
    assert check_regression.load_rows(str(tmp_path / "plain.json")) == rows
    # directory: BENCH_*.json files preferred and concatenated
    assert check_regression.load_rows(str(tmp_path)) == rows
    failures, improvements, checked, _ = check_regression.compare(
        rows, rows, ("permutes",), 0.2
    )
    assert not failures and checked == 1


def test_check_regression_telemetry_diff_direction_agnostic():
    from benchmarks import check_regression

    base = {"fl.permutes": 24.0, "fl.rounds": 4.0, "fl.skipped": 0.0}
    # identical counters: clean
    failures, table = check_regression.compare_telemetry(base, dict(base), 0.2)
    assert failures == []
    assert all(r[6] == "ok" for r in table)
    # drift UP and drift DOWN both fail (schedule changed either way)
    up = dict(base, **{"fl.permutes": 48.0})
    down = dict(base, **{"fl.permutes": 12.0})
    for run in (up, down):
        failures, table = check_regression.compare_telemetry(base, run, 0.2)
        assert len(failures) == 1 and "fl.permutes" in failures[0]
        assert any(r[2] == "fl.permutes" and r[6] == "DRIFTED" for r in table)
    # within threshold: clean
    failures, _ = check_regression.compare_telemetry(
        base, dict(base, **{"fl.permutes": 26.0}), 0.2
    )
    assert failures == []
    # zero baseline -> nonzero is drift; missing counter fails; run-only
    # counters are reported as new but don't fail
    failures, table = check_regression.compare_telemetry(
        base, {"fl.permutes": 24.0, "fl.skipped": 2.0, "extra": 1.0}, 0.2
    )
    msgs = "\n".join(failures)
    assert "fl.skipped" in msgs and "zero baseline" in msgs
    assert "fl.rounds" in msgs and "missing" in msgs
    assert len(failures) == 2
    assert any(r[2] == "extra" and r[6] == "new" for r in table)
    # prefix filter gates which counters can fail
    failures, _ = check_regression.compare_telemetry(
        base, {"fl.permutes": 999.0, "fl.rounds": 4.0, "fl.skipped": 0.0},
        0.2, prefix="fl.rounds",
    )
    assert failures == []


def test_check_regression_telemetry_loading_and_exit_code(tmp_path, capsys):
    from benchmarks import check_regression

    rows = [{"bench": "b", "cell": "c", "permutes": 4}]
    base = tmp_path / "base"
    run = tmp_path / "run"
    base.mkdir()
    run.mkdir()
    (base / "BENCH_a.json").write_text(json.dumps(
        {"bench": "a", "rows": rows, "telemetry": {"fl.permutes": 24}}
    ))
    (base / "BENCH_b.json").write_text(json.dumps(
        {"bench": "b", "rows": [], "telemetry": {"fl.permutes": 6, "x": 1}}
    ))
    # directory load sums counters across summaries
    assert check_regression.load_telemetry(str(base)) == {
        "fl.permutes": 30.0, "x": 1.0
    }
    # plain row-list files carry no counters
    (tmp_path / "plain.json").write_text(json.dumps(rows))
    assert check_regression.load_telemetry(str(tmp_path / "plain.json")) == {}

    # injected counter drift fails the job end-to-end (exit code 1)
    (run / "BENCH_a.json").write_text(json.dumps(
        {"bench": "a", "rows": rows, "telemetry": {"fl.permutes": 24}}
    ))
    (run / "BENCH_b.json").write_text(json.dumps(
        {"bench": "b", "rows": [], "telemetry": {"fl.permutes": 18, "x": 1}}
    ))
    rc = check_regression.main(
        ["--run", str(run), "--baseline", str(base)]
    )
    out = capsys.readouterr().out
    assert rc == 1 and "fl.permutes" in out and "drifted" in out
    # same run with --no-telemetry (rows match): clean
    rc = check_regression.main(
        ["--run", str(run), "--baseline", str(base), "--no-telemetry"]
    )
    capsys.readouterr()
    assert rc == 0


# ------------------------------------------- metrics registry (ISSUE 9)
def test_histogram_fixed_buckets_quantiles_and_summary():
    from repro.telemetry import metrics

    h = metrics.Histogram(bounds=(1, 2, 4, 8))
    for v in (0.5, 1.5, 3, 3, 7, 100):
        h.observe(v)
    assert h.count == 6 and h.total == pytest.approx(115.0)
    assert h.vmin == 0.5 and h.vmax == 100
    # cumulative counts are monotone and end at the observation count
    cum = h.cumulative()
    assert cum == sorted(cum) and cum[-1] == h.count
    s = h.summary()
    assert set(s) == {"count", "sum", "mean", "min", "max", "p50", "p90",
                      "p99"}
    assert s["mean"] == pytest.approx(115.0 / 6)
    # quantiles interpolate within buckets and clamp to observed extremes
    assert h.quantile(0.0) == 0.5
    assert h.quantile(1.0) == 100
    assert 1 <= h.quantile(0.5) <= 4
    # overflow bucket resolves to the observed max, not infinity
    assert h.quantile(0.99) <= 100


def test_metrics_registry_lands_on_active_recorder():
    from repro.telemetry import metrics

    with telemetry.record_scope() as rec:
        metrics.set_gauge("g.x", 0.25)
        metrics.ratio_gauge("g.rate", 3, 4)
        metrics.ratio_gauge("g.skipped", 1, 0)   # zero denom: no sample
        for v in (1, 2, 40):
            metrics.observe("q.depth", v, buckets=metrics.COUNT_BUCKETS)
        assert rec.gauges == {"g.x": 0.25, "g.rate": 0.75}
        assert metrics.get_gauge("g.rate") == 0.75
        assert metrics.get_histogram("q.depth").count == 3
        snap = telemetry.metrics_snapshot(rec)
    assert snap["gauges"]["g.rate"] == 0.75
    assert snap["histograms"]["q.depth"]["count"] == 3
    assert snap["histograms"]["q.depth"]["max"] == 40
    # scope exit: nothing leaked onto the enclosing recorder
    assert "g.x" not in telemetry.get_recorder().gauges


def test_prometheus_text_exposition(tmp_path):
    from repro.telemetry import metrics

    with telemetry.record_scope() as rec:
        rec.counter("fl.rounds", 4)
        metrics.set_gauge("cache.hit_rate", 0.5)
        for v in (0.5, 1.5, 3):
            metrics.observe("lat", v, buckets=(1, 2, 4))
        text = telemetry.prometheus_text(rec)
        out = telemetry.write_prometheus(tmp_path / "m.prom", rec)
    assert out.read_text() == text
    lines = text.splitlines()
    assert "# TYPE fl_rounds counter" in lines and "fl_rounds 4" in lines
    assert "# TYPE cache_hit_rate gauge" in lines
    assert "cache_hit_rate 0.5" in lines
    # cumulative buckets: le=1 -> 1 obs, le=2 -> 2, le=4 -> 3, +Inf == count
    assert 'lat_bucket{le="1"} 1' in lines
    assert 'lat_bucket{le="2"} 2' in lines
    assert 'lat_bucket{le="4"} 3' in lines
    assert 'lat_bucket{le="+Inf"} 3' in lines
    assert "lat_sum 5" in lines and "lat_count 3" in lines


def test_chrome_trace_counter_samples_after_spans():
    """Counter ``"C"`` samples ride at the trace end: every one sorts at or
    after the last span/event timestamp, so the Perfetto counter track
    shows the final values, and names stay in sorted order."""
    with telemetry.record_scope(tracing=True) as rec:
        with rec.span("w"):
            rec.event("mark")
        rec.counter("b.count", 2)
        rec.counter("a.count", 1)
        doc = json.loads(json.dumps(telemetry.chrome_trace(rec)))
    evs = doc["traceEvents"]
    t_busy = max(
        ev["ts"] + ev.get("dur", 0.0) for ev in evs if ev["ph"] in ("X", "i")
    )
    counter_evs = [ev for ev in evs if ev["ph"] == "C"]
    assert [ev["name"] for ev in counter_evs] == ["a.count", "b.count"]
    assert all(ev["ts"] >= t_busy for ev in counter_evs)
    # and the trailing suffix of the sorted list is exactly the counters
    assert [ev["ph"] for ev in evs[-len(counter_evs):]] == ["C", "C"]
    assert doc["otherData"]["counters"] == {"a.count": 1, "b.count": 2}
    assert doc["otherData"]["gauges"] == {}


def test_pop_counters_and_snapshot_under_nested_scopes():
    """pop_counters/counters_snapshot prefix semantics: prefix filtering is
    plain startswith on the ACTIVE recorder, and nested scopes neither see
    nor disturb the enclosing recorder's counters."""
    with telemetry.record_scope() as outer:
        outer.counter("sub.a", 1)
        outer.counter("sub.b", 2)
        outer.counter("other", 9)
        with telemetry.record_scope() as inner:
            inner.counter("sub.a", 100)
            # snapshot reads the innermost scope only
            assert telemetry.counters_snapshot() == {"sub.a": 100}
            assert telemetry.counters_snapshot("sub.") == {"sub.a": 100}
            assert inner.pop_counters("sub.") == {"sub.a": 100}
            assert inner.counters == {}
        # inner scope popped its own counters; outer's are untouched
        assert telemetry.counters_snapshot("sub.") == {"sub.a": 1, "sub.b": 2}
        popped = outer.pop_counters("sub.")
        assert popped == {"sub.a": 1, "sub.b": 2}
        assert telemetry.counters_snapshot() == {"other": 9}


# ------------------------------------- check_regression silent-pass guards
def test_check_regression_fails_on_zero_row_summaries(tmp_path, capsys):
    from benchmarks import check_regression

    rows = [{"bench": "b", "cell": "c", "permutes": 4}]
    good = tmp_path / "good.json"
    empty = tmp_path / "empty.json"
    good.write_text(json.dumps(rows))
    empty.write_text(json.dumps({"bench": "b", "rows": [],
                                 "telemetry": {}}))
    # empty RUN fails (was: baseline rows each fail row-match — keep that
    # too — but the guard names the real cause)
    rc = check_regression.main(
        ["--run", str(empty), "--baseline", str(good)]
    )
    out = capsys.readouterr().out
    assert rc == 1 and "zero BENCH rows" in out
    # empty BASELINE fails (was: nothing to iterate -> exit 0, silent pass)
    rc = check_regression.main(
        ["--run", str(good), "--baseline", str(empty)]
    )
    out = capsys.readouterr().out
    assert rc == 1 and "zero BENCH rows" in out


def test_check_regression_fails_when_nothing_compared(tmp_path, capsys):
    from benchmarks import check_regression

    # rows match but carry NONE of the default metrics: the old gate
    # compared zero cells and exited 0
    rows = [{"bench": "b", "cell": "c", "wall_ms": 1.0}]
    base = tmp_path / "base.json"
    run = tmp_path / "run.json"
    base.write_text(json.dumps(rows))
    run.write_text(json.dumps(rows))
    rc = check_regression.main(["--run", str(run), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 1 and "zero metric cells compared" in out
    # an explicitly requested metric that matches no baseline row fails
    # (typo protection); the same request naming a real metric passes
    rc = check_regression.main(
        ["--run", str(run), "--baseline", str(base), "--metrics", "wall_msx"]
    )
    out = capsys.readouterr().out
    assert rc == 1 and "matches no baseline row" in out
    rc = check_regression.main(
        ["--run", str(run), "--baseline", str(base), "--metrics", "wall_ms"]
    )
    capsys.readouterr()
    assert rc == 0


# ------------------------------------- named scopes and profiler-clock spans
# compiler-made fusions that carry no op_name of the program: a lone
# instruction XLA wraps (``wrapped_*``), and the layout copies, bitcasts
# and bf16 converts the CPU backend inserts
_UNSCOPED_OK = re.compile(
    r"^(wrapped_[\w-]+|((convert|copy|bitcast|transpose)_)+fusion)(\.\d+)?$"
)


def _carried_scopes(hlo_text):
    """(instruction, scope, backward) of every fusion, custom-call and
    collective-permute, read from its own op_name, or for a fusion without
    one, from its fused instructions' op_names."""
    from bench import scopes

    by_comp, comp = {}, None
    for line in hlo_text.splitlines():
        m = scopes._COMPUTATION.match(line)
        if m:
            comp = by_comp.setdefault(m["name"], [])
        elif comp is not None and 'op_name="' in line:
            op_name = line.split('op_name="')[1].split('"')[0]
            comp.append(scopes.parse_op_name(op_name))
    out = []
    for line in hlo_text.splitlines():
        m = scopes._INSTR.match(line)
        if m is None or not (
            m["op"] in ("fusion", "custom-call")
            or m["op"].startswith("collective-permute")
        ):
            continue
        found = [
            scopes.parse_op_name(x.split('"')[0])
            for x in line.split('op_name="')[1:]
        ]
        if m["op"] == "fusion" and not found:
            found = by_comp[re.search(r"calls=%([\w.\-]+)", line)[1]]
        found = [f for f in found if f[0]]
        out.append((m["name"],) + (found[0] if found else (None, False)))
    return out


@pytest.fixture(scope="module")
def scoped_round():
    """Two int8 rounds of a 2-node ring, traced (``_scope_worker.py``)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT / 'tests'}:" + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_scope_worker.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_round_ops_carry_named_scopes(scoped_round):
    carried = _carried_scopes(scoped_round["hlo"])
    stray = [
        n for n, scope, _ in carried if scope is None and not _UNSCOPED_OK.match(n)
    ]
    assert not stray, stray
    seen = {(scope, backward) for _, scope, backward in carried}
    # forward and backward of the local step, the optimizer, and the int8
    # exchange's phases (on the CPU, dequant_acc and mix fuse into unpack)
    for want in ("optimizer", "pack", "quantize", "permute", "unpack"):
        assert (want, False) in seen, (want, seen)
    assert {("local_step", False), ("local_step", True)} <= seen


def test_traced_rounds_make_no_host_syncs(scoped_round):
    assert scoped_round["syncs"] == 0
    assert scoped_round["spans"].count("fl.round") == 2


def test_traced_rounds_record_remat_saved_bytes(scoped_round):
    """The round's smoke Mamba-2 keeps its five in-projections and its SSD
    output per layer (bf16, 2x32 tokens a node) for the backward, and
    nothing else."""
    from repro.configs import archs

    cfg = archs.smoke_cfg(archs.get("mamba2-780m"))
    mb = cfg.mamba
    per_token = (
        3 * mb.d_inner(cfg.d_model) + 2 * mb.n_groups * mb.d_state + mb.n_heads(cfg.d_model)
    )
    expected = cfg.n_layers * 2 * 32 * per_token * 2
    assert scoped_round["gauges"]["fl.remat_saved_bytes"] == expected


def test_spans_are_profiler_annotations(tmp_path):
    """With tracing off the recorder keeps nothing, yet its span lands on
    the profiler's host plane, inside the annotation that encloses it."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    rec = telemetry.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            with rec.span("fl.round", cat="slot") as sp:
                jax.block_until_ready(jax.numpy.ones(4) * 2)
    finally:
        jax.profiler.stop_trace()
    assert sp is None and rec.spans == []
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = {
        e.name: (e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name in ("bench.window", "fl.round")
    }
    (w0, w1), (r0, r1) = spans["bench.window"], spans["fl.round"]
    assert w0 <= r0 <= r1 <= w1


def test_recorder_imports_without_jax():
    code = (
        "import sys\n"
        "from repro.telemetry import recorder\n"
        "rec = recorder.Recorder(tracing=True)\n"
        "with rec.span('fl.round'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'recorder imported jax'\n"
        "assert [s.name for s in rec.spans] == ['fl.round']\n"
    )
    env = dict(os.environ, PYTHONPATH=f"{ROOT / 'src'}")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# ------------------------------------------------------- multidevice worker
@pytest.mark.slow
def test_telemetry_multidevice_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{ROOT / 'tests'}:" + env.get(
        "PYTHONPATH", ""
    )
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "_telemetry_worker.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=1800,
    )
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, "worker failed"
    assert "ALL-OK" in proc.stdout


def test_hybrid_round_carries_layer_scopes_and_moe_gauges():
    """A round of the hybrid MoE (``nemotron-3-nano-30b-a3b`` at a smoke
    size) carries its kinds of layer as named scopes inside ``local_step``
    (``mamba``, ``attention``, ``moe``, and inside ``moe``: ``router``,
    ``experts``, ``shared_expert``), none of them a scope the benchmark's
    readers attribute time to; with tracing on, compiling it sets the MoE
    gauges (experts held, one MoE layer's buffer bound and the first size of
    its ladder) and the remat gauge."""
    import jax
    import jax.numpy as jnp

    from bench import scopes
    from repro.configs import archs
    from repro.launch import fl_train
    from repro.launch import mesh as mesh_lib
    from repro.models import registry
    from repro.optim import adamw

    cfg = archs.smoke_cfg(archs.get("nemotron-3-nano-30b-a3b"))
    opt_cfg = adamw.OptConfig()
    mesh = mesh_lib.make_mesh((1,), ("data",))
    cache = fl_train.RoundFnCache(cfg, opt_cfg, mesh, 1, fl_train.FLConfig())
    params = jax.eval_shape(
        lambda k: registry.bundle(cfg).init(k)[0], jax.random.PRNGKey(0)
    )
    state = {
        "params": params,
        "opt": jax.eval_shape(lambda p: adamw.init_opt_state(p, opt_cfg), params),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), state)
    B, S = 2, 16
    batch = {k: jax.ShapeDtypeStruct((1, 1, B, S), jnp.int32) for k in ("tokens", "labels")}
    with telemetry.record_scope(tracing=True) as rec:
        fn = cache(Relation.from_edges([], nodes=range(1)), example_args=(state, batch))
        gauges = telemetry.metrics_snapshot(rec)["gauges"]
    hlo = fn.lower(state, batch).compile().as_text()
    # op_names without their transformations: jvp(moe)/router -> moe/router
    stacks = {re.sub(r"[\w-]+\(|\)", "", n) for n in re.findall(r'op_name="([^"]*)"', hlo)}
    for scope in ("mamba", "attention", "moe/router", "moe/experts", "moe/shared_expert"):
        assert any(re.search(f"local_step/(.+/)?{scope}/", s) for s in stacks), scope
    assert not {"mamba", "attention", "moe", "router", "experts", "shared_expert"} & set(
        scopes.SCOPES
    )
    assert gauges["moe.experts_held"] == cfg.moe.n_held == 4
    assert gauges["moe.expert_rows"] == B * S * cfg.moe.top_k
    # 32 tokens: the ladder's first size, one grouped-matmul tile, is past
    # the bound, so the buffer has that one size
    assert gauges["moe.expert_rows_first"] == gauges["moe.expert_rows"]
    assert gauges["fl.remat_saved_bytes"] > 0


def test_moe_step_gauges_of_the_benchmark_cells():
    """The MoE gauges of a step at the benchmark's shapes: solo8k's
    2 x 8192 tokens route into a buffer of at most 98,304 rows (6 of each
    token's assignments, 8 of the 128 experts held), whose first size is
    twice the held experts' even share, 12,288; a model without a MoE has
    none."""
    from bench import harness
    from repro.configs import archs
    from repro.models import moe

    conf, model = harness.load_config("nemotron-3-nano-30b-a3b")
    cfg = model.program_config(conf, archs)
    assert moe.step_gauges(2 * 8192, cfg) == {
        "moe.experts_held": 8, "moe.expert_rows": 98_304, "moe.expert_rows_first": 12_288,
    }
    conf, model = harness.load_config("mamba2-780m")
    assert moe.step_gauges(2 * 512, model.program_config(conf, archs)) == {}
