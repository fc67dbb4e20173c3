"""Host spans and counters (``repro.monitoring.spans``): the in-memory
totals, the GC hook, the ``metrics.prom`` rendering, and the spans the
tuning loop and the serve cycle put on the profiler's clock."""
import gc
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.monitoring import spans
from repro.monitoring.spans import count, span, step

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402


@pytest.fixture
def totals():
    spans.TOTALS.reset()
    yield spans.TOTALS
    spans.TOTALS.reset()


def test_nested_spans_keep_totals_and_max(totals, monkeypatch):
    clock = iter([0.0, 1.0, 3.0, 10.0, 10.5, 20.0])
    gc.disable()            # a collection would read the patched clock too
    try:
        with monkeypatch.context() as m:
            m.setattr(spans.time, "perf_counter", lambda: next(clock))
            with step("unit", 7):
                with span("phase", batch=0):
                    pass
                with span("phase") as sp:
                    sp.set_metadata(bytes=12)
    finally:
        gc.enable()
    assert totals.spans["phase"].count == 2
    assert totals.spans["phase"].seconds == pytest.approx(2.0 + 0.5)
    assert totals.spans["phase"].max_s == pytest.approx(2.0)
    assert totals.spans["unit"].count == 1
    assert totals.spans["unit"].seconds == pytest.approx(20.0)


def test_span_records_even_when_its_body_raises(totals):
    with pytest.raises(KeyError):
        with span("failing"):
            raise KeyError("x")
    assert totals.spans["failing"].count == 1


def test_counters_add(totals):
    count("tune.repacks")
    count("tune.repacks", 2)
    count("tune.pull_bytes", 4096)
    assert totals.counters == {"tune.repacks": 3, "tune.pull_bytes": 4096}


def test_gc_collect_adds_one_span_and_one_collection(totals):
    gc.collect()
    assert totals.spans["python.gc"].count == 1
    assert totals.spans["python.gc"].seconds > 0
    assert totals.counters["python.gc.gen2"] == 1
    # the hook is installed once per process
    assert gc.callbacks.count(spans._gc_callback) == 1


def test_prometheus_text_renders_spans_and_counters(totals):
    totals.add("tune.pack", 0.25)
    totals.add("tune.pack", 0.5)
    count("tune.repacks", 3)
    text = spans.prometheus_text()
    lines = set(text.splitlines())
    assert "# TYPE repro_span_tune_pack_seconds_total counter" in lines
    assert "repro_span_tune_pack_seconds_total 0.75" in lines
    assert "repro_span_tune_pack_count_total 2" in lines
    assert "# TYPE repro_span_tune_pack_max_seconds gauge" in lines
    assert "repro_span_tune_pack_max_seconds 0.5" in lines
    assert "repro_count_tune_repacks_total 3" in lines


# ---------------------------------------------------------------------------
# the spans on the profiler's clock
# ---------------------------------------------------------------------------

def _traced(tmp_path, body):
    """Run ``body`` under the profiler; the host events of the dump."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    view = trace_reduce.load_xplane(
        trace_reduce.find_xplane(tmp_path / "trace"))
    evs = [e for e in view.host if span_reduce.is_program_span(e.name)]
    for e in evs:
        e.name = span_reduce.base(e.name)
    return evs


def _inside(e, outer):
    return [o for o in outer if o.start <= e.start and e.end <= o.end]


def _named(evs, name):
    return [e for e in evs if e.name == name]


def _disjoint(evs):
    evs = sorted(evs, key=lambda e: e.start)
    return all(a.end <= b.start for a, b in zip(evs, evs[1:]))


#: tune.* spans of one sequential update on the fused loop, and how often
#: each occurs in it
PER_UPDATE = {"tune.check": 1, "tune.pack": 1, "tune.dispatch.episode": 1,
              "tune.dispatch.update": 1, "tune.wait": 2, "tune.pull": 2,
              "tune.records": 1, "tune.replay": 1}


def test_tuning_loop_spans_on_the_trace(tmp_path):
    from repro.core.configurator import Configurator
    from repro.data.workloads import PoissonWorkload
    from repro.engine import FleetEnv

    env = FleetEnv([PoissonWorkload(8_000 + 500 * i, 0.5) for i in range(8)],
                   seeds=list(range(8)), backend="jax")
    cfgr = Configurator(env, ["latency_p99_ms", "queue_depth"],
                        ["max_batch_events", "prefetch_depth"],
                        steps_per_episode=2, window_s=240.0,
                        device_loop="on", mesh="off")
    evs = _traced(tmp_path, lambda: cfgr.tune(2))

    updates = _named(evs, "tune.update")
    assert len(updates) == 2
    phases = [e for e in evs if e.name in PER_UPDATE]
    for u in updates:
        mine = [e for e in phases if _inside(e, [u])]
        got = {n: sum(e.name == n for e in mine) for n in PER_UPDATE}
        assert got == PER_UPDATE
        # the phases of one update never overlap
        assert _disjoint(mine)
    for e in evs:
        if e.name.startswith("tune.") and e.name != "tune.update":
            assert _inside(e, updates), e.name
    # the first update builds the lever table and the config index, inside
    # its pack span (a later one rebuilds them only where the bins adapted)
    packs = _named(evs, "tune.pack")
    for name in ("tune.pack.repack", "tune.pack.index"):
        sub = _named(evs, name)
        assert sub and all(_inside(e, packs) for e in sub)
        assert _inside(sub[0], updates[:1])


def test_serve_cycle_spans_on_the_trace(tmp_path):
    from repro.data.workloads import PoissonWorkload
    from repro.serve import ServeController

    ctl = ServeController([PoissonWorkload(6_000, 0.5) for _ in range(2)],
                          metrics=["latency_p99_ms", "queue_depth"],
                          levers=["max_batch_events", "prefetch_depth"],
                          backend="jax", window_s=240.0, steps_per_episode=2,
                          canary_pairs=1, n_live=1, mesh="off",
                          slo_ms=400_000.0,
                          checkpoint_dir=tmp_path / "ck")

    def body():
        ctl.run_cycle()
        ctl.run_cycle()
        ctl.checkpoint()

    evs = _traced(tmp_path, body)
    cycles = _named(evs, "serve.cycle")
    assert len(cycles) == 2
    for c in cycles:
        mine = [e for e in evs if _inside(e, [c])]
        for name in ("serve.shadow", "serve.live"):
            assert sum(e.name == name for e in mine) == 1, name
        assert sum(e.name == "serve.history" for e in mine) >= 2
        top = [e for e in mine if e.name in
               ("serve.shadow", "serve.challenger", "serve.canary",
                "serve.gate", "serve.live")]
        assert _disjoint(top)
    shadows = _named(evs, "serve.shadow")
    assert all(_inside(u, shadows) for u in _named(evs, "tune.update"))
    assert len(_named(evs, "tune.update")) == 2
    # the first cycle adopts a challenger and canaries it
    first = [e.name for e in evs if _inside(e, cycles[:1])]
    for name in ("serve.challenger", "serve.canary", "serve.gate"):
        assert name in first
    assert len(_named(evs, "serve.checkpoint")) == 1


# ---------------------------------------------------------------------------
# the launchers' metrics.prom
# ---------------------------------------------------------------------------

def _launch(tmp_path, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-m", *argv, "--out",
                        str(tmp_path / "out")], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return (tmp_path / "out" / "metrics.prom").read_text()


@pytest.mark.parametrize("argv, series", [
    (("repro.launch.tune", "--env", "sim", "--fleet", "4", "--backend",
      "jax", "--collect", "4", "--updates", "2", "--steps-per-episode",
      "2"),
     ["tune_update", "tune_pack", "tune_dispatch_episode",
      "tune_dispatch_update", "tune_wait", "tune_pull", "tune_records",
      "tune_replay"]),
    (("repro.launch.serve", "--cycles", "2", "--quick", "--fleet", "2"),
     ["serve_cycle", "serve_shadow", "serve_canary", "serve_gate",
      "serve_live", "serve_history", "tune_update"]),
])
def test_launcher_metrics_prom_holds_span_totals(tmp_path, argv, series):
    text = _launch(tmp_path, *argv)
    names = {line.split()[0] for line in text.splitlines()
             if line and not line.startswith("#")}
    for s in series:
        for suffix in ("seconds_total", "count_total", "max_seconds"):
            assert f"repro_span_{s}_{suffix}" in names, (s, suffix)
    counters = ["tune_repacks", "tune_index_rebuilds", "tune_programs_traced",
                "tune_pull_bytes", "tune_records_built", "tune_replayed",
                "python_gc_gen0"]
    if argv[0] == "repro.launch.serve":
        counters.append("serve_history_rows")
    for c in counters:
        assert f"repro_count_{c}_total" in names, c
