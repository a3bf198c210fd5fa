"""Device profiling hooks (jax.profiler trace capture + annotations +
slow-step accounting — SURVEY §5 tracing TPU equivalent)."""

import os

import pytest

import jax.numpy as jnp

from orleans_tpu.observability import Profiler, StageSpan, StatsRegistry


def test_trace_capture_writes_files(tmp_path):
    p = Profiler()
    with p.capture(str(tmp_path)):
        with StageSpan(StatsRegistry(), "test-span"):
            jnp.arange(128).sum().block_until_ready()
    assert p.active_dir is None
    dumped = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert dumped, "no trace files written"


def test_double_start_rejected(tmp_path):
    p = Profiler()
    p.start(str(tmp_path))
    try:
        with pytest.raises(RuntimeError, match="already active"):
            p.start(str(tmp_path))
    finally:
        p.stop()
    assert p.stop() is None  # idempotent


def test_stage_span_observes_and_nests():
    """The one span primitive (was StepTimer): a closed span observes
    ``<name>.seconds`` once, failed steps included, and the thread's
    current stage is restored."""
    from orleans_tpu.observability import stats as stats_mod

    stats = StatsRegistry()
    with StageSpan(stats, "tick", tick=7) as outer:
        assert stats_mod._thread.stage is outer
        with pytest.raises(ValueError):
            with StageSpan(stats, "tick.inner", tick=7):
                raise ValueError("a failed step is recorded too")
        assert stats_mod._thread.stage is outer
    assert stats_mod._thread.stage is None
    assert stats.histogram("tick.seconds").total == 1
    assert stats.histogram("tick.inner.seconds").total == 1
    assert stats.histogram("tick.seconds").sum >= \
        stats.histogram("tick.inner.seconds").sum
