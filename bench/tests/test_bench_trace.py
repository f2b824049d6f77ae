"""Trace reduction: device busy, idle share, per-program device time."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib.tracereduce import (Event, module_function, reduce_events)

DATA = Path(__file__).parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _recorded() -> list[Event]:
    with gzip.open(DATA / "tpu_trace_events.json.gz", "rt") as fh:
        return [Event(*row) for row in json.load(fh)["events"]]


def test_module_names_map_to_jit_functions():
    assert module_function("jit__phase2_keys_jit(11273308398267362166)") \
        == "_phase2_keys_jit"
    assert module_function("jit__pdhg_block(42)") == "_pdhg_block"
    assert module_function("jit_dynamic_slice(4020459306236897689)") \
        == "dynamic_slice"
    assert module_function("fusion.3") == "fusion.3"


def test_union_and_idle_attribution_on_a_hand_made_trace():
    ev = [Event(HOST, "python", "bench.window", 0, 100),
          Event(HOST, "python", "plan.call", 0, 60),
          Event(HOST, "python", "PjitFunction(_screen_jit)", 10, 5),
          Event(HOST, "python", "risk.report", 60, 40),
          Event(DEV, "XLA Modules", "jit__screen_jit(1)", 12, 20),
          Event(DEV, "XLA Modules", "jit__screen_jit(1)", 20, 5),  # overlaps
          Event(DEV, "XLA Modules", "jit__pdhg_block(2)", 70, 40)]  # runs on
    s = reduce_events(ev)
    assert s.window_s == pytest.approx(100e-9)
    # busy: [12, 32) and [70, 100) clipped to the window.
    assert s.busy_s == pytest.approx(50e-9)
    assert s.idle_share == pytest.approx(0.5)
    assert s.module_calls == {"_screen_jit": 2, "_pdhg_block": 1}
    assert s.kernel_s("_pdhg_block") == pytest.approx(30e-9)
    assert s.kernel_s("_candidate_kernel") == 0.0
    # idle [0,12) inside the dispatch span's parent at its midpoint 6:
    # plan.call; [32,70) midpoint 51: plan.call; none is in the window
    # span alone.
    assert s.idle_by_host == pytest.approx({"plan.call": 50e-9})


def test_recorded_tpu_trace():
    ev = _recorded()
    s = reduce_events(ev)
    assert s.n_devices == 1
    assert s.module_calls == {"_phase2_keys_jit": 8, "_screen_jit": 3,
                              "dynamic_slice": 3, "_candidate_kernel": 1}
    assert 0.0 < s.busy_s < s.window_s
    assert s.window_s == pytest.approx(0.191522939)
    # Busy, checked against a brute-force 100 ns grid of the programs.
    w0 = next(e.start_ns for e in ev if e.name == "bench.window")
    grid = np.zeros(int(s.window_s * 1e7) + 1, bool)
    for e in ev:
        if e.plane == DEV and e.line == "XLA Modules":
            a = max(0, int((e.start_ns - w0) / 100))
            b = min(grid.size, int(np.ceil((e.end_ns - w0) / 100)))
            grid[a:b] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-7, rel=0.02)
    idle = sum(s.idle_by_host.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert {"plan.call", "risk.report"} <= set(s.idle_by_host)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "_screen_jit"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        reduce_events([Event(DEV, "XLA Modules", "jit_f(1)", 0, 1)])


def test_load_keeps_the_window_thread(tmp_path):
    """`load` on a real trace file (the CPU's: it has no device plane):
    the window span and the spans inside it, python tracer off."""
    import jax
    import jax.numpy as jnp

    from bench.lib.tracereduce import load
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("plan.call"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = [e.name for e in load(str(path))]
    assert names.count("bench.window") == 1
    assert "plan.call" in names
    assert not any(n.startswith("$") for n in names)
