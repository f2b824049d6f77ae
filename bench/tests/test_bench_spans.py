"""Self time of the program's spans and the idle device time charged to
them (`bench.lib.spantime`), and the readers of its byte counters."""
from __future__ import annotations

import types

import pytest

from bench.lib import spantime
from bench.lib.harness import BENCH, CallRecord, load_module
from bench.lib.tracereduce import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _hand_made() -> list[Event]:
    """Two plan calls.  Times in ns; the device runs each call's Phase-2
    program during its wait span, at [22, 26) and [72, 76)."""
    ev = [Event(HOST, "python", "bench.window", 0, 100)]
    for t in (0, 50):
        ev += [
            Event(HOST, "python", "plan.call", t + 0, 50),
            Event(HOST, "python", "facade.plan", t + 2, 46),
            Event(HOST, "python", "agh.setup", t + 4, 10),
            Event(HOST, "python", "xla.tensors", t + 5, 3),
            Event(HOST, "python", "DevicePutWithSharding", t + 6, 1),
            Event(HOST, "python", "xla.phase2.dispatch", t + 20, 2),
            Event(HOST, "python", "PjitFunction(_phase2_keys_jit)",
                  t + 20, 2),
            Event(HOST, "python", "xla.phase2.wait", t + 22, 4),
            Event(HOST, "python", "xla.phase2.fetch", t + 26, 8),
            Event(HOST, "python", "np.asarray(jax.Array)", t + 27, 6),
            Event(HOST, "python", "agh.phase2.walk", t + 34, 10),
        ]
    ev += [Event(DEV, "XLA Modules", "jit__phase2_keys_jit(1)", 22, 4),
           Event(DEV, "XLA Modules", "jit__phase2_keys_jit(1)", 72, 4)]
    return ev


def test_self_time_subtracts_program_children_only():
    t = spantime.span_times(_hand_made(), "plan.call")
    ns = 1e-9
    assert t.calls["facade.plan"] == 2 and t.calls["plan.call"] == 2
    assert "np.asarray(jax.Array)" not in t.calls
    # facade.plan: 46 minus agh.setup 10, dispatch 2, wait 4, fetch 8,
    # walk 10.
    assert t.self_s["facade.plan"] == pytest.approx(2 * 12 * ns)
    assert t.self_s["agh.setup"] == pytest.approx(2 * 7 * ns)
    # JAX's own events stay with the program span around them.
    assert t.self_s["xla.tensors"] == pytest.approx(2 * 3 * ns)
    assert t.self_s["xla.phase2.fetch"] == pytest.approx(2 * 8 * ns)
    assert t.self_s["xla.phase2.dispatch"] == pytest.approx(2 * 2 * ns)
    assert t.self_s["plan.call"] == pytest.approx(2 * 4 * ns)
    assert t.self_s["bench.window"] == pytest.approx(0.0)
    # Self times add up to the window.
    assert sum(t.self_s.values()) == pytest.approx(100 * ns)


def test_idle_time_goes_to_the_step_the_host_was_in():
    t = spantime.span_times(_hand_made(), "plan.call")
    ns = 1e-9
    assert t.idle_s == pytest.approx(92 * ns)
    # [0, 22) at 11: agh.setup; [26, 72) at 49: between the calls, in the
    # first call's span; [76, 100) at 88: agh.phase2.walk.
    assert t.idle_by_step == pytest.approx(
        {"agh.setup": 22 * ns, "plan.call": 46 * ns,
         "agh.phase2.walk": 24 * ns})
    assert t.named_idle_share == pytest.approx(46 / 92)


def test_root_spans_and_jax_events_are_not_named_steps():
    ev = [Event(HOST, "python", "bench.window", 0, 100),
          Event(HOST, "python", "risk.report", 0, 100),
          Event(HOST, "python", "facade.risk", 0, 80),
          Event(HOST, "python", "np.asarray(jax.Array)", 10, 20),
          Event(HOST, "python", "stage2.candidate.fetch", 40, 40),
          Event(DEV, "XLA Modules", "jit__candidate_kernel(3)", 30, 10)]
    t = spantime.span_times(ev, "risk.report")
    # [0, 30) at 15: np.asarray in facade.risk, the root; [40, 100) at 70:
    # the fetch.
    assert t.idle_by_step == pytest.approx(
        {"facade.risk": 30e-9, "stage2.candidate.fetch": 60e-9})
    assert t.named_idle_share == pytest.approx(60 / 90)


def test_layer_metrics_per_call():
    t = spantime.span_times(_hand_made(), "plan.call")
    per = {m: spantime.self_ms_per_call(t, 2, inc, exc)
           for m, (inc, exc) in spantime.LAYERS.items()}
    ms = 1e-6
    assert per["readback_ms.plan"] == pytest.approx(12 * ms)
    assert per["xla_host_ms.plan"] == pytest.approx(5 * ms)
    assert per["agh_host_ms.plan"] == pytest.approx(17 * ms)
    assert per["facade_ms.plan"] == pytest.approx(12 * ms)
    # No risk spans in a plan trace: nothing to read.
    assert per["readback_ms.risk"] is None
    assert per["exact_ms.risk"] is None


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        spantime.span_times([Event(HOST, "python", "plan.call", 0, 1)],
                            "plan.call")


@pytest.mark.parametrize("metric,scale", [("readback_kb.plan", 1e3),
                                          ("readback_mb.risk", 1e6)])
def test_readback_readers(metric, scale):
    mod = load_module(BENCH / "metrics" / f"{metric}.py", "m_" + metric
                      .replace(".", "_"))

    def run(diags):
        recs = [CallRecord(i, 0.0, 1.0, {"diagnostics": d})
                for i, d in enumerate(diags)]
        return types.SimpleNamespace(records=recs)

    assert mod.read(run([{"readback_bytes": 1000}, {"readback_bytes": 3000}])
                    ) == pytest.approx(2000 / scale)
    # A program without the counter (the parent of this counter) reads
    # nothing.
    assert mod.read(run([{"device_calls_phase2": 20}])) is None
    assert mod.read(run([])) is None
