"""The program's host spans on the profiler's trace, and the byte counters
kept at the same boundaries (`repro.spans`).

One small xla-engine plan and one small pdhg risk report run under the
profiler; the trace is read back with the benchmark's own reader
(`bench.lib.tracereduce.load`).
"""
from __future__ import annotations

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.spans import span

pytest.importorskip("jax")

# Every span the program opens (src/repro/core/README.md, "Tracing a live
# service").
SPAN_NAMES = {
    "facade.plan", "facade.replan", "facade.repair", "facade.risk",
    "facade.result",
    "agh.setup", "agh.phase1", "agh.phase2.walk", "agh.relocate",
    "agh.consolidate", "agh.reduce",
    "xla.tensors",
    "xla.phase2.pack", "xla.phase2.dispatch", "xla.phase2.wait",
    "xla.phase2.fetch",
    "xla.screen.pack", "xla.screen.dispatch", "xla.screen.wait",
    "xla.screen.fetch",
    "stage2.build", "stage2.draw", "stage2.coefficients", "stage2.upload",
    "stage2.assign", "stage2.candidate.dispatch", "stage2.candidate.wait",
    "stage2.candidate.fetch", "stage2.record", "stage2.pdhg",
    "stage2.pdhg.wait", "stage2.pdhg.fetch", "stage2.stats",
    "oracle.exact",
}


class _Tracked:
    """A device output that logs the bytes numpy reads from it."""

    def __init__(self, value, log: list):
        self.value, self.log = value, log

    @property
    def shape(self):
        return self.value.shape

    def block_until_ready(self):
        self.value.block_until_ready()
        return self

    def copy_to_host_async(self):
        self.value.copy_to_host_async()

    def __getitem__(self, key):
        return _Tracked(self.value[key], self.log)

    def __array__(self, dtype=None, copy=None):
        host = np.asarray(self.value)
        self.log.append(host.nbytes)
        return host


def _tracked(program, log: list):
    """`program` with each of its outputs wrapped in `_Tracked`; outputs
    handed back to it as inputs (the risk kernel's device-resident warm
    starts) go in unwrapped, so passing them reads nothing."""
    def run(*args):
        out = program(*(a.value if isinstance(a, _Tracked) else a
                        for a in args))
        if isinstance(out, tuple):
            return tuple(_Tracked(v, log) for v in out)
        return _Tracked(out, log)
    return run


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced xla plan and pdhg risk report, with the bytes numpy read
    from every device output of each."""
    import jax

    from bench.lib.tracereduce import load
    from repro import PlanOptions, plan
    from repro.core import gh, random_instance
    from repro.core.xla import kernels
    from repro.risk import risk_evaluate
    from repro.risk import solver as risk_solver

    inst = random_instance(8, 6, 6, seed=3)
    rinst = random_instance(10, 8, 8, seed=7)
    deploy = gh(rinst)
    opts = PlanOptions(engine="xla")
    plan("agh", instance=inst, options=opts)        # compile outside
    plan_log: list = []
    risk_log: list = []
    out = tmp_path_factory.mktemp("trace")
    prof = jax.profiler.ProfileOptions()
    prof.python_tracer_level = 0
    with pytest.MonkeyPatch.context() as mp:
        for name in ("_phase2_keys_jit", "_screen_jit"):
            mp.setattr(kernels, name, _tracked(getattr(kernels, name),
                                               plan_log))
        mp.setattr(risk_solver, "_candidate_kernel",
                   _tracked(risk_solver._candidate_kernel, risk_log))
        jax.profiler.start_trace(str(out), profiler_options=prof)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                res = plan("agh", instance=inst.with_lam(inst.lam * 1.05),
                           options=opts)
                rep = risk_evaluate(rinst, deploy, S=200, chunk=100,
                                    engine="pdhg")
        finally:
            jax.profiler.stop_trace()
    (path,) = out.glob("**/*.xplane.pb")
    return {"events": load(str(path)), "plan": res.diagnostics,
            "risk": rep.diagnostics, "plan_log": plan_log,
            "risk_log": risk_log}


def _program_spans(events):
    return sorted((e for e in events if e.name.startswith(
        ("facade.", "agh.", "xla.", "stage2.", "oracle."))),
        key=lambda e: (e.start_ns, -e.dur_ns))


def test_every_program_span_is_a_documented_one(traced):
    names = {e.name for e in _program_spans(traced["events"])}
    assert names <= SPAN_NAMES, names - SPAN_NAMES
    # Both services left their main steps on the trace.
    assert {"facade.plan", "facade.result", "agh.relocate",
            "xla.screen.fetch", "xla.phase2.fetch", "facade.risk",
            "stage2.candidate.fetch", "oracle.exact"} <= names


def test_spans_nest(traced):
    """Every span open when another starts contains it: no span was held
    open across a generator's `yield`."""
    from bench.lib.tracereduce import _SpanIndex
    spans = [e for e in traced["events"] if e.plane == "/host:CPU"]
    index = _SpanIndex(spans)
    open_: list = []
    for k, e in enumerate(index.spans):
        while open_ and open_[-1].end_ns <= e.start_ns:
            open_.pop()
        for outer in open_:
            assert outer.end_ns >= e.end_ns, (outer.name, e.name)
        if index.parent[k] >= 0:
            p = index.spans[index.parent[k]]
            assert p.start_ns <= e.start_ns and e.end_ns <= p.end_ns
        open_.append(e)


@pytest.mark.parametrize("prefix", ["xla.phase2.", "xla.screen.",
                                    "stage2.candidate."])
def test_each_dispatch_is_followed_by_its_wait_and_fetch(traced, prefix):
    seq = [e.name[len(prefix):] for e in _program_spans(traced["events"])
           if e.name.startswith(prefix) and not e.name.endswith(".pack")]
    assert seq and len(seq) % 3 == 0
    assert seq == ["dispatch", "wait", "fetch"] * (len(seq) // 3)


@pytest.mark.parametrize("service", ["plan", "risk"])
def test_readback_bytes_count_what_numpy_read(traced, service):
    diag, log = traced[service], traced[f"{service}_log"]
    assert log
    if service == "risk":
        assert diag["n_pdhg"] == 0      # only the candidate path read back
    assert diag["readback_bytes"] == sum(log)


def test_risk_reads_back_one_summary_per_candidate_row(traced):
    """The candidate path reads back each padded row's verdict, cost and
    statistics, never its iterates: a change that reads `z`, `y`,
    `rowsv` or `score` back again overshoots this bound."""
    from repro.core.stage2 import Stage2System
    r = traced["risk"]
    assert r["n_pdhg"] == 0
    n_fam = len(Stage2System.ROW_FAMILIES)
    assert 0 < r["readback_bytes"] <= 8 * (4 + n_fam) \
        * r["candidate_rows_padded"]


def test_upload_and_row_counters(traced):
    p, r = traced["plan"], traced["risk"]
    assert p["upload_bytes"] > 0
    assert p["screen_rows_padded"] >= p["screen_sources"] > 0
    assert r["upload_bytes"] > 0
    assert r["candidate_rows_padded"] >= r["candidate_rows"] \
        >= r["n_anchor0"] > 0


def test_span_is_a_null_context_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    ctx = span("facade.plan")
    assert ctx is span("agh.setup")
    with ctx:
        pass


def test_numpy_plan_runs_where_jax_cannot_be_imported():
    code = textwrap.dedent("""
        import sys
        sys.modules["jax"] = None
        from repro import plan
        from repro.core import random_instance
        res = plan("agh", instance=random_instance(6, 5, 5, seed=1))
        assert res.feasible and "jax" not in [
            m.split(".")[0] for m in sys.modules if sys.modules[m]]
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
