"""Program observability (``repro.core.obs``): the span and counter
registry, the step's device scopes in the compiled program, and the
spans a sweep launch leaves."""

import contextlib
import re
import time

import jax
import numpy as np
import pytest

from repro.core import PAPER_CONFIG, CCScheme, ScenarioSpec, Sweep, obs
from repro.core.experiments import _sweep_scan_fn
from repro.net import FabricSpec

# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


def test_snapshots_subtract():
    reg = obs.Registry()
    with reg.span("a"):
        pass
    reg.count("bytes", 5)
    before = reg.stats()
    with reg.span("a"):
        time.sleep(0.01)
    with reg.span("b"):
        pass
    reg.count("bytes", 7)
    reg.count("other", 1)
    d = reg.stats() - before
    assert set(d.spans) == {"a", "b"}
    assert d.span("a").n == 1 and d.span("a").s >= 0.01
    assert d.span("a").last_s == reg.stats().span("a").last_s
    assert d.counts == {"bytes": 7, "other": 1}
    assert reg.stats().counts["bytes"] == 12
    assert reg.stats().span("a").n == 2
    # a window with nothing in it is empty; an unknown span reads zero
    assert reg.stats() - reg.stats() == obs.Stats()
    assert d.span("never") == obs.SpanStat()


def test_nested_spans_and_errors():
    reg = obs.Registry()
    with reg.span("outer"):
        with reg.span("inner"):
            time.sleep(0.005)
        with reg.span("inner"):
            pass
    s = reg.stats()
    assert s.span("outer").n == 1 and s.span("inner").n == 2
    assert s.span("outer").s >= s.span("inner").s >= 0.005
    assert s.span("inner").last_s < s.span("inner").s
    with pytest.raises(ValueError):
        with reg.span("raises"):
            raise ValueError("boom")
    assert reg.stats().span("raises").n == 1     # a span that raised still counts


def test_span_decorates_each_call():
    reg = obs.Registry()

    @reg.span("f")
    def f(x):
        return x + 1

    assert [f(i) for i in range(3)] == [1, 2, 3]
    assert reg.stats().span("f").n == 3


# ---------------------------------------------------------------------------
# op scopes from HLO text
# ---------------------------------------------------------------------------

HLO = """HloModule jit_scan_fn, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/vmap(fluid.transfer)/fluid.reduce/add"}
}

%fused_b (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  %m.1 = f32[4]{0} multiply(%q, %q), metadata={op_name="jit(f)/while/body/vmap(fluid.pfc)/fluid.reduce/scatter-add"}
  ROOT %m.2 = f32[4]{0} multiply(%m.1, %q), metadata={op_name="jit(f)/while/body/closed_call"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/vmap(fluid.transfer)/fluid.reduce/add" stack_frame_id=3}
  %copy.2 = f32[4]{0} copy(%fusion.7), metadata={op_name="jit(f)/while/body/vmap(fluid.react)/mul"}
  %wrapped_add = s32[] fusion(%a), kind=kLoop, calls=%c, metadata={op_name="jit(f)/while/body/add"}
  %multiply_fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_b
  ROOT %tuple.1 = (f32[4]{0}) tuple(%copy.2)
}
"""


def test_op_scopes_takes_the_innermost_fluid_scope():
    m = obs.op_scopes(HLO)
    assert m["fusion.7"] == "fluid.reduce" and m["add.1"] == "fluid.reduce"
    assert m["copy.2"] == "fluid.react"
    assert m["wrapped_add"] == "unscoped" and m["tuple.1"] == "unscoped"
    assert m["p"] == "unscoped"
    # a fusion the compiler left without metadata: the scope its fused ops carry
    assert m["multiply_fusion.3"] == "fluid.reduce" and m["m.2"] == "unscoped"
    assert obs.module_name(HLO) == "jit_scan_fn"


# ---------------------------------------------------------------------------
# scopes in the compiled sweep program
# ---------------------------------------------------------------------------


def strip_metadata(hlo: str) -> str:
    """An optimized HLO module's text without its metadata: every
    ``metadata={...}`` and the stack-frame tables that source lines and
    scope names fill."""
    out, tables = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            tables = True
            continue
        if tables and line.startswith(("%", "ENTRY")):
            tables = False
        if not tables:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def _paper_clos():
    return [(s.name, PAPER_CONFIG.replace(scheme=s),
             ScenarioSpec.paper_incast(roll=0)) for s in CCScheme]


def _fat_tree_a2a():
    pairs = [(i, j) for i in range(8) for j in range(8) if i != j]
    spec = ScenarioSpec.flows(pairs, fabric=FabricSpec.xgft((4, 4), (1, 4)))
    return [(s.name, PAPER_CONFIG.replace(scheme=s), spec)
            for s in (CCScheme.DCQCN, CCScheme.DCQCN_REV)]


def _compiled_text(points) -> str:
    static, args, _, _ = Sweep(points)._prepare(20, 10)
    return jax.jit(_sweep_scan_fn(*static)).lower(*args).compile().as_text()


#: fusions of the per-step scan that carry no phase scope: the inner
#: scan's own step counter, and vmap's broadcast of an unbatched output
UNSCOPED_IN_STEP = ("while/body/add", "while/body/closed_call")


@pytest.fixture(scope="module", params=["paper_clos", "fat_tree_a2a"])
def scoped(request):
    points = {"paper_clos": _paper_clos, "fat_tree_a2a": _fat_tree_a2a}[request.param]()
    return points, _compiled_text(points)


def test_step_fusions_carry_a_phase_scope(scoped):
    _, text = scoped
    scopes = obs.op_scopes(text)
    in_step = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .* fusion\(", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op and op.group(1).count("while/body") >= 2:
            in_step.append((m.group(1), op.group(1)))
    assert len(in_step) > 20
    phases = {scopes[name] for name, _ in in_step}
    assert {"fluid.reduce", "fluid.transfer", "fluid.mark", "fluid.react",
            "fluid.decimate"} <= phases
    stray = [(name, op) for name, op in in_step if scopes[name] == obs.UNSCOPED
             and not op.endswith(UNSCOPED_IN_STEP)]
    assert not stray
    assert all(s == obs.UNSCOPED or s.startswith(obs.SCOPE_PREFIX)
               for s in scopes.values())


def test_scopes_leave_the_compiled_program_alone(scoped, monkeypatch):
    """The scopes are metadata: without them the optimized program is
    the same, instruction for instruction."""
    points, text = scoped
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    bare = _compiled_text(points)
    assert set(obs.op_scopes(bare).values()) == {obs.UNSCOPED}
    assert "fluid.reduce" in obs.op_scopes(text).values()
    assert strip_metadata(bare) == strip_metadata(text)


# ---------------------------------------------------------------------------
# spans of a launch
# ---------------------------------------------------------------------------


def test_run_leaves_one_span_of_each_part():
    sweep = Sweep(_paper_clos())
    sweep.run(n_steps=20, trace_every=10)           # compiles
    sd = sweep._prepare(20, 10)[1][1]
    before = obs.stats()
    res = sweep.run(n_steps=20, trace_every=10)
    d = obs.stats() - before
    for part in ("stage", "resolve", "execute", "fetch"):
        assert d.span(f"repro.sweep.{part}").n == 1, part
    assert d.span("repro.exec_cache.build").n == 0          # a cache hit
    nbytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves((res.traces, res.final)))
    rows = int((sd.red_idx != sd.alt_routes[0].size).sum())
    assert d.counts == {"sweep.fetch_bytes": nbytes,
                        "sweep.reduce_slots": sd.red_idx.size,
                        "sweep.reduce_rows": rows}
    assert "jit_scan_fn" in obs.sweep_op_scopes()
    assert "fluid.reduce" in obs.sweep_op_scopes()["jit_scan_fn"].values()



def _bench_reader(name: str):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reduce_fill_reads_the_share_of_slots_with_a_contributor(
        monkeypatch):
    """``sweep.reduce_rows`` / ``sweep.reduce_slots`` of a launch is the
    share of the jagged layout's slots that are not the sentinel, and
    the benchmark's reader reports it; the segment-sum engine counts
    nothing, so the reader reports nothing there."""
    reader = _bench_reader("reduce_fill.sweep")
    sweep = Sweep(_paper_clos())
    sd = sweep._prepare(20, 10)[1][1]
    fill = np.mean(np.asarray(sd.red_idx) != sd.alt_routes[0].size)
    stats = obs.stats
    before = stats()
    monkeypatch.setattr(obs, "stats", lambda: stats() - before)
    sweep.run(n_steps=20, trace_every=10, dense_rows=0)
    assert reader.read({}) is None
    sweep.run(n_steps=20, trace_every=10)
    assert reader.read({}) == pytest.approx(fill)
    assert 0.5 <= reader.read({}) <= 1.0

def test_routes_build_once_per_fabric():
    fab = FabricSpec.xgft((3, 3), (1, 2))                   # no other test builds it
    spec = ScenarioSpec.flows([(0, 4), (1, 5)], fabric=fab)
    before = obs.stats()
    spec.build(PAPER_CONFIG)
    spec.build(PAPER_CONFIG)
    d = obs.stats() - before
    assert d.span("repro.scenario.build").n == 2
    assert d.span("repro.routes.build").n == 1              # the table is cached
