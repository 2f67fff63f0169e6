"""The program's trace points and the benchmark's reading of them.

- ``repro.tracing``'s compile counter records a fresh compile, nothing
  for a cached call, and stamps its records on the profiler's clock;
- ``bench.lib.scopes`` decodes each device op's ``tf_op`` path from a
  recorded TPU trace and gives every op event of the window one owner:
  a kernel by its reader's pattern, else the innermost program scope,
  else nothing;
- ``bench.lib.compiles`` counts and times the records against the
  window.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import compiles, scopes  # noqa: E402
from bench.lib.trace import Trace  # noqa: E402
from repro import tracing  # noqa: E402

DATA = ROOT / "tests" / "data"
SMALL_TRACE = ROOT / "bench" / "tests" / "data" / "small.xplane.pb"
# one TPU v5e, scripts/record_scoped_trace.py: three calls of a gradient
# step with a Pallas kernel (custom_vjp forward, ``%jvp__.N``), the loss
# under fed.model, the update under fed.update and a sort under no scope;
# 42 KB
SCOPED_TRACE = DATA / "scoped.xplane.pb"


@pytest.fixture(scope="module", autouse=True)
def counter():
    tracing.install()


def test_scope_names_agree():
    """The benchmark names the program's scopes itself (a rename reads as
    nothing); the two lists must stay the same."""
    assert scopes.SCOPES == tracing.SCOPES


def test_counter_records_a_fresh_compile_once():
    f = jax.jit(lambda x: x * 3.0 - 1.0)
    x = jnp.arange(7.0)
    n = len(tracing.records())
    f(x).block_until_ready()
    first = {r[0] for r in tracing.records()[n:]}
    assert {tracing.TRACE_EVENT, tracing.LOWER_EVENT,
            tracing.COMPILE_EVENT} <= first
    assert all(s <= e for _, s, e in tracing.records()[n:])
    n = len(tracing.records())
    f(x).block_until_ready()
    assert tracing.records()[n:] == []


def test_records_share_the_profilers_clock(tmp_path):
    """A compile made inside a traced ``bench.window`` span falls inside
    that span as ``Trace.from_file`` reads it."""
    x = jnp.ones(11)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            n = len(tracing.records())
            jax.jit(lambda x: x * 7.0 + 2.0)(x).block_until_ready()
            made = tracing.records()[n:]
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    t = Trace.from_file(str(path))
    t0 = scopes.profile_start_ns(str(path))
    assert made and t0 is not None
    for _, s, e in made:
        assert t.lo <= s - t0 <= e - t0 <= t.hi
    ctx = {"trace": t, "profile_start_ns": t0}
    assert compiles.compiles_in_window(ctx) == 1


def test_decoder_reads_tf_op():
    paths = scopes.op_paths(str(SMALL_TRACE))
    (name, path), = [(k, v) for k, v in paths.items()
                     if k.startswith("%fusion = f32[512,512]")]
    assert path == "jit(<lambda>)/dot_general:"
    assert scopes.profile_start_ns(str(SMALL_TRACE)) > 1.7e18


@pytest.mark.parametrize("path,scope", [
    ("jit(fn)/while/body/closed_call/fed.update/add:", "fed.update"),
    ("jit(fn)/vmap()/while/body/transpose(jvp(fed.model))/dot_general:",
     "fed.model"),
    ("jit(fn)/closed_call/fed.aggregate/fed.downlink/convert:",
     "fed.downlink"),
    ("jit(fn)/transpose(jvp(fed.model))/transpose(jvp(qz.layout))/reshape",
     "qz.layout"),
    ("jit(fn)/closed_call/vmap(fed.upload)/pallas_call:", "fed.upload"),
    ("jit(fn)/while/body/squeeze:", None),
    ("jit(fn)/fed.modelx/add:", None),
    ("jit(fn)/my.fed.model/add:", None),
    ("", None),
])
def test_scope_of(path, scope):
    assert scopes.scope_of(path) == scope


def test_attribution_on_recorded_trace():
    t = Trace.from_file(str(SCOPED_TRACE))
    paths = scopes.op_paths(str(SCOPED_TRACE))
    kernels = scopes.kernel_patterns()
    owners = scopes.attribute(t, paths, kernels)
    assert set(owners) == {*kernels, *scopes.SCOPES, scopes.UNSCOPED}
    # the kernel by its reader's pattern, once a call (the trace holds
    # the device events of the first two calls); no plan backward
    calls = owners["reconstruct_roofline"][1]
    assert calls == 2
    assert owners["bwd_plan_roofline"] == (0.0, 0)
    # one fused contraction a call under each scope
    assert owners["fed.model"][1] == owners["fed.update"][1] == calls
    # the sort, and the copies the compiler inserted (no op_name)
    assert owners[scopes.UNSCOPED][1] > calls
    top = scopes.top_unscoped(t, paths, kernels, k=2)
    assert top[0][0].startswith("%sort") and top[0][1].endswith("sort:")
    assert all(secs > 0 for secs, n in owners.values() if n)
    for owner in ("fed.upload", "fed.aggregate", "fed.downlink",
                  "qz.layout"):
        assert owners[owner] == (0.0, 0), owner
    # nothing lost, nothing counted twice
    events = [e for c in t.chips for e in t.ops[c]]
    assert sum(n for _, n in owners.values()) == len(events)
    total = sum(s for s, _ in owners.values())
    assert total == pytest.approx(sum(e - s for s, e, _ in events) * 1e-9,
                                  rel=1e-9)
    # the ops of one chip do not overlap: the owners' sums are busy time
    assert total == pytest.approx(t.busy_s(), rel=1e-6)


def test_compile_counts_against_a_window(monkeypatch):
    """Nested records (a retrieval inside its compile, a trace inside an
    outer trace) count and time once; a compile inside a trace counts as
    compile time, not trace time."""
    recs = [
        (tracing.TRACE_EVENT, 100, 400),
        (tracing.TRACE_EVENT, 200, 300),
        (tracing.COMPILE_EVENT, 150, 250),
        (tracing.LOWER_EVENT, 400, 500),
        (tracing.COMPILE_EVENT, 500, 900),
        (tracing.CACHE_EVENT, 600, 800),
        (tracing.COMPILE_EVENT, 1500, 1600),  # inside the window
        (tracing.CACHE_EVENT, 1700, 1750),
        (tracing.COMPILE_EVENT, 1900, 2100),  # overlaps its end
    ]
    monkeypatch.setattr(tracing, "records",
                        lambda: [(e, s + 10, f + 10) for e, s, f in recs])

    class Window:
        lo, hi = 1000, 2000

    ctx = {"trace": Window, "profile_start_ns": 10}
    assert compiles.compiles_in_window(ctx) == 3
    assert compiles.seconds_before_window(
        ctx, ("TRACE_EVENT", "LOWER_EVENT")) == pytest.approx(400e-9)
    assert compiles.seconds_before_window(
        ctx, ("TRACE_EVENT", "LOWER_EVENT"),
        less=("COMPILE_EVENT", "CACHE_EVENT")) == pytest.approx(300e-9)
    assert compiles.seconds_before_window(
        ctx, ("COMPILE_EVENT", "CACHE_EVENT")) == pytest.approx(500e-9)
