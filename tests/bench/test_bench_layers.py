"""The scope of each device op, read from the trace's wire format
(``xspace.py``), and the reduction of a step's device time by layer scope
(``layers.py``): on the recorded traces of ``benchmarks/chip/testdata`` and
on synthetic ops."""
import json
import os
from types import SimpleNamespace

import pytest

import layers
import tracefile
import xspace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
DATA = os.path.join(ROOT, "benchmarks", "chip", "testdata")
UNSCOPED = os.path.join(DATA, "ring4_reduced.xplane.pb")
SCOPED = os.path.join(DATA, "ring4_scoped.xplane.pb")


# ------------------------------------------------------------ the decoder

@pytest.fixture(scope="module")
def unscoped():
    with open(os.path.join(DATA, "ring4_reduced.json")) as f:
        rec = json.load(f)
    return rec, layers.load(UNSCOPED)


def test_each_device_event_has_its_scope(unscoped):
    _, trace = unscoped
    ops = trace.devices[0].ops
    kernel = [o for o in ops if o.name == "sign_topk_blocks.3"]
    norm = [o for o in ops if o.name == "multiply_reduce_fusion"]
    assert len(kernel) == len(norm) == 3          # one a sync step
    for o in kernel:
        assert "cond/branch_1_fun" in o.scope and "pallas_call" in o.scope
    for o in norm:
        assert o.scope.startswith("jit(train_step)/cond/branch_1_fun/")


def test_every_op_event_gets_one_scope(unscoped):
    """As many events, in the same order and with the same durations, as
    ProfileData reads; each with one scope string ("" for containers and
    copies), most of them with a name stack."""
    from jax.profiler import ProfileData
    _, trace = unscoped
    evs = xspace.device_op_scopes(UNSCOPED)
    assert list(evs) == [0]
    pd_ops = [e for p in ProfileData.from_file(UNSCOPED).planes
              if p.name == "/device:TPU:0" for line in p.lines
              if line.name == "XLA Ops" for e in line.events]
    assert len(evs[0]) == len(pd_ops) == len(trace.devices[0].ops) == 2955
    for e, p in zip(evs[0], pd_ops):
        assert isinstance(e.scope, str)
        assert abs(e.duration_ps * 1e-3 - p.duration_ns) <= 1.0
    assert sum(1 for e in evs[0] if e.scope) == 2079
    window = trace.window
    inside = [o for o in trace.devices[0].ops
              if window[0] <= o.start < window[1]]
    assert inside and all(isinstance(o.scope, str) for o in inside)


def test_the_unscoped_program_reads_no_layer(unscoped):
    """A program without layer scopes (the trace of the parent) gives every
    layer None, so a reader of it reports nothing rather than zero."""
    rec, trace = unscoped
    ctx = SimpleNamespace(trace=trace, interval=trace.window,
                          window=SimpleNamespace(**rec["window"]))
    for scope, want in layers.READ_ON.items():
        assert layers.layer_ms(ctx, scope, want) is None


# ------------------------------------------------------------ synthetic

def _op(name, start, end, scope=""):
    return layers.ScopedOp(name, float(start), float(end), scope)


def _ctx(ops, modules, flags):
    dev = tracefile.Device(0, ops, modules)
    return SimpleNamespace(trace=tracefile.Trace([dev], []),
                           window=SimpleNamespace(sync_flags=flags),
                           interval=(0.0, 1e9))


def test_containers_lose_their_bodies():
    """A conditional's and a while's self time is what their bodies leave;
    only the body ops hold the scope, so the layer reads their time."""
    ops = [_op("conditional.1", 0, 10e6),
           _op("fusion.1", 1e6, 4e6, "jit(train_step)/cond/sparq_mix/add"),
           _op("while.2", 4e6, 9e6, "jit(train_step)/cond/sparq_mix/while"),
           _op("fusion.3", 5e6, 7e6,
               "jit(train_step)/cond/sparq_mix/while/body/mul")]
    mods = [_op("jit_train_step(1)", 0, 10e6)]
    ctx = _ctx(ops, mods, [True])
    # fusion.1 3 ms + while.2 (5 - 2) ms + fusion.3 2 ms
    assert layers.layer_ms(ctx, "sparq_mix", True) == pytest.approx(8.0)
    row = layers.step_layers(ctx.trace.devices[0], mods, ["sparq_mix"])[0]
    assert row["matched"] == 3 and row["module"] == pytest.approx(10.0)


def test_a_scope_matches_as_a_whole_component():
    ops = [_op("a", 0, 1e6, "jit(train_step)/sparq_grad/dot"),
           _op("b", 1e6, 3e6, "jit(train_step)/sparq_gradx/dot"),
           _op("c", 3e6, 6e6, "jit(train_step)/my_sparq_grad/dot"),
           _op("d", 6e6, 7e6, "jit(train_step)/sparq_grad.1/dot")]
    ctx = _ctx(ops, [_op("jit_train_step(1)", 0, 7e6)], [False])
    assert layers.layer_ms(ctx, "sparq_grad", None) == pytest.approx(1.0)


def test_a_scope_matches_inside_transform_wrappers():
    ops = [_op("a", 0, 1e6, "jit(train_step)/sparq_grad/"
               "vmap(jvp(sparq_unravel))/slice"),
           _op("b", 1e6, 3e6, "jit(train_step)/sparq_grad/"
               "vmap(transpose(jvp(sparq_unravel)))/pad"),
           _op("c", 3e6, 7e6, "jit(train_step)/sparq_grad/"
               "vmap(transpose(jvp(dot_general)))")]
    ctx = _ctx(ops, [_op("jit_train_step(1)", 0, 7e6)], [False])
    assert layers.layer_ms(ctx, "sparq_unravel", None) == pytest.approx(3.0)
    assert layers.layer_ms(ctx, "sparq_grad", None) == pytest.approx(7.0)


def test_steps_of_the_wanted_kind_and_none_without_a_match():
    def step(t0, ms):
        return [_op("k", t0, t0 + ms * 1e6,
                    "jit(train_step)/cond/sparq_compress/pallas_call")]
    ops = step(0, 4) + step(10e6, 6) + step(20e6, 8)
    mods = [_op("jit_train_step(1)", t, t + 9e6) for t in (0, 10e6, 20e6)]
    ctx = _ctx(ops, mods, [False, True, True])
    assert layers.layer_ms(ctx, "sparq_compress", True) == pytest.approx(7.0)
    assert layers.layer_ms(ctx, "sparq_compress", None) == pytest.approx(6.0)
    assert layers.layer_ms(ctx, "sparq_mix", None) is None
    # a window whose step count is not the trace's reads nothing
    ctx.window.sync_flags = [True, True]
    assert layers.layer_ms(ctx, "sparq_compress", True) is None


# ------------------------------------------------------------ recorded

@pytest.fixture(scope="module")
def scoped():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip", "tools"))
    import record_testdata
    with open(os.path.join(DATA, "ring4_scoped.json")) as f:
        rec = json.load(f)
    trace = layers.load(SCOPED)
    ctx = record_testdata.context(trace, rec["window"], rec["device_kind"])
    return rec, ctx


@pytest.mark.parametrize("scope", sorted(layers.READ_ON))
def test_each_layer_reads_what_was_recorded(scoped, scope):
    rec, ctx = scoped
    got = layers.layer_ms(ctx, scope, layers.READ_ON[scope])
    want = rec["readings"][scope]
    assert want is not None and want > 0
    assert got == pytest.approx(want, rel=1e-9)


def test_compress_runs_on_exactly_the_sync_steps(scoped):
    _, ctx = scoped
    dev = ctx.trace.devices[0]
    execs = tracefile.step_executions(dev, ctx.interval)
    rows = layers.step_layers(dev, execs, ["sparq_compress"])
    assert [r["sparq_compress"] > 0 for r in rows] == ctx.window.sync_flags


def test_no_op_counts_to_two_layers(scoped):
    """The five top-level layers' self times never add up to more than the
    step's module time; sparq_unravel lies inside sparq_grad."""
    _, ctx = scoped
    dev = ctx.trace.devices[0]
    execs = tracefile.step_executions(dev, ctx.interval)
    names = list(layers.TOP) + ["sparq_unravel"]
    for row in layers.step_layers(dev, execs, names):
        assert sum(row[n] for n in layers.TOP) <= row["module"] + 1e-9
        assert 0 < row["sparq_unravel"] <= row["sparq_grad"]


def test_the_kernel_reader_finds_the_named_kernel(scoped):
    """The Pallas call's fixed name still starts with sign_topk, so the
    kernel's roofline reads as it did under the generated name."""
    import run
    _, ctx = scoped
    names = {o.name for o in ctx.trace.devices[0].ops
             if o.name.startswith("sign_topk")}
    assert names and all(n.startswith("sign_topk_tiles") for n in names)
    with open(os.path.join(DATA, "ring4_reduced.json")) as f:
        before = json.load(f)["readings"]["sign_topk_roofline"]
    got = run.reader("sign_topk_roofline").read(ctx)
    assert got == pytest.approx(before, rel=1e-3)
