"""Device time per name scope, and the readers of the four metrics the
program's own instrumentation feeds (replay_device_ms,
measure_device_ms, window_compile_s, replay_fill_pct)."""
import glob
import json
import os

import pytest

import benchkit  # noqa: F401 — puts the harness on the path
from harness import profile, scopes, spec
from harness.cell import Context
from harness.drive import Record

FAMILY = "two_phase|evolve|reconstruct|batch_measure"


def _scoped():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_scoped.json")) as fh:
        d = json.load(fh)
    ops = [(s, dur, 0, module, tuple(path))
           for _, s, dur, module, path in d["ops"]]
    return d, ops


def test_scopes_and_unscoped_add_up_to_busy_time():
    d, ops = _scoped()
    want = d["expect"]
    r = scopes.reduce(ops, d["start_ns"], d["end_ns"], family=FAMILY)
    busy = profile.reduce(profile.Trace(
        ops=[(n, s, dur) for n, s, dur, *_ in d["ops"]], modules=[],
        host=[], start_ns=d["start_ns"], end_ns=d["end_ns"]))["busy_s"]
    assert busy == pytest.approx(want["busy_ns"] / 1e9)
    assert sum(r["scopes_s"].values()) == pytest.approx(busy)
    assert {k: round(v * 1e9) for k, v in r["scopes_s"].items()} == \
        want["scopes_ns"]
    assert {k: round(v * 1e9) for k, v in r["paths_s"].items()} == \
        want["paths_ns"]
    assert round(r["family_s"] * 1e9) == want["family_ns"]
    assert round(r["family_unscoped_s"] * 1e9) == \
        want["family_unscoped_ns"]


def test_scopes_average_over_devices():
    d, ops = _scoped()
    twice = ops + [(s, dur, 1, m, p) for s, dur, _, m, p in ops]
    r = scopes.reduce(twice, d["start_ns"], d["end_ns"], devices=2)
    assert r["scopes_s"]["replay"] == pytest.approx(200e-9)


def test_time_inside_a_loop_goes_to_its_body_ops():
    # a while op around two body ops: only its own overhead stays
    # with it
    ops = [(100, 400, 0, "jit_batch_edge_two_phase_agg", ()),
           (150, 100, 0, "jit_batch_edge_two_phase_agg", ("replay",)),
           (300, 150, 0, "jit_batch_edge_two_phase_agg", ("measure",))]
    r = scopes.reduce(ops, 0, 1000, family=FAMILY)
    assert {k: round(v * 1e9) for k, v in r["scopes_s"].items()} == \
        {"replay": 100, "measure": 150, "unscoped": 150}
    assert round(r["family_unscoped_s"] * 1e9) == 150


def test_idle_gap_under_a_compile_is_named_compile():
    d, _ = _scoped()
    tr = profile.Trace(ops=[(n, s, dur) for n, s, dur, *_ in d["ops"]],
                       modules=[], host=[tuple(h) for h in d["host"]],
                       start_ns=d["start_ns"], end_ns=d["end_ns"])
    name, secs = profile.reduce(tr, top=1)["idle_gaps"][0]
    assert [name, round(secs * 1e9)] == d["expect"]["gap"]


def test_reduce_of_the_small_trace_is_unchanged():
    from test_bench_profile import FAMILIES, small
    tr, _ = small()
    assert profile.reduce(tr, FAMILIES) == {
        "busy_s": 3e-07, "window_s": 1e-06, "idle_share": 0.7,
        "modules_s": {"jit_batch_edge_two_phase_point": 1.5e-07,
                      "jit_batch_hybrid_point": 1e-07,
                      "jit_concatenate": 5e-08},
        "families_s": {"reconstruct": 1.5e-07, "scan": 1e-07,
                       "other": 5e-08},
        "top_modules": [["jit_batch_edge_two_phase_point", 1.5e-07],
                        ["jit_batch_hybrid_point", 1e-07],
                        ["jit_concatenate", 5e-08]],
        "idle_gaps": [["bench.flush", 4.5e-07], ["query", 1.5e-07],
                      ["bench.submit", 1e-07]]}


@pytest.mark.parametrize("op_name,path", [
    ("jit(f)/vmap(jit(reconstruct_dense))/replay/decide/jit(_where)/"
     "select_n", ("replay", "decide")),
    ("jit(f)/vmap(measure)/dot_general", ("measure",)),
    ("jit(batch_evolve)/vmap()/while/body/closed_call/measure/shift_left",
     ("measure",)),
    ("replay/scatter/broadcast_in_dim;replay/scatter/broadcast_in_dim",
     ("replay", "scatter")),
    ("jit(f)/vmap(jit(reconstruct_dense))", ()),
    ("delta.op", ()),
    (None, ()),
])
def test_scope_path_of_an_op_name(op_name, path):
    assert scopes.scope_path(op_name) == path


HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %neg.1 = s32[8]{0} negate(%param_0), metadata={op_name="jit(f)/replay/scatter/neg"}
  ROOT %scatter.4 = s32[8]{0} scatter(%neg.1, %neg.1)
}

%fused_computation.1 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  ROOT %max.2 = s32[8]{0} maximum(%param_0.1, %param_0.1), metadata={op_name="jit(f)/vmap(measure)/max"}
}

ENTRY %main.9 (x: s32[8]) -> (s32[8], s32[8]) {
  %x = s32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion = s32[8]{0} fusion(%x), kind=kCustom, calls=%fused_computation
  %copy.3 = s32[8]{0} copy(%fusion)
  %fusion.1 = s32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple = (s32[8]{0}, s32[8]{0}) tuple(%fusion, %fusion.1)
}
"""


def test_hlo_scopes_resolve_fusions_by_their_root():
    m = scopes.hlo_scopes(HLO)
    # a fusion whose root lost its metadata: the nearest scoped
    # instruction before the root; a fusion with a scoped root: its own
    assert m["fusion"] == ("replay", "scatter")
    assert m["fusion.1"] == ("measure",)
    assert m["neg.1"] == ("replay", "scatter")
    # an instruction without a scope stays without one
    assert m["copy.3"] == () and m["x"] == () and m["tuple"] == ()


def test_hlo_of_a_profiled_program_carries_its_scopes(tmp_path):
    """The protobuf reading of a real (CPU) profile: the program's
    optimized HLO comes back from the trace's metadata plane with its
    op names."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped_program(x):
        with jax.named_scope("replay"):
            return (x * 2).sum()

    x = jnp.ones(64)
    scoped_program(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.profile"):
            scoped_program(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as fh:
        raw = fh.read()
    (meta,) = [m for _, m, _ in scopes._planes(
        raw, lambda n: n == "/host:metadata")]
    protos = {name: st["Hlo Proto"] for name, _, st in meta.values()}
    (prog,) = [p for p in protos if p.startswith("jit_scoped_program(")]
    module = dict(scopes._fields(protos[prog]))[1]
    found = scopes.hlo_scopes(scopes.hlo_text(module))
    assert ("replay",) in found.values()
    tr = scopes.from_xplane(path)            # no device plane on a CPU
    assert tr["ops"] == [] and tr["end_ns"] > tr["start_ns"]


# ---------------------------------------------------------------------------
# the readers, on a synthetic context
# ---------------------------------------------------------------------------


def _record(done):
    r = Record(None, 0.0)
    r.sent, r.done, r.value = 0.0, done, 1
    return r


def _hist(s, n):
    return {"histograms": {"jax_compile_seconds": {"": {"sum": s,
                                                        "count": n}}}}


def _ctx(**kw):
    cell = spec.load(benchkit.ROOT, benchkit.B1)
    base = dict(cell=cell, seconds=10.0, setup_s=1.0,
                records=[_record(t) for t in (1.0, 2.5, 3.0, 3.5, 9.0)],
                window=(0.0, 10.0), profiled=(2.0, 4.0),
                profile={"busy_s": 4e-7})
    base.update(kw)
    return Context(**base)


def _reader(name):
    return spec.metric_reader(spec.load(benchkit.ROOT, benchkit.B1), name)


def test_scope_readers_per_answered_request():
    d, ops = _scoped()
    ctx = _ctx()
    vars(ctx)["_scopes"] = scopes.reduce(ops, 0, 1000, family=FAMILY)
    # three requests answered in the profiled part of the window
    assert _reader("replay_device_ms")(ctx) == pytest.approx(
        200e-9 / 3 * 1e3)
    assert _reader("measure_device_ms")(ctx) == pytest.approx(
        150e-9 / 3 * 1e3)


def test_scope_readers_report_nothing_without_scopes():
    ctx = _ctx()
    vars(ctx)["_scopes"] = None          # a program without scopes
    assert _reader("replay_device_ms")(ctx) is None
    assert _reader("measure_device_ms")(ctx) is None
    ctx = _ctx(profile=None, profiled=None)
    assert _reader("replay_device_ms")(ctx) is None


def test_window_compile_s_reads_the_compile_histogram():
    read = _reader("window_compile_s")
    assert read(_ctx(reg0=_hist(1.5, 3), reg1=_hist(4.0, 4))) == \
        pytest.approx(2.5)
    assert read(_ctx(reg0=_hist(1.5, 3), reg1=_hist(1.5, 3))) == 0.0
    # a program that does not feed the histogram: nothing to read
    assert read(_ctx(reg0={"histograms": {}},
                     reg1={"histograms": {}})) is None


def test_replay_fill_pct_reads_window_delta_spans():
    read = _reader("replay_fill_pct")
    spans = [
        {"name": "window_delta", "args": {"cap": 1024, "padded": 2,
                                          "replays": 1, "own_ops": 512}},
        {"name": "window_delta", "args": {"cap": 512, "padded": 1,
                                          "replays": 2, "own_ops": 256}},
        {"name": "window_delta", "args": {"plan": "hybrid"}},
        {"name": "query", "args": {}},
    ]
    assert read(_ctx(spans=spans)) == pytest.approx(
        100 * (512 + 256) / (2048 + 1024))
    assert read(_ctx(spans=spans[2:])) is None
    assert read(_ctx(spans=None)) is None
