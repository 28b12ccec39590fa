"""The per-scope trace reduction (``bench/scopes.py``) on a compiled
program's text and a trace written by hand in the TPU's layout, and on the
recorded traces of ``bench/testdata``."""

import os

import pytest

from bench import cells
from bench import scopes as SC
from bench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
US = 1e-6

HLO = """
HloModule jit_wrapped

%fused_computation.5 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(wrapped)/train.optimizer/mul"}
}

%body.1 (t: (s32[])) -> (s32[]) {
  %t = (s32[]) parameter(0)
  %copy.1 = f32[8]{0} copy(f32[8]{0} %t)
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %copy.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(wrapped)/train.optimizer/mul"}
}

ENTRY %main (a: f32[8], w: u8[1,512], h: f32[2048]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %w = u8[1,512]{1,0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.1, metadata={op_name="jit(wrapped)/jvp(model.blocks)/while/body/closed_call/model.mixer/dot_general"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.2, metadata={op_name="jit(wrapped)/transpose(jvp(model.blocks))/while/body/closed_call/checkpoint/rematted_computation/model.mixer/tanh"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.3, metadata={op_name="jit(wrapped)/transpose(jvp(model.blocks))/while/body/closed_call/checkpoint/model.mixer/mul"}
  %all-gather-start.1 = (u8[1,512]{1,0}, u8[4,512]{1,0}) all-gather-start(u8[1,512]{1,0} %w), dimensions={0}, metadata={op_name="jit(wrapped)/diana.round/diana.allgather/all_gather"}
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fc.4, metadata={op_name="jit(wrapped)/diana.round/diana.decode_own/mul"}
  %all-gather-done.1 = u8[4,512]{1,0} all-gather-done((u8[1,512]{1,0}, u8[4,512]{1,0}) %all-gather-start.1), metadata={op_name="jit(wrapped)/diana.round/diana.allgather/all_gather"}
  %unpack_reduce_apply.1 = (f32[1,2048]{1,0}, f32[1,2048]{1,0}) custom-call(u8[4,512]{1,0} %all-gather-done.1, f32[2048]{0} %h), custom_call_target="tpu_custom_call", metadata={op_name="jit(wrapped)/diana.round/diana.decode_sum_apply/unpack_reduce_apply/pallas_call"}
  %while.1 = (s32[]) while((s32[]) %t), condition=%cond.1, body=%body.1, metadata={op_name="jit(wrapped)/jvp(model.blocks)/while"}
  ROOT %copy.2 = f32[8]{0} copy(f32[8]{0} %a)
}
"""

# (instruction, start us, duration us) on the one chip's ops line: two steps
# of 50 us; the gather is in flight over [40, 48] while fusion.4 runs [41, 45].
OPS = [
    ("fusion.1", 2, 20), ("fusion.2", 22, 10), ("fusion.3", 32, 8),
    ("all-gather-start.1", 40, 1), ("fusion.4", 41, 4), ("all-gather-done.1", 45, 3),
    ("unpack_reduce_apply.1", 48, 1),
    ("while.1", 52, 38), ("copy.1", 52, 8), ("fusion.5", 60, 28),
    ("copy.2", 92, 8),
]
HOST = [
    ("bench.window", 0, 100), ("bench.step", 0, 50), ("bench.step", 50, 50),
    ("bench.feed", 0, 0.4), ("bench.feed", 50, 1), ("bench.block", 1.5, 48),
    ("bench.block", 51.5, 48), ("PjitFunction(jit(wrapped))", 0.5, 0.7),
    ("PjitFunction(jit(wrapped))", 50.5, 0.7),
    ("CommonPjRtLoadedExecutable::Execute", 50.6, 0.5),
]


def _instruction_text(name):
    line = next(l for l in HLO.splitlines() if l.strip().startswith(f"%{name} ")
                or l.strip().startswith(f"ROOT %{name} "))
    return line.strip().replace("ROOT ", "").split(", metadata=")[0]


def _pbtxt(planes):
    """A trace in ``ProfileData``'s text format: [(plane, {line: [(event
    name, start us, duration us)]})]."""
    out = []
    for pid, (plane, lines) in enumerate(planes, 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        out.append(f'planes {{ id: {pid} name: "{plane}"')
        for lid, (line, evs) in enumerate(lines.items(), 1):
            out.append(f'  lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
            for n, a, d in evs:
                out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: {round(a * 1e6)}"
                           f" duration_ps: {round(d * 1e6)} }}")
            out.append("  }")
        for n, i in ids.items():
            esc = n.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{esc}" }} }}')
        out.append("}")
    return "\n".join(out)


def _trace(shift_us=0.0, modules=((1.0, 48.0), (51.0, 48.0))):
    from jax.profiler import ProfileData

    dev = {
        "XLA Ops": [(_instruction_text(n), a + shift_us, d) for n, a, d in OPS],
        "Async XLA Ops": [(_instruction_text("all-gather-start.1"), 40 + shift_us, 8)],
        "XLA Modules": [("jit_wrapped(1)", a + shift_us, d) for a, d in modules],
    }
    return ProfileData.from_text_proto(_pbtxt([("/device:TPU:0", dev),
                                                ("/host:CPU", {"python": HOST})]))


def test_scope_of_reads_innermost_scope_and_phase():
    assert SC.scope_of("jit(f)/jvp(model.blocks)/while/body/model.mixer/dot") == \
        ("model.mixer", "forward")
    assert SC.scope_of("jit(f)/transpose(jvp(model.blocks))/checkpoint/model.mixer/mul") == \
        ("model.mixer", "backward")
    assert SC.scope_of("jit(f)/transpose(jvp(model.head_loss))/checkpoint/"
                       "rematted_computation/dot") == ("model.head_loss", "recompute")
    assert SC.scope_of("jit(f)/diana.round/diana.decode_sum_apply/pallas_call") == \
        ("diana.decode_sum_apply", "forward")
    assert SC.scope_of("jit(f)/add") == (SC.UNSCOPED, "forward")
    # an argument's name is not a scope
    assert SC.scope_of("opt_state.diana.h_worker") == (SC.UNSCOPED, "forward")


def test_instruction_scopes_inherit_from_the_caller():
    s = SC.instruction_scopes(HLO)
    assert s["fusion.1"] == ("model.mixer", "forward")
    assert s["fusion.2"] == ("model.mixer", "recompute")
    assert s["fusion.3"] == ("model.mixer", "backward")
    assert s["unpack_reduce_apply.1"] == ("diana.decode_sum_apply", "forward")
    # no metadata: takes the scope of the while that runs its body
    assert s["copy.1"] == ("model.blocks", "forward")
    assert s["mul.3"] == ("train.optimizer", "forward")
    assert s["copy.2"] == (SC.UNSCOPED, "forward")


def test_gathered_bytes_exclude_the_own_row():
    s = SC.instruction_scopes(HLO)
    assert SC.gathered_bytes(HLO, s) == 3 * 512
    # the compiled TPU text prints operands by name, and a context scalar
    tpu = HLO.replace(
        "(u8[1,512]{1,0}, u8[4,512]{1,0}) all-gather-start(u8[1,512]{1,0} %w)",
        "(u8[1,512]{1,0:T(8,128)}, u8[4,512]{1,0:T(8,128)}, u32[]{:S(2)}) "
        "all-gather-start(%w)")
    assert "all-gather-start(%w)" in tpu
    assert SC.gathered_bytes(tpu, SC.instruction_scopes(tpu)) == 3 * 512
    assert SC.gathered_bytes(HLO, s, scope="diana.encode") == 0


def test_scope_self_times_sum_to_busy():
    pd = _trace()
    red = SC.reduce_scopes(pd, HLO)
    old = TR.reduce(pd, TR.classify_hlo(HLO))
    by = red["scope_ms_per_step"]
    ms = 1e-3                                    # per step, in ms: us / 2 steps
    assert by["model.mixer"] == pytest.approx(
        {"forward": 10 * ms, "recompute": 5 * ms, "backward": 4 * ms})
    assert by["diana.allgather"]["forward"] == pytest.approx(2 * ms)
    assert by["diana.decode_own"]["forward"] == pytest.approx(2 * ms)
    assert by["diana.decode_sum_apply"]["forward"] == pytest.approx(0.5 * ms)
    # the loop's own self time (2 us) and its body's copy (8 us)
    assert by["model.blocks"]["forward"] == pytest.approx(5 * ms)
    assert by["train.optimizer"]["forward"] == pytest.approx(14 * ms)
    assert by[SC.UNSCOPED]["forward"] == pytest.approx(4 * ms)
    total = sum(v for p in by.values() for v in p.values())
    assert total * red["steps"] * 1e-3 == pytest.approx(old["busy_s"], rel=1e-9)
    assert old["busy_s"] == pytest.approx(93 * US)


def test_exposed_and_hidden_gather():
    red = SC.reduce_scopes(_trace(), HLO)
    # in flight [40, 48]; fusion.4 runs [41, 45] beside it: 4 us exposed
    assert red["allgather_inflight_ms"] == pytest.approx(8e-3 / 2)
    assert red["allgather_exposed_ms"] == pytest.approx(4e-3 / 2)
    assert red["allgather_bytes_per_step"] == 3 * 512


def test_gather_in_flight_from_its_start_to_its_done():
    """Without an ``Async XLA Ops`` line the gather is in flight from its
    start to its done on the ops line."""
    from jax.profiler import ProfileData

    ops = [(_instruction_text(n), a, d) for n, a, d in OPS]
    pd = ProfileData.from_text_proto(_pbtxt([("/device:TPU:0", {"XLA Ops": ops}),
                                             ("/host:CPU", {"python": HOST})]))
    red = SC.reduce_scopes(pd, HLO)
    assert red["allgather_inflight_ms"] == pytest.approx(8e-3 / 2)
    assert red["allgather_exposed_ms"] == pytest.approx(4e-3 / 2)


# The TPU compiler's split of one gather: an async start, a continuation
# fused into a compute fusion (here the head's matmul), and a done; all three
# carry the gather's channel.
TPU_HLO = """
HloModule jit_wrapped

%start_computation (p0: u8[1,512]) -> (u8[1,512], u8[4,512], u32[]) {
  %p0 = u8[1,512]{1,0} parameter(0)
  %all-gather.5 = u8[4,512]{1,0} all-gather(%p0), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(wrapped)/diana.round/diana.allgather/all_gather"}
  ROOT %custom-call.1 = (u8[1,512]{1,0}, u8[4,512]{1,0}, u32[]) custom-call(%all-gather.5), custom_call_target="AsyncCollectiveStart"
}

%carry_computation (p1: u8[1,512], x: bf16[8,64], w: bf16[64,128]) -> (bf16[8,128], u8[4,512]) {
  %p1 = u8[1,512]{1,0} parameter(0)
  %x = bf16[8,64]{1,0} parameter(1)
  %w = bf16[64,128]{1,0} parameter(2)
  %convolution.1 = bf16[8,128]{1,0} convolution(%x, %w), dim_labels=bf_io->bf, metadata={op_name="jit(wrapped)/jvp(model.head_loss)/dot_general"}
  %all-gather.7 = u8[4,512]{1,0} all-gather(%p1), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(wrapped)/diana.round/diana.allgather/all_gather"}
  ROOT %tuple.1 = (bf16[8,128]{1,0}, u8[4,512]{1,0}) tuple(%convolution.1, %all-gather.7)
}

%done_computation (p2: u8[1,512]) -> u8[4,512] {
  %p2 = u8[1,512]{1,0} parameter(0)
  %all-gather.9 = u8[4,512]{1,0} all-gather(%p2), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(wrapped)/diana.round/diana.allgather/all_gather"}
  ROOT %custom-call.2 = u8[4,512]{1,0} custom-call(%p2, %all-gather.9), custom_call_target="AsyncCollectiveDone"
}

ENTRY %main (w: u8[1,512], x: bf16[8,64], h: bf16[64,128]) -> u8[4,512] {
  %w = u8[1,512]{1,0} parameter(0)
  %x = bf16[8,64]{1,0} parameter(1)
  %h = bf16[64,128]{1,0} parameter(2)
  %async-collective-start = (u8[1,512]{1,0}, u8[4,512]{1,0}, u32[]) fusion(%w), kind=kCustom, calls=%start_computation
  %fusion.9 = (bf16[8,128]{1,0}, u8[4,512]{1,0}) fusion(%w, %x, %h), kind=kOutput, calls=%carry_computation, metadata={op_name="jit(wrapped)/jvp(model.head_loss)/dot_general"}
  ROOT %async-collective-done = u8[4,512]{1,0} fusion(%w), kind=kCustom, calls=%done_computation, metadata={op_name="jit(wrapped)/diana.round/diana.allgather/all_gather"}
}
"""


def test_gather_carried_by_a_compute_fusion_is_hidden():
    from jax.profiler import ProfileData

    s = SC.instruction_scopes(TPU_HLO)
    # no metadata on the async start: the scope of what it calls
    assert s["async-collective-start"] == ("diana.allgather", "forward")
    assert s["fusion.9"] == ("model.head_loss", "forward")
    assert SC.pure_gathers(TPU_HLO) >= {"async-collective-start", "async-collective-done"}
    assert "fusion.9" not in SC.pure_gathers(TPU_HLO)
    assert SC.gathered_bytes(TPU_HLO, s) == 3 * 512          # one channel, once

    def text(name):
        return next(l.strip().replace("ROOT ", "") for l in TPU_HLO.splitlines()
                    if l.strip().replace("ROOT ", "").startswith(f"%{name} "))

    ops = [(text("async-collective-start"), 0, 1), (text("fusion.9"), 2, 8),
           (text("async-collective-done"), 10, 0.5)]
    host = [("bench.window", 0, 20), ("bench.step", 0, 20)]
    pd = ProfileData.from_text_proto(_pbtxt([("/device:TPU:0", {"XLA Ops": ops}),
                                             ("/host:CPU", {"python": host})]))
    red = SC.reduce_scopes(pd, TPU_HLO)
    assert red["allgather_inflight_ms"] == pytest.approx(10.5e-3)
    # the start, the done and the gap between them: not the carrying fusion
    assert red["allgather_exposed_ms"] == pytest.approx(2.5e-3)


def test_idle_gap_causes():
    red = SC.reduce_scopes(_trace(), HLO)
    old = TR.reduce(_trace(), TR.classify_hlo(HLO))
    causes = red["idle_gap_causes"]
    assert [c["ms"] * 1e-3 for c in causes] == pytest.approx(
        [g[1] for g in old["breakdown"]["idle_gaps"]])
    widest = causes[0]                           # [49, 52]: between the steps
    assert widest["ms"] == pytest.approx(3e-3)
    assert widest["bench_span"] == "bench.feed"
    assert widest["host_event"] == "PjitFunction(jit(wrapped))"
    assert widest["scope_before"] == "diana.decode_sum_apply/forward"
    assert widest["scope_after"] == "model.blocks/forward"   # copy.1, in the loop
    first = next(c for c in causes if c["scope_before"] is None)   # [0, 2]
    assert first["scope_after"] == "model.mixer/forward"


def test_clock_skew_reads_a_planted_offset():
    red = SC.reduce_scopes(_trace(), HLO)
    assert red["clock_skew_us"] == 0
    assert red["launch_to_run_us"] == pytest.approx([0.5, 0.5], abs=1e-6)
    # the device's clock 0.8 us early: each run starts 0.3 us before its launch
    red = SC.reduce_scopes(_trace(shift_us=-0.8), HLO)
    assert red["clock_skew_us"] == pytest.approx(0.3, abs=1e-6)
    assert red["launch_to_run_us"] == pytest.approx([-0.3, -0.3], abs=1e-6)
    # no module events: nothing to check against
    assert SC.reduce_scopes(_trace(modules=()), HLO)["clock_skew_us"] is None


def test_layer_metrics():
    lm = SC.layer_metrics(SC.reduce_scopes(_trace(), HLO))
    ms = 1e-3
    assert lm["forward_ms"] == pytest.approx((10 + 5) * ms)     # mixer + loop
    assert lm["backward_ms"] == pytest.approx((5 + 4) * ms)     # recompute included
    assert lm["optimizer_ms"] == pytest.approx(14 * ms)
    assert lm["diana_round_ms"] == pytest.approx((2 + 2 + 0.5) * ms)
    assert lm["diana_decode_ms"] == pytest.approx(0.5 * ms)
    assert lm["allgather_exposed_ms"] == pytest.approx(2 * ms)
    assert lm["allgather_mb"] == pytest.approx(1536 / 1e6)


def test_layer_metrics_read_nothing_of_scopes_the_step_lacks():
    """A step with no DIANA round and no gather (operator ``none`` on one
    chip): its sums over absent scopes read None, not 0."""
    red = {"scope_ms_per_step": {"model.mixer": {"forward": 3.0, "backward": 5.0},
                                 "train.optimizer": {"forward": 0.5}},
           "allgather_bytes_per_step": 0.0, "allgather_exposed_ms": 0.0}
    assert SC.layer_metrics(red) == {
        "forward_ms": 3.0, "backward_ms": 5.0, "optimizer_ms": 0.5, "diana_round_ms": None,
        "diana_decode_ms": None, "allgather_exposed_ms": None, "allgather_mb": None}


SCOPE_METRICS = ["forward_ms","backward_ms", "optimizer_ms", "diana_round_ms",
                 "diana_decode_ms", "allgather_exposed_ms", "allgather_mb"]


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_reader(name):
    """Each reader ``bench/metrics/<name>.py`` reads the traced run's
    reduction by scope from the context, and nothing in an untraced run."""
    red = SC.reduce_scopes(_trace(), HLO)
    read = cells.load_metric_reader(name)
    assert read({"trace": None, "scopes": red}) == SC.layer_metrics(red)[name]
    assert read({"trace": None, "scopes": red}) > 0
    assert read({"trace": None, "scopes": None}) is None
    # a program without scopes: nothing to read
    pd = TR.load(os.path.join(DATA, "trace_g1.pbtxt"))
    assert read({"trace": None, "scopes": SC.reduce_scopes(pd, "")}) is None


@pytest.mark.parametrize("name", ["trace_hand.pbtxt", "trace_g1.pbtxt"])
def test_recorded_traces_without_scopes(name):
    """A program without scopes (the recorded traces' HLO is not kept)
    reads all of its time as unscoped, and every metric as None."""
    pd = TR.load(os.path.join(DATA, name))
    red = SC.reduce_scopes(pd, "")
    old = TR.reduce(pd, {"kernel": set(), "gather": set()})
    assert set(red["scope_ms_per_step"]) == {SC.UNSCOPED}
    total = sum(red["scope_ms_per_step"][SC.UNSCOPED].values())
    assert total * red["steps"] * 1e-3 == pytest.approx(old["busy_s"], rel=1e-9)
    assert set(SC.layer_metrics(red).values()) == {None}
    assert len(red["idle_gap_causes"]) == len(old["breakdown"]["idle_gaps"])


def test_replay_reads_a_kept_trace(tmp_path, capsys):
    """``bench/scope_run.py --replay`` reduces what a ``--keep`` run wrote."""
    import gzip
    import json

    from jax.profiler import ProfileData

    from bench import scope_run

    dev = {"XLA Ops": [(_instruction_text(n), a, d) for n, a, d in OPS]}
    text = _pbtxt([("/device:TPU:0", dev), ("/host:CPU", {"python": HOST})])
    with gzip.open(tmp_path / scope_run.TRACE_FILE, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    with gzip.open(tmp_path / scope_run.HLO_FILE, "wt") as f:
        f.write(HLO)
    assert scope_run.main(["--replay", str(tmp_path)]) == 0
    red = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert red["checks"]["scopes_over_busy"] == pytest.approx(1.0)
    assert red["checks"]["unscoped_share"] == pytest.approx(4 / 46.5)
    assert red["layer_metrics"]["optimizer_ms"] == pytest.approx(14e-3)
