"""The trace reduction, on a trace written by hand in the TPU's layout and
on one recorded on a v5e (granite l4 on one chip, cut to one step)."""

import os

import pytest

from bench import trace as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
HLO = """
HloModule m

%fused_computation.9 (p: u8[1,516]) -> u8[4,516] {
  %p = u8[1,516]{1,0} parameter(0)
  ROOT %all-gather.3 = u8[4,516]{1,0} all-gather(u8[1,516]{1,0} %p), dimensions={0}
}

ENTRY %main (a: u8[1,516], k: s32[2], d: f32[1,2048]) -> u8[4,516] {
  %a = u8[1,516]{1,0} parameter(0)
  %k = s32[2]{0} parameter(1)
  %d = f32[1,2048]{1,0} parameter(2)
  %quantize_pack_prng.1 = (u8[1,512]{1,0}, f32[1,1]{1,0}) custom-call(s32[2]{0} %k, f32[1,2048]{1,0} %d), custom_call_target="tpu_custom_call"
  %while.1 = (s32[]) while((s32[]) %t), condition=%cond.1, body=%body.1
  ROOT %fusion.9 = u8[4,516]{1,0} fusion(u8[1,516]{1,0} %a), kind=kCustom, calls=%fused_computation.9
}
"""


def test_classify_hlo():
    c = TR.classify_hlo(HLO)
    assert c["kernel"] == {"quantize_pack_prng.1"}
    assert c["gather"] == {"all-gather.3", "fusion.9"}


def test_hand_trace():
    r = TR.reduce(TR.load(os.path.join(DATA, "trace_hand.pbtxt")),
                  {"kernel": set(), "gather": set()})
    us = 1e-6
    assert r["window_s"] == pytest.approx(100 * us)
    assert r["steps"] == 2 and r["chips_traced"] == 2
    # chip 0: [10, 60] u [70, 80] u [95, 100 (clipped)]; chip 1: all 100
    assert r["busy_s"] == pytest.approx((65 + 100) / 2 * us)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.175)
    # kernels 10 + 10 us on chip 0, none on chip 1
    assert r["kernel_s_per_chip"] == pytest.approx(10 * us)
    # all-gather start 2 + done 5 us on the ops line (the async line is not busy time)
    assert r["gather_s_per_chip"] == pytest.approx(3.5 * us)
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([15 * us, 10 * us, 10 * us])
    assert gaps[0][0] == "bench.block"
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.2"] == pytest.approx(50 * us)           # averaged over 2 chips
    assert ops["while.1"] == pytest.approx((50 - 18 - 10 - 2 - 5) / 2 * us)   # self time


def _brute_force(pd):
    """The same numbers by a sweep over every event boundary."""
    spans, devices = TR.events(pd)
    lo, hi = next((a, b) for n, a, b in spans if n == "bench.window")
    busy = []
    for ops in devices.values():
        cuts = sorted({lo, hi} | {min(max(t, lo), hi) for _, _, a, b in ops for t in (a, b)})
        total = 0.0
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            if any(x <= mid < y for _, _, x, y in ops):
                total += b - a
        busy.append(total)
    return hi - lo, sum(busy) / len(busy)


def test_recorded_trace():
    pd = TR.load(os.path.join(DATA, "trace_g1.pbtxt"))
    r = TR.reduce(pd, {"kernel": set(), "gather": set()})
    window, busy = _brute_force(pd)
    assert r["chips_traced"] == 1 and r["steps"] == 1
    assert r["window_s"] == pytest.approx(window)
    assert r["busy_s"] == pytest.approx(busy, rel=1e-9)
    spans, devices = TR.events(pd)
    lo, hi = next((a, b) for n, a, b in spans if n == "bench.window")
    kernels = [(f, min(b, hi) - max(a, lo)) for ops in devices.values()
               for _, f, a, b in ops if "tpu_custom_call" in f and b > lo and a < hi]
    assert len(kernels) == 2                    # encode and decode_sum_apply
    assert r["kernel_s_per_chip"] == pytest.approx(sum(t for _, t in kernels))
    assert r["gather_s_per_chip"] == 0          # one chip: no exchange
