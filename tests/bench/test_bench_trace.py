"""The reduction from a device trace to busy time, kernel time and
gaps, pinned on a small trace recorded on a TPU v5 lite.

The recording (``data/probe_trace.json.gz``, the compact form that
``bench.trace.load`` makes of an ``.xplane.pb``) holds one 64-node
``stream_init`` at 4,096 rows a node (the fused stats kernel), 50 dense
eq. (20) rounds, 100 neighbor rounds of a 1024-node network (the gossip
kernel) and one ``ELMServer`` flush of five requests (the predict
kernel), each under a host span of the benchmark's names.
"""

import gzip
import json
from pathlib import Path

import pytest
from bench_tiny import REPO

from bench import harness, trace

DATA = Path(__file__).parent / "data" / "probe_trace.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return trace.Trace(json.load(f))


def test_busy_and_idle(recorded):
    # no window span in this recording: the window runs from the first
    # program's start to the last one's end
    assert recorded.window_s == pytest.approx(0.247722776, rel=1e-9)
    assert recorded.busy_s == pytest.approx(0.222633315, rel=1e-9)
    assert recorded.idle_share == pytest.approx(0.1012803966, rel=1e-6)


def test_kernel_time(recorded):
    assert recorded.op_seconds(trace.is_kernel) == pytest.approx(0.158067758, rel=1e-9)
    stats = recorded.op_seconds(
        lambda n, k: k == "tpu_custom_call" and "elm_stats_pallas" in n
    )
    assert stats == pytest.approx(0.082289441, rel=1e-9)


def test_no_collective_on_one_chip(recorded):
    assert recorded.op_seconds(lambda n, k: k.startswith("collective")) == 0.0


def test_breakdown_and_gap_attribution(recorded):
    b = recorded.breakdown()
    assert b["device_ops"][0] == ["vmap_jit_elm_stats_pallas__.1", pytest.approx(0.082289441)]
    assert b["device_ops"][1][0] == "closed_call.8"  # the gossip kernel's launches
    assert len(b["device_ops"]) == 10
    gaps = dict(b["idle_gaps"])
    # the host packing and dispatching one flush left the chip idle longest
    assert list(gaps) == ["flush", "stats", "rounds"]
    assert gaps["flush"] == pytest.approx(0.022718708, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=1e-9
    )


def test_stats_roofline_reader_on_the_recording(recorded):
    cfg = json.loads((REPO / "bench" / "configs" / "mnist64.json").read_text())
    cfg["Ni"] = 4096  # the recording's rows a node
    ctx = harness.MetricContext(
        cell="mnist64.learn", config=cfg, traffic={}, chips=1,
        peak=harness.load_peak("TPU v5 lite"), counters={"jobs": 1},
        trace=recorded,
    )
    share = harness.load_reader("stats_roofline.learn").read(ctx)
    # 64 x (2*4096*784*1024 + 4096*1024*1025 + 2*4096*1024*10) FLOPs at
    # 197 TFLOP/s (compute-bound) over 82.289441 ms
    flops = 64 * (2 * 4096 * 784 * 1024 + 4096 * 1024 * 1025 + 2 * 4096 * 1024 * 10)
    assert share == pytest.approx(100 * flops / 197e12 / 0.082289441, rel=1e-9)
    assert 0 < share < 100


def test_op_name_and_kind():
    assert trace.op_name_kind(
        '%fusion.11 = f32[64,1024,10]{1,2,0:T(8,128)S(1)} fusion(f32[64] %a), kind=kOutput'
    ) == ("fusion.11", "fusion")
    assert trace.op_name_kind(
        '%closed_call.8 = f32[8]{0} custom-call(f32[8] %x), custom_call_target="tpu_custom_call"'
    ) == ("closed_call.8", "tpu_custom_call")
    assert trace.op_name_kind(
        "%while.2 = (s32[]{:T(128)}, f32[2]{0}) while((s32[], f32[2]) %t), condition=%c"
    ) == ("while.2", "while")
