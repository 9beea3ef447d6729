"""Device time by DC-ELM phase (``bench/scopes.py``) and the readers of
the phase metrics, pinned on a small trace recorded on a TPU v5 lite.

The recording (``data/scoped_trace.json.gz``: the device ops that
``scopes.load`` keeps of an ``.xplane.pb``, each with its ``op_name``,
and the harness's window) holds, under the benchmark's host spans: one
8-node ``stream_init`` at 2,048 rows a node (the fused stats kernel, the
Cholesky Omega, the re-seed), 40 dense rounds to the residual, one
256-node chunk (feature map, Woodbury add, re-seed, 10 gossip-kernel
rounds) and 20 more kernel rounds, and one stats pass over a feature map
the kernel does not take, so that ``features`` nests inside ``stats``.
"""

import gzip
import json
import types
from pathlib import Path

import pytest
from bench_tiny import REPO  # noqa: F401  (puts the repo on sys.path)

from bench import harness, scopes, trace

DATA = Path(__file__).parent / "data" / "scoped_trace.json.gz"
NEW = {
    "mnist64.learn": ["stats_ms.learn", "omega_ms.learn", "round_us.learn",
                      "unscoped_share.learn"],
    "rgg1024.stream": ["woodbury_ms.stream", "round_us.stream",
                       "unscoped_share.stream"],
}


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def _ops(recorded):
    return [op for ops in recorded["devices"].values() for op in ops]


def test_innermost_phase_wins():
    assert scopes.phase_of("jit(f)/vmap(dcelm/stats)/jit(elm_stats_pallas)/pad") == "stats"
    assert scopes.phase_of(
        "jit(f)/dcelm/stats/dcelm/features/dot_general"
    ) == "features"
    assert scopes.phase_of("jit(settle)/while/body/jit(consensus_error)/sqrt") is None
    assert scopes.phase_of("jit(f)/dcelm/statsplus/add") is None
    assert scopes.phase_of("") is None


def test_window_clips_and_containers_drop_out():
    ops = [
        [0, 100, "fusion.1", "fusion", "jit(f)/dcelm/rounds/mul"],
        [100, 100, "while.2", "while", "jit(f)/dcelm/rounds/while"],
        [150, 100, "copy.3", "copy", "jit(f)/dcelm/stats/copy"],
        [300, 50, "add.4", "add", "jit(f)/add"],
        [400, 10, "add.5", "add", "jit(f)/dcelm/omega/add"],
    ]
    p = scopes.Phases({"devices": {"0": ops}}, lo=50, hi=320)
    assert p["rounds"] == pytest.approx(50e-9)  # [50, 100) of [0, 100)
    assert p["stats"] == pytest.approx(100e-9)
    assert p["omega"] == 0.0  # after the window
    assert p.unscoped_s == pytest.approx(20e-9)  # [300, 320)
    assert p.busy_s == pytest.approx(170e-9)  # the while loop is not counted


#: device seconds by phase inside the recording's window
EXPECTED = {
    "features": 0.00010199, "stats": 0.002877045, "omega": 0.016190035,
    "reseed": 0.000213314, "woodbury": 0.001372676, "rounds": 0.005532204,
}
EXPECTED_UNSCOPED = 0.001338307


def test_known_seconds_per_phase(recorded):
    lo, hi = recorded["window"]
    p = scopes.Phases(recorded, lo, hi)
    for phase, seconds in EXPECTED.items():
        assert p[phase] == pytest.approx(seconds, rel=1e-9), phase
    assert p.unscoped_s == pytest.approx(EXPECTED_UNSCOPED, rel=1e-9)
    # the busy op time, clipped to the window, is all in some bucket
    clipped = sum(
        max(0.0, min(s + d, hi) - max(s, lo))
        for s, d, _, kind, _ in _ops(recorded) if kind not in trace.CONTAINERS
    )
    assert p.busy_s == pytest.approx(clipped / 1e9, rel=1e-12)
    assert p.scoped_s + p.unscoped_s == pytest.approx(p.busy_s, rel=1e-12)


def test_ops_across_the_window_start_count_in_part(recorded):
    lo, hi = recorded["window"]
    straddle = [op for op in _ops(recorded) if op[0] < lo < op[0] + op[1]]
    assert straddle  # the profiler's clocks put the first copy of X across it
    whole = sum(op[1] for op in _ops(recorded) if op[3] not in trace.CONTAINERS)
    p = scopes.Phases(recorded, lo, hi)
    assert p.busy_s < whole / 1e9
    later = scopes.Phases(recorded, lo + 1e6, hi)  # one ms later
    assert later.busy_s < p.busy_s
    assert scopes.Phases(recorded, hi, hi).busy_s == 0.0


def test_containers_are_left_out(recorded):
    ops = _ops(recorded)
    loops = [op for op in ops if op[3] in trace.CONTAINERS]
    assert loops  # the round loops and the residual loop are while ops
    # a window over the whole recording
    p = scopes.Phases(recorded, min(op[0] for op in ops),
                      max(op[0] + op[1] for op in ops))
    every = sum(op[1] for op in ops) / 1e9
    assert every - p.busy_s == pytest.approx(sum(op[1] for op in loops) / 1e9)


def test_kernels_sit_in_their_phases(recorded):
    kernels = {}
    for _, _, name, kind, op_name in _ops(recorded):
        if kind == "tpu_custom_call":
            kernels.setdefault(scopes.phase_of(op_name), set()).add(name.split(".")[0])
    assert kernels == {"stats": {"elm_stats_pallas"}, "rounds": {"elm_gossip_pallas"}}


def test_features_nested_in_stats_count_as_features(recorded):
    nested = [op for op in _ops(recorded)
              if scopes.phase_of(op[4]) == "features" and "dcelm/stats" in op[4]]
    assert nested
    lo, hi = recorded["window"]
    assert scopes.Phases(recorded, lo, hi)["features"] >= sum(
        op[1] for op in nested) / 1e9


def _ctx(cell, recorded, counters):
    lo, hi = recorded["window"]
    return harness.MetricContext(
        cell=cell, config={}, traffic={}, peak=None, chips=1,
        counters=counters, trace=types.SimpleNamespace(lo=lo, hi=hi),
    )


def _read(name, ctx):
    return harness.load_reader(name).read(ctx)


def test_readers_on_the_recording(recorded, monkeypatch):
    monkeypatch.setattr(scopes, "events_for", lambda cell_dir: recorded)
    lo, hi = recorded["window"]
    p = scopes.Phases(recorded, lo, hi)
    learn = _ctx("mnist64.learn", recorded, {"jobs": 2, "rounds_per_job": [30, 40]})
    assert _read("stats_ms.learn", learn) == pytest.approx(
        1e3 * (p["stats"] + p["features"]) / 2)
    assert _read("omega_ms.learn", learn) == pytest.approx(
        1e3 * (p["omega"] + p["reseed"]) / 2)
    assert _read("round_us.learn", learn) == pytest.approx(1e6 * p["rounds"] / 70)
    share = _read("unscoped_share.learn", learn)
    assert share == pytest.approx(100 * p.unscoped_s / p.busy_s)
    assert 0 < share < 100
    stream = _ctx("rgg1024.stream", recorded, {"chunks": 4, "rounds": 80})
    assert _read("woodbury_ms.stream", stream) == pytest.approx(
        1e3 * (p["features"] + p["woodbury"] + p["reseed"]) / 4)
    assert _read("round_us.stream", stream) == pytest.approx(1e6 * p["rounds"] / 80)
    assert _read("unscoped_share.stream", stream) == pytest.approx(share)


@pytest.mark.parametrize("cell", sorted(NEW))
def test_readers_report_nothing_without_phases(recorded, monkeypatch, cell):
    """A program without the scopes (as the parent commit's) has no
    phase: every reader returns None and none raises."""
    bare = {"devices": {k: [op[:4] + [op[4].replace("dcelm/", "")] for op in ops]
                        for k, ops in recorded["devices"].items()}}
    monkeypatch.setattr(scopes, "events_for", lambda cell_dir: bare)
    ctx = _ctx(cell, recorded, {"jobs": 1, "rounds_per_job": [1], "chunks": 1,
                                "rounds": 1})
    assert all(_read(name, ctx) is None for name in NEW[cell])


@pytest.mark.parametrize("name,phase", [
    ("stats_ms.learn", "stats"), ("omega_ms.learn", "omega"),
    ("round_us.learn", "rounds"), ("woodbury_ms.stream", "woodbury"),
    ("round_us.stream", "rounds"),
])
def test_a_reader_without_its_phase_reports_nothing(recorded, monkeypatch, name, phase):
    keep = {k: [op for op in ops if scopes.phase_of(op[4]) != phase]
            for k, ops in recorded["devices"].items()}
    monkeypatch.setattr(scopes, "events_for", lambda cell_dir: {"devices": keep})
    cell = "mnist64.learn" if name.endswith(".learn") else "rgg1024.stream"
    ctx = _ctx(cell, recorded, {"jobs": 1, "rounds_per_job": [1], "chunks": 1,
                                "rounds": 1})
    assert _read(name, ctx) is None


def test_no_profile_means_no_phases(tmp_path):
    assert scopes.events_for(tmp_path) is None
    ctx = types.SimpleNamespace(cell="no-such-cell", trace=None)
    assert scopes.for_cell(ctx) is None


def _pb(field, value):
    """One protobuf field: an int is a varint, bytes or str length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def _entry(field, key, message):
    """One entry of a map<int64, message>."""
    return _pb(field, _pb(1, key) + _pb(2, message))


def test_op_names_from_event_metadata():
    """``tf_op`` lives on the event metadata of a plane (XPlane field 4),
    as a string or as a reference to a stat metadata's name."""
    entry = _entry

    stat_meta = entry(5, 7, _pb(1, 7) + _pb(2, "tf_op")) + entry(
        5, 8, _pb(1, 8) + _pb(2, "jit(f)/dcelm/rounds/mul"))
    events = (
        entry(4, 1, _pb(1, 1) + _pb(2, "%fusion.1 = f32[2] fusion()")
              + _pb(5, _pb(1, 7) + _pb(5, "jit(f)/dcelm/stats/dot")))
        + entry(4, 2, _pb(1, 2) + _pb(2, "%mul.2 = f32[2] multiply()")
                + _pb(5, _pb(1, 7) + _pb(7, 8)))
        + entry(4, 3, _pb(1, 3) + _pb(2, "%copy.3 = f32[2] copy()"))
    )
    tpu = _pb(1, 1) + _pb(2, "/device:TPU:0") + _pb(3, b"\x08\x01") + events
    host = _pb(2, "/host:CPU") + entry(4, 1, _pb(2, "python"))
    space = _pb(1, tpu + stat_meta) + _pb(1, host) + _pb(4, "a-host")
    assert scopes.op_names(space) == {
        "/device:TPU:0": {
            "%fusion.1 = f32[2] fusion()": "jit(f)/dcelm/stats/dot",
            "%mul.2 = f32[2] multiply()": "jit(f)/dcelm/rounds/mul",
        },
        "/host:CPU": {},
    }


def test_load_joins_op_names_to_the_device_ops(tmp_path, monkeypatch):
    """A profile as ``trace.Recorder`` leaves it: the ops of a TPU's
    ``XLA Ops`` line, each with the ``op_name`` of its instruction."""
    fusion, mul = "%fusion.1 = f32[2] fusion()", "%mul.2 = f32[2] multiply()"
    tf_op = _entry(5, 7, _pb(1, 7) + _pb(2, "tf_op"))
    meta = (
        _entry(4, 1, _pb(1, 1) + _pb(2, fusion)
               + _pb(5, _pb(1, 7) + _pb(5, "jit(f)/dcelm/omega/dot")))
        + _entry(4, 2, _pb(1, 2) + _pb(2, mul))
    )
    line = (_pb(1, 1) + _pb(2, "XLA Ops") + _pb(3, 1000)
            + _pb(4, _pb(1, 1) + _pb(2, 5_000) + _pb(3, 2_000_000))
            + _pb(4, _pb(1, 2) + _pb(2, 3_000_000) + _pb(3, 1_000_000)))
    plane = _pb(1, 1) + _pb(2, "/device:TPU:0") + _pb(3, line) + meta + tf_op
    run = tmp_path / "mnist64.learn" / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_pb(1, plane))
    assert scopes.load(str(run / "host.xplane.pb")) == {"devices": {"0": [
        [1005.0, 2000.0, "fusion.1", "fusion", "jit(f)/dcelm/omega/dot"],
        [4000.0, 1000.0, "mul.2", "multiply", ""],
    ]}}
    monkeypatch.setattr(scopes, "TRACES", tmp_path)
    ctx = types.SimpleNamespace(
        cell="mnist64.learn", trace=types.SimpleNamespace(lo=0, hi=1e4)
    )
    p = scopes.for_cell(ctx)
    assert p["omega"] == pytest.approx(2e-6) and p.unscoped_s == pytest.approx(1e-6)
