"""The ring exchange's readers (``exchange_us.learn``,
``exchange_share.learn``) on a small synthetic profile: two chips, ops
of the sharded round with ``dcelm/exchange`` nested in ``dcelm/rounds``,
known durations."""

import types

import pytest
from bench_tiny import REPO  # noqa: F401  (puts the repo on sys.path)

from bench import harness, scopes

ROUND = "jit(settle)/while/body/dcelm/rounds/jit(scanned)/shard_map/while/body"
EXCHANGE = ROUND + "/closed_call/dcelm/exchange"


def _chip(offset):
    """[start_ns, dur_ns, name, kind, op_name] of one chip, inside a
    window of [1000, 9000)."""
    return [
        [offset + 0, 500, "fusion.1", "fusion", "jit(stats)/dcelm/stats/dot"],
        [offset + 1000, 300, "collective-permute-start.2",
         "collective-permute-start", EXCHANGE + "/ppermute"],
        [offset + 1300, 200, "collective-permute-done.3",
         "collective-permute-done", EXCHANGE + "/ppermute"],
        [offset + 1500, 100, "subtract.4", "subtract", EXCHANGE + "/sub"],
        [offset + 1600, 400, "fusion.5", "fusion", ROUND + "/dot_general"],
        [offset + 1000, 1000, "while.6", "while", ROUND + "/while"],
        [offset + 2000, 50, "fusion.7", "fusion", "jit(settle)/while/body/sqrt"],
    ]


EVENTS = {"devices": {"0": _chip(0), "1": _chip(2000)}}
WINDOW = (1000, 9000)


def _ctx(chips=2, rounds=(3, 2)):
    return harness.MetricContext(
        cell="mnist8m-silo4.learn", config={}, traffic={}, peak=None,
        chips=chips, counters={"jobs": len(rounds), "rounds_per_job": list(rounds)},
        trace=types.SimpleNamespace(lo=WINDOW[0], hi=WINDOW[1]),
    )


def _read(name, ctx):
    return harness.load_reader(name).read(ctx)


@pytest.fixture
def profile(monkeypatch):
    def use(events):
        monkeypatch.setattr(scopes, "events_for", lambda cell_dir: events)
    use(EVENTS)
    return use


def test_exchange_us_is_per_round_per_chip(profile):
    # 600 ns of exchange a chip (start, done, subtract), 5 rounds
    assert _read("exchange_us.learn", _ctx()) == pytest.approx(2 * 600 / 1e3 / 2 / 5)
    assert _read("exchange_us.learn", _ctx(rounds=(10,))) == pytest.approx(0.06)


def test_exchange_share_of_the_rounds(profile):
    # rounds: 600 ns exchanged and 400 ns of Omega product a chip
    assert _read("exchange_share.learn", _ctx()) == pytest.approx(60.0)


def test_the_window_clips_the_exchange(profile):
    # chip 0's ops before 1000 and chip 1's stats op are out; shift the
    # window so that chip 1's exchange start is cut in half
    events = {"devices": {"1": _chip(2000)}}
    profile(events)
    ctx = _ctx(chips=1, rounds=(1,))
    ctx.trace = types.SimpleNamespace(lo=3150, hi=9000)
    assert _read("exchange_us.learn", ctx) == pytest.approx(0.45)
    assert _read("exchange_share.learn", ctx) == pytest.approx(100 * 450 / 850)


def test_the_phase_metrics_still_count_the_exchange_as_rounds(profile):
    p = scopes.Phases(EVENTS, *WINDOW)
    assert p["rounds"] == pytest.approx(2 * 1000e-9)
    assert scopes.phase_of(EXCHANGE + "/ppermute") == "rounds"


@pytest.mark.parametrize("name", ["exchange_us.learn", "exchange_share.learn"])
def test_a_program_without_the_exchange_reports_nothing(profile, name):
    """The parent commit names no exchange: the readers return None."""
    bare = {"devices": {k: [op[:4] + [op[4].replace("/closed_call/dcelm/exchange", "")]
                            for op in ops] for k, ops in EVENTS["devices"].items()}}
    profile(bare)
    assert _read(name, _ctx()) is None
    profile(None)  # no profile at all
    assert _read(name, _ctx()) is None
