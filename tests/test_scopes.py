"""Every DC-ELM phase carries its ``dcelm/`` name into the compiled HLO.

A device profile sums op time by the innermost ``dcelm/<phase>`` of each
op's ``op_name`` metadata (``repro.core.scopes``). These tests compile
the programs the benchmark's cells run, at toy sizes on the CPU with the
Pallas kernels in interpret mode, and check where their ops land: the
stats kernel under ``stats``, the Cholesky under ``omega``, the Woodbury
update under ``woodbury``, every round body, on each mixer arm, under
``rounds``, and the sharded round's ppermutes under ``exchange``.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import consensus, engine, scopes
from repro.core.features import RandomFeatureMap
from repro.kernels import elm_gossip_ops

F32 = jnp.float32
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_PHASE = re.compile(r"dcelm/([a-z]+)")
_METADATA = re.compile(r", (metadata|frontend_attributes)=\{[^}]*\}")
#: the tables of source locations that op metadata points into
_LOCATIONS = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*\n?", re.M
)


def _program(text: str) -> str:
    """A compiled HLO text without its metadata and location tables."""
    return _LOCATIONS.sub("", _METADATA.sub("", text))


def S(*shape):
    return jax.ShapeDtypeStruct(shape, F32)


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def op_names(fn, *args) -> list[str]:
    return _OP_NAME.findall(compiled_text(fn, *args))


def phase(op_name: str):
    """The innermost phase, as the trace reduction reads it."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


def phases_of(names, marker: str) -> set:
    hits = {phase(n) for n in names if marker in n}
    assert hits, f"no op_name holds {marker!r}"
    return hits


@pytest.fixture
def interpret(monkeypatch):
    """The kernel dispatchers take their Pallas arms, in interpret mode."""
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")


def _init(eng):
    def init(X, T, W, b):
        return eng.stream_init(
            X_nodes=X, T_nodes=T, feature_map=RandomFeatureMap(W, b)
        )

    return init


def _chunk(eng, gamma, *, remove=False):
    def chunk(state, dX, dT, W, b):
        dH = RandomFeatureMap(W, b)(dX)
        event = {"removed" if remove else "added": (dH, dT)}
        return eng.stream_chunk(state, gamma=gamma, num_iters=3, **event)[0]

    return chunk


def test_phase_names_are_fixed():
    assert scopes.PHASES == (
        "features", "stats", "omega", "reseed", "woodbury", "rounds", "exchange"
    )
    with pytest.raises(ValueError, match="unknown DC-ELM phase"):
        scopes.phase("solve")


def test_stream_init_fused_path(interpret):
    eng = engine.simulated_dc_elm(consensus.hypercube(2), 0.5)
    names = op_names(_init(eng), S(4, 32, 16), S(4, 32, 3), S(16, 16), S(16))
    # the kernel and the pads and copies of X around it
    assert phases_of(names, "elm_stats_pallas") == {"stats"}
    assert phases_of(names, "/pad") == {"stats"}
    assert phases_of(names, "cholesky") == {"omega"}
    assert "reseed" in {phase(n) for n in names}
    # every contraction of the job belongs to a phase
    assert None not in phases_of(names, "dot_general")


@pytest.mark.parametrize("remove", [False, True], ids=["add", "remove"])
def test_stream_chunk_phases(remove):
    g = consensus.hypercube(3)
    eng = engine.simulated_dc_elm(g, 0.5)
    state = engine.StreamState(omegas=S(8, 16, 16), Qs=S(8, 16, 3), betas=S(8, 16, 3))
    names = op_names(
        _chunk(eng, g.default_gamma(), remove=remove),
        state, S(8, 4, 16), S(8, 4, 3), S(16, 16), S(16),
    )
    update = "jit(remove_chunk)" if remove else "jit(add_chunk)"
    assert phases_of(names, update) == {"woodbury"}
    assert phases_of(names, "/lu") == {"woodbury"}  # the dN x dN solve
    assert {"features", "woodbury", "reseed", "rounds"} <= {phase(n) for n in names}
    assert None not in phases_of(names, "dot_general")


def test_dense_round_body():
    g = consensus.hypercube(3)
    eng = engine.simulated_dc_elm(g, 0.5)
    names = op_names(
        lambda x, om: eng.run(x, om, g.default_gamma(), 5)[0],
        S(8, 16, 3), S(8, 16, 16),
    )
    assert phases_of(names, "while/body") == {"rounds"}


def test_neighbor_kernel_rounds(interpret):
    # large V at small L: every backend's slack picks the gossip kernel
    g = consensus.hypercube(7)
    assert not elm_gossip_ops.prefers_dense(128, 7, 8, 2)
    eng = engine.simulated_dc_elm(g, 0.5, mixer="neighbor")
    names = op_names(
        lambda x, om: eng.run(x, om, g.default_gamma(), 4)[0],
        S(128, 8, 2), S(128, 8, 8),
    )
    assert phases_of(names, "elm_gossip_pallas") == {"rounds"}
    # the jitted wrapper is named after the kernel's entry point
    assert any("jit(elm_gossip_pallas" in n for n in names)
    assert not any("jit(<unknown>)" in n for n in names)


SHARDED_ROUND = r"""
import re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import engine, gossip
mesh = Mesh(np.asarray(jax.devices()), ("data",))
eng = engine.sharded_dc_elm(mesh, gossip.GossipSpec(("data",), ("ring",)), 0.5)
if {faulty!r}:
    eng = engine.with_faults(eng, np.ones((2, 4, 4), np.float32))
S = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
text = jax.jit(lambda x, om: eng.run(x, om, 0.45, 1)[0]).lower(
    S(4, 16, 3), S(4, 16, 16)).compile().as_text()
for line in text.splitlines():
    if " collective-permute(" in line:
        print("PERMUTE", re.search(r'op_name="([^"]+)"', line).group(1))
"""


@pytest.mark.parametrize("faulty", [False, True], ids=["ring", "masked"])
def test_sharded_round_exchange(faulty):
    """One round of the sharded engine on four host devices: both
    ppermutes of the ring (the masked variant's too) are ``exchange``,
    nested in ``rounds``."""
    from tests.conftest import run_py

    proc = run_py(SHARDED_ROUND.format(faulty=faulty), devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    names = [x.split(" ", 1)[1] for x in proc.stdout.splitlines()
             if x.startswith("PERMUTE ")]
    assert len(names) == 2  # +1 and -1 shifts
    assert {phase(n) for n in names} == {"exchange"}
    assert all("dcelm/rounds/" in n for n in names)


def test_phase_is_in_the_compile_cache_key():
    """The persistent compile cache keys on the program without its debug
    info, named scopes included: the phase also rides on each op as a
    frontend attribute, which the key keeps."""
    g = consensus.hypercube(3)
    eng = engine.simulated_dc_elm(g, 0.5)
    lowered = jax.jit(
        lambda x, om: eng.run(x, om, g.default_gamma(), 5)[0]
    ).lower(S(8, 16, 3), S(8, 16, 16))
    assert f'{scopes.ATTRIBUTE} = "rounds"' in lowered.as_text()
    assert f'{scopes.ATTRIBUTE}="rounds"' in lowered.compile().as_text()


def test_scopes_are_metadata_only(monkeypatch):
    g = consensus.hypercube(3)
    eng = engine.simulated_dc_elm(g, 0.5)
    state = engine.StreamState(omegas=S(8, 16, 16), Qs=S(8, 16, 3), betas=S(8, 16, 3))
    args = (state, S(8, 4, 16), S(8, 4, 3), S(16, 16), S(16))
    chunk = _chunk(eng, g.default_gamma())
    scoped = compiled_text(chunk, *args)
    assert "dcelm/" in scoped
    monkeypatch.setattr(scopes, "phase", lambda name: contextlib.nullcontext())
    for module in ("engine", "features", "online", "stats"):
        monkeypatch.setattr(f"repro.core.{module}.phase", scopes.phase)
    jax.clear_caches()  # module-level jits keep their scoped traces
    try:
        bare = compiled_text(_chunk(eng, g.default_gamma()), *args)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "dcelm/" not in bare
    assert _program(scoped) == _program(bare)
