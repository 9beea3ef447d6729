"""Core: the paper's contribution (DC-ELM and friends) in JAX.

Modules:
  features    random ELM feature maps h(x) (+ the activation registry)
  async_engine event-driven push-sum gossip runtime (no round barrier)
  push_sum    ratio-consensus mass algebra + conservation accounting
  stats       the statistics plane: (P, Q, ||T||^2, Omega) for every
              path — fused feature->moment kernels, chunked
              SufficientStats, Cholesky solves
  elm         centralized ELM (paper Sec. II-A)
  consensus   communication graphs, Laplacians, rates (Sec. III-A)
  dc_elm      DC-ELM Algorithm 1 (simulated + ppermute-sharded)
  online      Online DC-ELM Algorithm 2 (Woodbury updates)
  gossip      ppermute neighbor-exchange primitives
  compression quantized/sparsified gossip payloads + wire accounting
  dsgd        beyond-paper decentralized deep training (paper rule on pytrees)
  incremental Hamiltonian-cycle baseline (Sec. II-B1)
  fusion_elm  fusion-center / MapReduce baseline (refs [17][18])
  scopes      device-side names of the DC-ELM phases (jax.named_scope)
"""

from repro.core import (  # noqa: F401
    async_engine,
    compression,
    consensus,
    dc_elm,
    dsgd,
    elm,
    features,
    fusion_elm,
    gossip,
    incremental,
    online,
    push_sum,
    scopes,
    stats,
)
