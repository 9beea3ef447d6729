"""Pallas TPU kernels for the compute hot-spots.

  elm_stats.py / _ops.py / _ref.py      fused feature->moment pipeline
                                        H = g(XW+b), P += H^T H,
                                        Q += H^T T in one grid pass —
                                        H never written to HBM; feeds
                                        core/stats.py (the statistics
                                        plane, every execution path)
  elm_predict.py / _ops.py / _ref.py    fused predict pipeline
                                        Y = g(XW+b) @ beta, the serving
                                        twin — H stays in VMEM while the
                                        output block accumulates; feeds
                                        ELM.__call__, dc_elm.node_predict
                                        and serving/elm_server.py
  gram.py / gram_ops.py / gram_ref.py   P = H^T H, Q = H^T T from a
                                        *materialized* H (deep-backbone
                                        features and other non-fusable
                                        maps); symmetric block-triangle
  ssd_scan.py / ssd_ops.py / ssd_ref.py Mamba2 chunked SSD scan
  attn.py / attn_ops.py / attn_ref.py   causal/SWA GQA flash attention
  decode_attn.py                        flash-decode (one token vs a
                                        long KV cache, serving hot path)
  elm_gossip.py / _ops.py / _ref.py     fused eq. (20) gossip round
                                        over padded neighbor lists;
                                        feeds core/mixers.NeighborMixer
  vmem.py                               the VMEM a kernel may request,
                                        which sizes gossip node blocks
                                        and picks the stats kernel's arm
  dispatch.py                           the one dispatch policy of the
                                        *_ops.py wrappers

Each kernel is a pl.pallas_call with explicit BlockSpec VMEM tiling,
validated against its pure-jnp oracle in interpret mode (tests/).
The *_ops.py wrappers dispatch kernel-on-TPU / fallback-elsewhere
through dispatch.py; block sizes are the kernels' defaults or derived
from the VMEM budget (vmem.py).
"""

from repro.kernels import (  # noqa: F401
    attn_ops,
    elm_predict_ops,
    elm_stats_ops,
    gram_ops,
    ssd_ops,
)
