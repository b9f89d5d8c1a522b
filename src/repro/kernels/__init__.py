"""Pallas TPU kernels (each with kernel.py + ops.py wrapper + ref.py oracle).

* dgap_decode      — blocked prefix-sum w/ carry: posting-list decompression
* anchor_intersect — batched anchor probes: RePair-Skip on the VPU
* fused_decode     — per-row bounded rule expansion (+ fused membership
                     probe) for the compressed device layout
* minhash_sig      — batched MinHash signatures: min-reduction over hashed
                     shingles (version-structure mining)
"""

from .anchor_intersect.ops import anchor_probe
from .dgap_decode.ops import dgap_decode
from .fused_decode.ops import decode_rows, probe_rows
from .minhash_sig.ops import hash_params, minhash_signatures

__all__ = ["anchor_probe", "decode_rows", "dgap_decode", "hash_params", "minhash_signatures", "probe_rows"]
