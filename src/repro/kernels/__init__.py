"""Pallas TPU kernels for the framework's compute hot-spots.

  * rwsadmm_update — the paper's per-round fused elementwise triple update
    (x, z, y): one HBM pass instead of ~10 unfused elementwise HLO ops.
  * flash_decode — single-token GQA attention against a long KV cache
    (decode_32k / long_500k bottleneck), online softmax in VMEM scratch.
  * rglru_scan — blocked linear recurrence for RG-LRU / hybrid archs.

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit
wrapper; interpret mode on CPU only, see backend.py), ref.py (pure-jnp
oracle).
"""
from . import flash_decode, rglru_scan, rwsadmm_update  # noqa: F401
