"""Pallas/Mosaic TPU kernels for the serving hot loop.

The north-star requirement (BASELINE.json): "PagedAttention and
ragged-prefill rewritten as Pallas/XLA custom-calls". These kernels replace
the reference's vLLM CUDA PagedAttention (the engine inside the images that
reference ``values-01-minimal-example*.yaml`` deploy):

- paged_decode.py — decode attention streaming only the valid KV pages
  HBM->VMEM with double-buffered DMA and online softmax (the XLA fallback
  gathers the full padded page table instead).
- flash_prefill.py — ragged (segment-causal) flash attention for prefill,
  O(T) memory (the XLA fallback materializes the O(T^2) score matrix): by
  blocks of kv heads whose K/V lie in VMEM whole, a kv head's q heads
  stacked as rows, a loop over the 512-key tiles from a q block's segment
  start to its diagonal (masked only on the diagonal and where a segment
  boundary lies), operands in the input's dtype.
- flash_prefill_hist.py — chunked prefill: the chunk against its own
  history pages in the pool.
- kv_write.py — the post-scan KV page write: read-modify-write of the
  touched pool tiles by DMA, in place (the XLA reference is a loop of
  dynamic_update_slices).

They are numerically validated against the XLA reference implementations in
tests/test_pallas.py (interpret mode on CPU; compiled on real TPU).
"""

from .paged_decode import pallas_paged_decode
from .flash_prefill import flash_ragged_prefill

__all__ = ["pallas_paged_decode", "flash_ragged_prefill"]
