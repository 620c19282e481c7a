# Pallas TPU kernels for the paper's compute hot-spots, each with a pure-jnp
# oracle in ref.py and a jit'd dispatch wrapper in ops.py:
#   brgemm.py          — BRGEMM TPP on the MXU, PARLOOPER-scheduled grid
#   block_spmm.py      — BCSR work-list block-sparse × dense
#   flash_attention.py — fused attention (prefill + decode), GQA/causal/window
#   paged_attention.py — single-token decode over the live pages of paged KV pools
#   mamba_scan.py      — chunked selective scan (state resident in VMEM)
#   conv.py            — Listing-4 direct convolution (executor + 1×1 Pallas path)
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
