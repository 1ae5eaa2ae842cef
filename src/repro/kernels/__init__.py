"""Pallas TPU kernels for the compute hot spots (validated interpret=True on
CPU): flash_attention (prefill/train attention), ssd_scan (Mamba-2 chunked
scan), gt_update (fused PISCO local-step / mix-combine elementwise passes),
quantize (fused quantize→mix→dequantize for compressed gossip).

The paper itself has no kernel-level contribution (its contribution is the
communication protocol); these kernels target the workloads PISCO trains plus
PISCO's own memory-bound state updates.  ops.py holds the jit'd wrappers,
ref.py the pure-jnp oracles.
"""
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gt_update import fused_local_step, fused_mix_combine
from repro.kernels.quantize import fused_compressed_mix, rowwise_quant_dequant
from repro.kernels.ssd_scan import ssd_scan_kernel

__all__ = [
    "ops", "ref", "flash_attention", "fused_local_step",
    "fused_mix_combine", "fused_compressed_mix", "rowwise_quant_dequant",
    "ssd_scan_kernel",
]
