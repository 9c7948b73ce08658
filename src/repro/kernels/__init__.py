"""Pallas TPU kernels for PhoneBit's compute hot-spots.

xnor_popcount_matmul     paper-faithful binary matmul (VPU, Eqn 1),
                         whole-tile vectorized xor+popcount reduction
fused_conv_bn_binarize   integrated conv+BN+sign+pack on im2col patches
                         (C4/C6, Eqns 5-9)
direct_conv_bn_binarize  direct (im2col-free) fused conv: VMEM-resident
                         input tiles, in-VMEM KHxKW window walk, integer
                         threshold + bit-pack + OR-pool epilogue
                         (DESIGN.md §5)
bitplane_pack            first-layer bit-plane split+pack (C8, Eqn 2)
image_conv               the first binary conv as one exact integer conv on
                         the MXU, straight from the uint8 image (XLA, not
                         Pallas; DESIGN.md §3.6)
mxu_pm1_matmul           beyond-paper MXU path (unpack-to-bf16 in VMEM)
flash_attention          fused attention (score chain never leaves VMEM —
                         the LM/DiT/ViT hot-spot; custom_vjp bwd)
ops                      jit'd wrappers + mode dispatch
ref                      pure-jnp oracles
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
