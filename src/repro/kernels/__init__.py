"""Pallas TPU kernels (+ jnp oracles) for the framework's compute hot-spots.

  flash_attention  — blocked causal GQA attention (training / prefill)
  decode_attention — flash-decode over long KV caches
  qsnap            — blockwise int8 quantization of checkpoint images
                     (format-compatible with repro.ckpt.compression)

Use via ``repro.kernels.ops`` — wrappers pick pallas on TPU, jnp oracle on
CPU, and support interpret=True for kernel-body validation on CPU.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
