"""Pallas TPU kernels for the compute hot spots (validated in interpret
mode on CPU; see each subpackage's kernel.py for the BlockSpec tiling):

  bucket_count     — the map's key histogram as a one-hot matmul on the MXU
  coded_combine    — the paper's linear f(.) encode/decode (+ XOR variant)
  flash_attention  — blockwise online-softmax attention (prefill hot spot)
  rwkv_scan        — chunked WKV gated linear recurrence (long-context)
"""
