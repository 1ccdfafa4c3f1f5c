"""The yardstick's arithmetic: chip peaks, and a step's work from its shape.

``PEAKS`` is keyed by the ``device_kind`` JAX reports; a device without a
row is an error.  A step's *required* work is what the configuration's
train step needs for the batch's real tokens, whatever the program does
on top (padding, recomputation, unfused elementwise passes):

* FLOPs: forward matmuls 2 per weight per token (embedding lookup is not
  a matmul), causal attention 2 x 2 x heads x head_dim per (query, key)
  pair at or before the query within one document, and the backward pass
  twice the forward;
* HBM bytes: every parameter read in the forward and the backward pass,
  its gradient written and read once, and the optimizer's read and write
  of parameter and both moments.  Activations are left out, so the bytes
  are a lower bound.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable

import numpy as np

from reference import table_rows

PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GiB HBM at 819 GB/s"},
}

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def peaks_for(kind: str) -> Dict[str, Any]:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]


def matmul_params(c: Dict[str, Any]) -> int:
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    hd, f = d // h, c["intermediate_size"]
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return c["num_hidden_layers"] * layer + d * c["vocab_size"]


def all_params(c: Dict[str, Any]) -> int:
    d, vocab = c["hidden_size"], table_rows(c)
    return matmul_params(c) + d * (vocab - c["vocab_size"]) + vocab * d \
        + d * (2 * c["num_hidden_layers"] + 1)


def step_flops(c: Dict[str, Any], doc_lengths: Iterable[int]) -> float:
    """Required FLOPs of one train step over documents of these lengths."""
    n = np.asarray(list(doc_lengths), dtype=np.float64)
    d, h = c["hidden_size"], c["num_attention_heads"]
    fwd = 2.0 * matmul_params(c) * n.sum() \
        + 4.0 * c["num_hidden_layers"] * h * (d // h) \
        * float((n * (n + 1) / 2).sum())
    return 3.0 * fwd


def step_bytes(c: Dict[str, Any]) -> float:
    """Required HBM bytes of one train step (a lower bound)."""
    p = _DTYPE_BYTES[c["torch_dtype"]]
    s = _DTYPE_BYTES[c["optimizer"]["state_dtype"]]
    per_param = 2 * p + 2 * p + (p + 2 * s) * 2
    return float(all_params(c)) * per_param
