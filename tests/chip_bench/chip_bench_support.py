"""Shared by the benchmark's CPU tests: the harness on the path, and a
tiny cell built from a real cell's files."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import spec  # noqa: E402

# Limits for the tiny cell, set from CPU readings at these widths (three
# seeds, both tiny cells): the sound program read at most 5.9e-4 (loss),
# 8.4e-4 (grad) and 1.1e-3 (update); the float8 control at least 1.1e-3,
# 5.3e-3 and 2.2e-3.  The end steps repeat the set-up steps, so their
# numbers (``_end``) take the same limits.
TINY_LIMITS = {"loss": 1.5e-3, "grad": 3e-3, "update": 2.5e-3}
TINY_LIMITS.update({f"{k}_end": v for k, v in list(TINY_LIMITS.items())})


def tiny_cell(workload: str = "granite-8b.varlen") -> "spec.Cell":
    """The workload's cell at CPU widths: its layers (at most two) of width
    128, a 4-row cycle of short documents, the same files otherwise."""
    cell = spec.load_cell(workload)
    c = copy.deepcopy(cell.config)
    c.update(num_hidden_layers=min(c["num_hidden_layers"], 2),
             hidden_size=128, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=256, vocab_size=512)
    t = copy.deepcopy(cell.traffic)
    if t["layout"] == "padded":
        t.update(batch_rows=4, min_tokens=5, max_tokens=60, align=16,
                 cycle_batches=4)
    else:
        t.update(batch_rows=4, row_tokens=32, cycle_batches=3)
    return spec.Cell(name=cell.name, chips=1, config=c, traffic=t,
                     limits=dict(TINY_LIMITS),
                     end_to_end=cell.end_to_end, per_layer=cell.per_layer)
