"""The one traffic generator: a fixed cycle of training batches.

A traffic file fixes the *shapes* of a cycle of batches, the way a
fine-tuning job's dataset fixes them; the window replays the cycle as
epochs.  ``--seed`` draws only the token ids, never a length, so every
seed runs the same shape sequence.

Layouts:

* ``padded`` - each row holds one document, its length drawn once from
  the file's distribution with the file's ``data_seed``; the batch is
  padded to its own longest document rounded up to ``align``.  Useful
  tokens are the documents' own tokens.
* ``packed`` - documents concatenated into ``batch_rows`` rows of
  ``row_tokens``: one shape every step, every token useful.

Each batch is ``tokens``, ``labels`` (the next token in the row) and
``mask`` (1 where a label is a real next token of the same row).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

PAD_ID = 0


def doc_lengths(t: Dict[str, Any]) -> np.ndarray:
    """(cycle_batches, batch_rows) document lengths of a padded cycle,
    log-uniform in [min_tokens, max_tokens) as ``data/pipeline.py`` draws
    them (truncated to an integer)."""
    if t["distribution"] != "log-uniform":
        raise ValueError(f"unknown length distribution {t['distribution']!r}")
    rng = np.random.default_rng(t["data_seed"])
    u = rng.uniform(np.log(t["min_tokens"]), np.log(t["max_tokens"]),
                    size=(t["cycle_batches"], t["batch_rows"]))
    return np.exp(u).astype(np.int64)


def cycle_shapes(t: Dict[str, Any]) -> List[Tuple[int, int]]:
    if t["layout"] == "packed":
        return [(t["batch_rows"], t["row_tokens"])] * t["cycle_batches"]
    if t["layout"] != "padded":
        raise ValueError(f"unknown layout {t['layout']!r}")
    a = t["align"]
    return [(t["batch_rows"], int(-(-n.max() // a) * a))
            for n in doc_lengths(t)]


def make_cycle(t: Dict[str, Any], vocab: int, seed: int
               ) -> List[Dict[str, Any]]:
    """The cycle's batches as host arrays, token ids drawn from ``seed``.

    Each entry: ``tokens``, ``labels``, ``mask`` and ``useful`` (the
    number of non-padding tokens)."""
    rng = np.random.default_rng(seed)
    shapes = cycle_shapes(t)
    lengths = (doc_lengths(t) if t["layout"] == "padded"
               else np.full((len(shapes), t["batch_rows"]), t["row_tokens"]))
    out = []
    for (b, s), lens in zip(shapes, lengths):
        tokens = rng.integers(1, vocab, size=(b, s), dtype=np.int32)
        col = np.arange(s)[None, :]
        tokens = np.where(col < lens[:, None], tokens, PAD_ID).astype(np.int32)
        labels = np.full_like(tokens, PAD_ID)
        labels[:, :-1] = tokens[:, 1:]
        mask = (col < lens[:, None] - 1).astype(np.float32)
        out.append({"tokens": tokens, "labels": labels, "mask": mask,
                    "useful": int(lens.sum()), "lengths": lens})
    return out


def setup_order(shapes: List[Tuple[int, int]], n_compared: int
                ) -> Tuple[List[int], List[int]]:
    """Cycle indices of the set-up steps: (warm, compared).

    ``warm`` is the first batch of each distinct shape, largest first; those
    steps compile or load the per-op programs, and their outputs are
    dropped.  ``compared`` are the steps then run from the same initial
    state and compared with the reference: for each shape in the same
    order, its next batch in the cycle (other rows than the warm-up's,
    where the cycle has them), then further batches in cycle order until
    there are ``n_compared``.  So the compared steps take the window's own
    warm path, and include the largest shape."""
    warm = [shapes.index(s) for s in sorted(set(shapes), reverse=True)]
    compared: List[int] = []
    for w in warm:
        later = [i for i in range(len(shapes))
                 if shapes[i] == shapes[w] and i not in warm
                 and i not in compared]
        compared.append(later[0] if later else w)
    rest = [i for i in range(len(shapes)) if i not in warm + compared]
    rest += [i for i in warm if i not in compared]
    return warm, (compared + rest)[:n_compared]
