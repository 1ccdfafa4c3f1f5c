"""The comparison that decides ``correct``.

Both sides give the same readings of their first three train steps from
the seed's weights on the same batches (see ``reference.Reference.run``):
each step's loss, the per-leaf norm of the first moment after step 1, and
the per-leaf norm of the parameters' change after the last step.  The
program takes them twice through the timed step: in set-up, and again
after the window (the ``_end`` readings), so that a fault which shows only
after many calls is seen.  Three numbers are read each time:

* ``loss``   - the largest relative gap of a step's loss;
* ``grad``   - the worst leaf's gap between the two first-moment norms,
  over the larger of that leaf's reference norm and the median leaf's
  (some gradients are all but zero);
* ``update`` - the same for the parameters' change, over the leaves the
  reference moves: a leaf whose reference gradient is under a thousandth
  of the median leaf's moves by round-off alone and is left out.

A number is compared where the cell's file gives it a limit.
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, Tuple

# a leaf whose step-1 gradient norm is under this share of the median
# leaf's is not held to its change
MOVED_SHARE = 1e-3
NUMBERS = ("loss", "grad", "update")


def _worst(prog: Dict[str, float], ref: Dict[str, float], keys
           ) -> Tuple[float, str]:
    med = statistics.median(ref[k] for k in keys)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The three numbers, with the leaf each was read at."""
    if set(prog["first_moment"]) != set(ref["first_moment"]):
        raise ValueError("the two sides' parameter trees differ")
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                    ref["loss"]))
    grad, grad_at = _worst(prog["first_moment"], ref["first_moment"],
                           ref["first_moment"])
    med = statistics.median(ref["first_moment"].values())
    moved = [k for k, g in ref["first_moment"].items()
             if g >= MOVED_SHARE * med]
    update, update_at = _worst(prog["change"], ref["change"], moved)
    return {"loss": loss, "grad": grad, "grad_at": grad_at,
            "update": update, "update_at": update_at,
            "left_out": sorted(set(ref["first_moment"]) - set(moved))}


def judge(g: Dict[str, Dict[str, Any]], limits: Dict[str, float]
          ) -> Tuple[bool, Dict]:
    """(correct, {name: {"value", "limit"}}) for ``g``, the gaps keyed by
    the suffix of their numbers' names (``""``, ``"_end"``), over the
    numbers that have a limit; a number that is not finite fails."""
    read = {f"{k}{when}": x[k] for when, x in g.items() for k in NUMBERS}
    if set(limits) - set(read):
        raise KeyError(f"limits for numbers not read: "
                       f"{sorted(set(limits) - set(read))}")
    shown = {k: {"value": v, "limit": limits[k]}
             for k, v in read.items() if k in limits}
    ok = all(v["value"] <= v["limit"] for v in shown.values())
    return ok, shown
