"""Model FLOP/s utilization of the whole step, in percent: the window's
required FLOPs (recomputation not counted) over its length, over the
chip's peak bf16 FLOP/s."""


def read(run):
    w = run["window"]
    return 100.0 * w["flops"] / w["seconds"] / run["peaks"]["flops"]
