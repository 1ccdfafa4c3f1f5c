"""CPU test of ``fused_bind_share``: a tiny cell's cycle traced under a CPU
profiler session reads a number (every bind of the fast stream runs inside
an op-group executable), and a run without the program's records, or
with records that lack the counter, reads none."""
from __future__ import annotations

from collections import namedtuple

import chip_bench_support  # noqa: F401  (puts the harness on the path)
import run
from test_chip_bench_program_metrics import traced_run  # noqa: F401


def test_reads_the_traced_cycle(traced_run):  # noqa: F811
    assert run.load_reader("fused_bind_share")(traced_run) == 100.0


def test_reads_none_without_the_records(traced_run, monkeypatch):  # noqa: F811
    from repro.core.obs import telemetry
    read = run.load_reader("fused_bind_share")
    assert read(dict(traced_run, trace=None)) is None
    # a program from before the counter: records without ``fused_binds``
    Old = namedtuple("Old", "env binds")
    old = telemetry.Telemetry()
    for r in telemetry.PROFILE_SINK.ring.records()[
            -traced_run["trace"]["steps"]:]:
        old.ring.push(Old(env=r.env, binds=r.binds))
    monkeypatch.setattr(telemetry, "PROFILE_SINK", old)
    assert read(traced_run) is None
    monkeypatch.setattr(telemetry, "PROFILE_SINK", telemetry.Telemetry())
    assert read(traced_run) is None
