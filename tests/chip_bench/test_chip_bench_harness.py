"""CPU tests of the chip benchmark's harness: its files, its traffic, its
trace reduction, and that it refuses to run without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chip_bench_support import BENCH, ROOT

import compare
import devtrace
import flops
import spec
import traffic

BENCHMARK = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
# the numbers every cell compares: from the seed's weights and from the
# window's last state
LIMITED = {f"{k}{when}" for k in compare.NUMBERS for when in ("", "_end")}


def test_benchmark_json_keeps_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"][1].startswith(b["paths"][0] + "/")
    for p in b["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    assert 1 <= b["run_seconds"] <= 51
    cells = len(b["workloads"])
    # a full check's runs of every later PR, with 24 cells, must fit
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert {m["name"] for m in b["end_to_end"]} >= {"setup_s"}
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["better"] in ("lower",
                                                             "higher")
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= cells // 2 or \
        sum(w["chips"] == 4 for w in b["workloads"]) <= 1
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_names_an_existing_config_and_traffic(workload):
    cell = spec.load_cell(workload)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == workload)
    conf = next(c for c in BENCHMARK["configs"]
                if c["name"] == entry["config"])
    assert (ROOT / conf["file"]).is_file()
    assert cell.config["name"] == entry["config"]
    assert set(conf["reduced"]) == set(cell.config["reduced"])
    assert (BENCH / "traffic" / f"{entry['traffic']}.json").is_file()
    assert set(cell.limits) == LIMITED
    for m in cell.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("path", sorted((BENCH / "cells").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_cell_file_names_an_existing_config_and_traffic(path):
    cell = json.loads(path.read_text())
    assert (BENCH / "configs" / f"{cell['config']}.json").is_file()
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    assert set(cell["limits"]) == LIMITED


def test_cell_files_and_benchmark_json_agree():
    on_disk = {p.stem for p in (BENCH / "cells").glob("*.json")}
    assert on_disk >= set(WORKLOADS)
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == {c["name"] for c in BENCHMARK["configs"]}


@pytest.mark.parametrize("name", ["varlen", "packed"])
def test_shape_cycle_is_the_same_for_every_seed(name):
    t = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    a = traffic.make_cycle(t, 49152, 7)
    b = traffic.make_cycle(t, 49152, 2**31 + 11)
    assert [x["tokens"].shape for x in a] == [x["tokens"].shape for x in b]
    assert [x["useful"] for x in a] == [x["useful"] for x in b]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["mask"], y["mask"])
        assert not np.array_equal(x["tokens"], y["tokens"])
        # labels are the next token of the same row, masked past the end
        np.testing.assert_array_equal(x["labels"][:, :-1][x["mask"][:, :-1]
                                                          > 0],
                                      x["tokens"][:, 1:][x["mask"][:, :-1]
                                                         > 0])
    again = traffic.make_cycle(t, 49152, 7)
    for x, y in zip(a, again):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_varlen_cycle_comes_in_the_distributions_proportions():
    t = json.loads((BENCH / "traffic" / "varlen.json").read_text())
    seqs = [s for _, s in traffic.cycle_shapes(t)]
    assert len(seqs) == 12
    assert (seqs.count(768), seqs.count(640), seqs.count(512)) == (6, 4, 2)
    warm, compared = traffic.setup_order(traffic.cycle_shapes(t), 3)
    assert [seqs[i] for i in warm] == [768, 640, 512]
    assert [seqs[i] for i in compared] == [768, 640, 512]
    assert not set(warm) & set(compared)


def test_packed_cycle_compares_other_rows_than_it_warms():
    t = json.loads((BENCH / "traffic" / "packed.json").read_text())
    warm, compared = traffic.setup_order(traffic.cycle_shapes(t), 3)
    assert warm == [0] and compared == [1, 2, 3]


def test_reduction_of_a_hand_made_trace():
    ev = {"device": [["fusion.1", 100, 50, "/device:TPU:0"],
                     ["dot.2", 200, 100, "/device:TPU:0"],
                     ["fusion.3", 400, 20, "/device:TPU:0"]],
          "host": [["py", "bench_step", 90, 200], ["py", "bench_step", 300,
                                                   200],
                   ["py", "_run_fast", 95, 150], ["py", "bind", 160, 30],
                   ["other", "x", 0, 1000]]}
    r = devtrace.reduce(ev)
    assert r["window_s"] == pytest.approx(410e-9)
    assert r["busy_s"] == pytest.approx(170e-9)
    assert r["steps"] == 2 and r["device_ops"] == 3
    assert r["breakdown"]["device_ops"][0] == ["dot", pytest.approx(1e-7)]
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench_step -> fusion", pytest.approx(1e-7)]
    assert ["_run_fast -> dot", pytest.approx(5e-8)] in gaps


def test_reduction_of_a_recorded_chip_trace():
    """Three steps of two jitted programs, traced on one TPU v5e."""
    ev = json.loads((ROOT / "tests" / "chip_bench" / "data"
                     / "trace_small.json").read_text())
    r = devtrace.reduce(ev)
    assert r["steps"] == 3
    assert 0 < r["busy_s"] < r["window_s"]
    steps = sorted((s, s + d) for _, n, s, d in ev["host"]
                   if n == devtrace.STEP_SPAN)
    inside = [d for n, s, d, _ in ev["device"]
              if s >= steps[0][0] and s + d <= steps[-1][1]]
    assert r["busy_s"] == pytest.approx(sum(inside) * 1e-9, rel=1e-6)
    assert r["device_ops"] == len(inside)
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_required_work_of_a_step():
    c = spec.load_cell("granite-8b.varlen").config
    d, f, v = 4096, 14336, c["vocab_size"]
    mm = 2 * d * 4096 + 2 * d * 1024 + 3 * d * f + d * v
    assert flops.matmul_params(c) == mm
    assert flops.step_flops(c, [3]) == pytest.approx(
        3 * (2 * mm * 3 + 4 * 32 * 128 * 6))
    # parameters, gradient and optimizer traffic: 28 B per bf16 parameter
    assert flops.step_bytes(c) == 28 * flops.all_params(c)


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite-8b.varlen", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def test_command_fails_with_only_the_benchmarks_files(tmp_path):
    for rel in BENCHMARK["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert not p.stdout.strip()
