"""BENCHMARK.json names only files that exist, and the harness finds each
cell's configuration, traffic, limits and metric readers by name."""
import json
import os
import re
import subprocess
import sys

import pytest

from harness.spec import BENCH_DIR, CHECKOUT, load_cell, load_limits, readers
from harness.traffic import known_kinds, make_traffic

with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = load_cell(cell)
    assert c.chips in (1, 4)
    assert c.traffic["kind"] in known_kinds()
    assert float(load_limits(cell)["worst_rel_l2"]) > 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert all(m["moves"] in names for m in c.per_layer)
    assert set(readers(c)) == {m["name"] for m in c.per_layer}


def test_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert c["file"].startswith("perfbench/")
        assert os.path.isfile(os.path.join(CHECKOUT, c["file"]))
    for w in BENCH["workloads"]:
        assert os.path.isfile(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    assert BENCH["paths"] == ["perfbench"]


def test_kinds_are_found_by_name():
    assert "closed_loop" in known_kinds()
    with pytest.raises(ValueError, match="closed_loop"):
        make_traffic({"kind": "no_such_kind"}, (4, 4, 3), None, None)


def test_run_refuses_without_a_chip():
    """On the CPU the run exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELLS[0],
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
