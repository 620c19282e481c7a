"""The harness finds a cell's files by name, so a new configuration, mix
and per-layer metric need no edit of an existing file; and ``run.py``
prints no result and exits non-zero without a TPU or without the
program."""
import hashlib
import json
import os
import pathlib
import runpy
import subprocess
import sys

import pytest

from chipbench.lib import bench
from chipbench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "chipbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    dest = tiny.make_checkout(tmp_path)
    before = digest(ROOT)
    (dest / "chipbench" / "metrics" / "tiny_extra.py").write_text(
        "def read(r):\n    return r.get('num_slots')\n")
    b = json.loads((dest / "BENCHMARK.json").read_text())
    for suffix, cell in (("serve", "tiny.closed"), ("chat", "tiny.poisson")):
        b["per_layer"].append({"name": f"tiny_extra.{suffix}",
                               "unit": "slots", "better": "higher",
                               "source": "program_counter",
                               "layer": "engine and scheduler",
                               "moves": "serve_tokens_per_s",
                               "workloads": [cell]})
    (dest / "BENCHMARK.json").write_text(json.dumps(b))
    cell = bench.load_cell(dest, "tiny.closed")
    assert cell.config["name"] == "tiny" and cell.mix["loop"] == "closed"
    names = [m["name"] for m in cell.per_layer]
    assert "tiny_extra.serve" in names and "tiny_extra.chat" not in names
    # one reader per quantity, found by the name before the cell's suffix
    for name in ("tiny_extra.serve", "tiny_extra.chat"):
        assert bench.load_reader(dest, name)({"num_slots": 3}) == 3
    # every file the benchmark already had is there unchanged
    after = digest(dest)
    for path, h in before.items():
        if "tests" not in path.parts:
            assert after[path] == h, path
    with pytest.raises(KeyError):
        bench.load_cell(dest, "no.such-cell")
    del b["per_layer"][-1]["workloads"]
    (dest / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(ValueError, match="workloads"):
        bench.load_cell(dest, "tiny.closed")


def test_every_cell_and_metric_resolves():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = bench.load_cell(ROOT, w["name"])
        assert cell.per_layer and cell.end_to_end
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(bench.load_reader(ROOT, m["name"]))
    for m in b["per_layer"]:
        assert m["workloads"]
        for r in ({}, {"num_slots": 6}):
            assert bench.load_reader(ROOT, m["name"])(r) is None


def test_run_without_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "minicpm-2b.decode-long", "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_run_without_the_program_exits_nonzero(tmp_path, monkeypatch,
                                               capsys):
    import shutil
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    import jax
    monkeypatch.setattr(bench, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "minicpm-2b.decode-long", "--seed", "1",
        "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit) as e:
        runpy.run_path(str(tmp_path / "chipbench" / "run.py"),
                       run_name="__main__")
    assert e.value.code != 0
    assert "no program" in str(e.value.code)
    assert "{" not in capsys.readouterr().out
