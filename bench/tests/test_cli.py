"""``bench/run.py`` refuses to run without an accelerator and prints no
result line."""

import os
import subprocess
import sys

from conftest import ROOT


def test_no_chip_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "clos64.paper_grid.sweep", "--seed", str(2**31 + 7),
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_benchmark_file_names_existing_files():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(ROOT, "bench", "limits", f"{w['name']}.json"))
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics", f"{m['name']}.py"))


def test_parts_are_found_by_name():
    """Every driver, fabric, pattern and relabelling a data file names is
    a module of its own with the function the harness calls."""
    import json
    from bench import harness, traffic
    from bench.lookup import module
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        mix = traffic.load(w["traffic"])
        fabric = harness.load_config(w["config"])["fabric"]
        assert callable(module("drivers", mix["driver"]).run)
        fab = module("fabrics", fabric["kind"])
        assert callable(fab.program) and callable(fab.path) and fab.hosts(fabric) > 0
        for scene in mix["scenes"]:
            for part in scene["parts"]:
                assert callable(module("patterns", part["pattern"]).rows)
            if scene.get("seed"):
                assert callable(module("relabel", scene["seed"]).apply)
