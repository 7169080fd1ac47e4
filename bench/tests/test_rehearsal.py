"""`bench/run.py`'s phases at a tiny size on the CPU (the
`on-chip-measurement` guide, sections 2.1 and 2.2). The test steers: a
shrunk copy of the data files, the CPU's device kind in the peaks, the
mesh. The harness itself has no off-chip option, and says so."""

import json
import os
import subprocess
import sys

import jax
import pytest

import rehearse
import run
from registry import Registry

REPO = rehearse.REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny")))


def rehearse_cell(root, workload, seed=2 ** 31 + 11, trace=False):
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    devs = jax.devices()[:reg.entry(workload)["chips"]]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        return run.run_cell(reg, workload, seed, 0.5, trace, devs)


@pytest.mark.parametrize("workload", ["gbm-higgs.train",
                                      "gbm-higgs.train-4chip"])
def test_run_cell_end_to_end(root, workload):
    """One device and four virtual ones: a seed past 2**31, jobs until
    the window is over, nothing compiled inside it, the comparison with
    the plain reference within the cell's own limits, and the result
    line's keys in the contract's order."""
    line = rehearse_cell(root, workload)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == Registry(root).entry(
        workload)["chips"]
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    json.dumps(line)          # plain numbers only


def test_same_seed_same_table():
    import datasets

    a = datasets.higgs_like(3000, 2 ** 31 + 5)
    b = datasets.higgs_like(3000, 2 ** 31 + 5)
    c = datasets.higgs_like(3000, 6)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (a[0] == c[0]).all()
    cols = datasets.as_columns(*a)
    assert len(cols) == 29 and set(cols["y"]) == {"b", "s"}


def test_unknown_device_kind_is_an_error(root):
    reg = Registry(root)

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    with pytest.raises(SystemExit, match="no peaks"):
        run.run_cell(reg, "gbm-higgs.train", 1, 0.1, False, [Dev()])


def _run_script(cwd, script):
    return subprocess.run(
        [sys.executable, script, "--workload", "gbm-higgs.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)


def test_script_refuses_without_a_tpu():
    r = _run_script(REPO, os.path.join("bench", "run.py"))
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "platform 'cpu'" in r.stderr


def test_script_fails_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under `paths` there is nothing to measure: no result."""
    import shutil

    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    r = _run_script(str(tmp_path), os.path.join("bench", "run.py"))
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_harness_finds_dropped_in_files(tmp_path):
    """A later PR adds a configuration, a cell, a traffic kind and a
    per-layer metric as new files and new entries of BENCHMARK.json,
    and edits no file that is there."""
    root = rehearse.tiny_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(bench) for p in fs}
    with open(os.path.join(bench, "configs", "gbm-higgs.json")) as f:
        cfg = json.load(f)
    cfg.update(name="gbm-narrow", ntrees=3)
    with open(os.path.join(bench, "configs", "gbm-narrow.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workloads",
                           "gbm-higgs.train.json")) as f:
        cell = json.load(f)
    cell.update(config="gbm-narrow", kind="train_jobs_twice")
    with open(os.path.join(bench, "workloads", "gbm-narrow.twice.json"),
              "w") as f:
        json.dump(cell, f)
    with open(os.path.join(bench, "traffic", "train_jobs_twice.py"),
              "w") as f:
        f.write("import train_jobs\n\n\n"
                "class Traffic(train_jobs.Traffic):\n"
                "    def window(self, seconds):\n"
                "        res = super().window(seconds)\n"
                "        res['end_to_end']['jobs_per_window'] = "
                "float(res['attempted'])\n"
                "        return res\n")
    with open(os.path.join(bench, "metrics", "jobs_traced.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return float(ctx['result']['attempted'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "gbm-narrow", "source": "test",
                         "file": "bench/configs/gbm-narrow.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "gbm-narrow.twice",
                           "config": "gbm-narrow", "traffic": "twice",
                           "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "jobs_per_window", "unit": "count",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock",
                            "workloads": ["gbm-narrow.twice"]})
    b["per_layer"].append({"name": "jobs_traced", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "entry", "moves": "jobs_per_window",
                           "workloads": ["gbm-narrow.twice"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    line = rehearse_cell(root, "gbm-narrow.twice")
    assert line["correct"] is True
    assert line["metrics"]["jobs_per_window"]["value"] == line["attempted"]
    reg = Registry(root)
    assert [m["name"] for m in reg.metrics("per_layer", "gbm-narrow.twice")
            ][-1] == "jobs_traced"
    assert "jobs_traced" not in [
        m["name"] for m in reg.metrics("per_layer", "gbm-higgs.train")]
    assert reg.reader("jobs_traced").read(
        {"result": {"attempted": 3}}) == 3.0
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(bench) for p in fs
             if not p.endswith(".pyc")}
    assert all(after[p] == t for p, t in before.items()
               if not p.endswith(".pyc"))
