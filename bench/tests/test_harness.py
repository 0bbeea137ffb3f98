"""The harness around the program: the benchmark file, the refusal
without a chip, and a run with the timed path broken underneath."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

import smoke
from conftest import BENCH

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# mean logit gap at smoke size, seeds 1-6 on the CPU, pre-roll included:
# granite's sound runs read 0.00003 at most, its fp8 control 0.0015 and
# more, a cache never written 0.065 and more; chameleon's sound runs
# 0.0011 at most, fp8 0.019 and more, a cache never written 0.17 and more
SMOKE_LIMIT = {"granite-moe-3b-a800m": 0.0008, "chameleon-34b-L6": 0.005}


def _spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_file_names_files_that_exist():
    spec = _spec()
    assert spec["command"][1] == "bench/run.py" and spec["paths"] == ["bench"]
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert all(k in cfg for k in c["reduced"])
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and (BENCH / "metrics" / f"{m['name']}.py").exists()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        assert limits["mean_logit_gap"]["limit"] > 0
        mine = [m for m in spec["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
    for m in spec["per_layer"]:
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_without_a_chip_the_run_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # in the checkout, and in a directory with only the benchmark's files
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for root in (ROOT, tmp_path):
        p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                            "granite-chat", "--seed", "1", "--seconds", "1",
                            "--trace", "0"], cwd=root, env=env,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode != 0 and p.stdout == ""


def _run(name, seed, fault=None):
    import run as R
    from drivers import lm_serving as D
    from repro.serve import engine as E
    orig = E._step_batched_fused

    def altered(model, impl, params, blocks, packed):
        tok, blocks = orig(model, impl, params, blocks, packed)
        return (tok + 1) % model.cfg.vocab_size, blocks

    def frozen(model, impl, params, blocks, packed):
        tok, _ = orig(model, impl, params, blocks, packed)
        return tok, blocks

    E._step_batched_fused = {None: orig, "altered": altered, "frozen": frozen}[fault]
    try:
        return D.run({"name": "smoke", "traffic": None}, smoke.config(name),
                     seed, 1.0, False, t_start=time.perf_counter(),
                     limits={"mean_logit_gap": SMOKE_LIMIT[name]}, peaks={}, trace_dir=None,
                     compiles=R.CompileCounter(), mix=smoke.mix())
    finally:
        E._step_batched_fused = orig


CONFIGS = ["granite-moe-3b-a800m", "chameleon-34b-L6"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("fault", [None, "altered", "frozen"])
def test_a_broken_timed_path_is_not_correct(name, fault):
    """A token altered where the decode step makes it, and a decode step
    that hands back its cache unwritten, each read as not correct."""
    for seed in (1, 2, 3):
        out = _run(name, seed, fault)
        assert out.failed == 0 and out.attempted > 10
        assert out.correct is (fault is None), (seed, out.checks)


@pytest.mark.parametrize("name", CONFIGS)
def test_the_control_is_not_correct(name):
    """The reference in fp8, put in the program's place at each position
    of what the program served, goes through the run's own verdict and
    reads not correct on every seed, where the program reads correct."""
    from drivers import lm_serving as D
    config, mix = smoke.config(name), smoke.mix()
    for seed in (1, 2, 3):
        out = _run(name, seed)
        assert out.correct, (seed, out.checks)
        gaps = D.reference_gaps(config, seed, mix, out.sample, low="fp8")
        checks, correct = D.verdict(out.data.clients, out.sample, gaps,
                                    {"mean_logit_gap": SMOKE_LIMIT[name]},
                                    mix["check"])
        assert correct is False, (seed, checks)
