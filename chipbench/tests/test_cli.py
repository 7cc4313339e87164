"""The command as the driver starts it: a new process."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "chipbench.run", *argv],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


def test_no_tpu_is_exit_code_2_and_nothing_on_stdout():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell in bench["workloads"]:
        p = run("--workload", cell["name"], "--seed", "1", "--seconds", "1",
                "--trace", "0")
        assert p.returncode == 2 and p.stdout == ""
        assert "no TPU" in p.stderr


def test_rehearsal_names_the_cpu_and_says_not_correct():
    p = run("--rehearse", "--workload", "tiny-train.pretrain", "--seed",
            str(2 ** 31 + 77), "--seconds", "0.3", "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert "check ok" in p.stdout       # each number beside its limit


def test_benchmark_json_names_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cfg in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, cfg["file"]))
    for cell in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "traffic", cell["traffic"] + ".json"))
    ends = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", m["name"] + ".json"))
        assert m["moves"] in ends
