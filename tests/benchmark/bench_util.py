"""What the benchmark's tests share: where the repository and the toy
manifest are, and one run of ``perfbench/run.py`` as the driver makes it."""
import atexit
import functools
import json
import os
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TOYDIR = os.path.join(ROOT, "tests", "benchmark", "toy")
# the toy stand-in of every configuration and cell of BENCHMARK.json; a toy
# mix is named ``toy-<mix>``
TOY_CONFIGS = {"cerebras-gpt-1.3b": "toy-gpt", "resnet-50": "toy-resnet"}
TOY_CELLS = {"cgpt13b-train-s2048": "toy-train-lm",
             "cgpt13b-decode-closed": "toy-decode",
             "resnet50-train-b256": "toy-train-img",
             "cgpt13b-train-dp4": "toy-train-dp4"}


def toy_manifest_data():
    """BENCHMARK.json with every configuration, mix and cell replaced by
    its toy stand-in under ``tests/benchmark/toy``: the same metrics and
    readers, found by name like any other."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["paths"] = ["tests/benchmark/toy", "perfbench"]
    m["run_seconds"] = 2
    # what a later PR adds has no toy stand-in here: it is left out
    m["configs"] = [
        dict(c, name=TOY_CONFIGS[c["name"]],
             file="tests/benchmark/toy/configs/%s.json"
             % TOY_CONFIGS[c["name"]])
        for c in m["configs"] if c["name"] in TOY_CONFIGS]
    m["workloads"] = [
        dict(w, name=TOY_CELLS[w["name"]], config=TOY_CONFIGS[w["config"]],
             traffic="toy-" + w["traffic"])
        for w in m["workloads"] if w["name"] in TOY_CELLS]
    for section in ("end_to_end", "per_layer"):
        kept = []
        for metric in m[section]:
            if "bound" in metric:
                metric["bound"] = 0.1
            if "workloads" in metric:
                metric["workloads"] = [TOY_CELLS[w]
                                       for w in metric["workloads"]
                                       if w in TOY_CELLS]
                if not metric["workloads"]:
                    continue
            kept.append(metric)
        m[section] = kept
    return m


@functools.lru_cache(maxsize=None)
def toy_manifest():
    """Path of the toy manifest, written once a process to a directory of
    its own that is removed at exit."""
    d = tempfile.mkdtemp(prefix="perfbench-toy-")
    atexit.register(shutil.rmtree, d, ignore_errors=True)
    path = os.path.join(d, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(toy_manifest_data(), f)
    return path


def run_cell(workload, seed=1, seconds=1, trace=0, manifest=None,
             rehearse=True, root=ROOT, timeout=600):
    """One run of ``perfbench/run.py`` as the driver makes it; returns
    (exit code, standard output, standard error)."""
    manifest = manifest or toy_manifest()
    cmd = [sys.executable, os.path.join(root, "perfbench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace),
           "--manifest", manifest]
    if rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ, BENCH_RUN="ignored")
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    return p.returncode, p.stdout, p.stderr


def last_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])
