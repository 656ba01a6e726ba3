"""run.py end to end on the CPU: every driver at its tiny preset under
`--rehearse`, the refusal without a chip, `--check`, and a new cell added
as files alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TIMING = ("_s", "_ms", "seconds", "per_s")


def run_py(*args, root=ROOT, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=root)


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def timing_keys(obj, path=""):
    """Keys that name a timing, anywhere in a printed record."""
    found = []
    if isinstance(obj, dict):
        for k, v in obj.items():
            if k != "rehearse_seconds" and k.endswith(TIMING):
                found.append(path + k)
            found += timing_keys(v, path + k + ".")
    elif isinstance(obj, list):
        for v in obj:
            found += timing_keys(v, path)
    return found


@pytest.mark.parametrize("cell", cells())
def test_rehearse_drives_the_cell(cell):
    done = run_py("--workload", cell, "--rehearse", "--trace", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.strip()]
    last = lines[-1]
    assert last["correct"] is True and last["rehearse"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    assert "window_compiles" in last["reduced"]
    for rec in lines:
        if rec.get("line") != "start":      # `seconds` there is a setting
            assert timing_keys(rec) == [], rec


def test_without_a_chip_there_is_no_result():
    done = run_py("--workload", cells()[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert done.returncode != 0
    assert done.stdout == ""
    assert "TPU" in done.stderr


def test_check_passes():
    done = run_py("--check")
    assert done.returncode == 0, done.stdout
    assert json.loads(done.stdout.splitlines()[-1]) == {"check": "ok",
                                                        "problems": 0}


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_a_cell_is_added_as_files(tmp_path):
    """One new configuration, traffic mix, workload and metric file plus
    one entry each in BENCHMARK.json: `--check` and `--rehearse` take them
    with no edit to a file that was there."""
    root = str(tmp_path / "copy")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    b = os.path.join(root, "benchmarks")
    cfg = load(os.path.join(b, "configs", "gpt2-medium.json"))
    cfg.update(name="gpt2-small", n_embd=768, n_layer=12, n_head=12,
               source="https://huggingface.co/openai-community/gpt2")
    dump(os.path.join(b, "configs", "gpt2-small.json"), cfg)
    traffic = load(os.path.join(b, "traffic", "uniform-tokens-s1024.json"))
    traffic["seq_len"] = 512
    dump(os.path.join(b, "traffic", "uniform-tokens-s512.json"), traffic)
    wl = load(os.path.join(b, "workloads", "lm-train.json"))
    wl["per_layer"]["epoch_compiles"] = {"scale": 1.0}
    dump(os.path.join(b, "workloads", "lm-train-small.json"), wl)
    metric = load(os.path.join(b, "metrics", "window_compiles.json"))
    metric.update(name="epoch_compiles", moves="train_tokens_per_s",
                  args={"num": "xla.compile.hot_path.training.lm_train_epoch"})
    dump(os.path.join(b, "metrics", "epoch_compiles.json"), metric)

    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "gpt2-small", "source": cfg["source"],
                         "file": "benchmarks/configs/gpt2-small.json",
                         "reduced": [], "why": "a third width of the block"})
    m["workloads"].append({"name": "lm-train-small", "config": "gpt2-small",
                           "traffic": "uniform-tokens-s512", "chips": 1,
                           "why": "shorter sequences on a smaller model"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "workloads" in x and x["name"] in (*wl["end_to_end"],
                                              *wl["per_layer"]):
            x["workloads"].append("lm-train-small")
    m["per_layer"].append({"name": "epoch_compiles", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "entry points",
                           "moves": "train_tokens_per_s",
                           "workloads": ["lm-train-small"]})
    dump(os.path.join(root, "BENCHMARK.json"), m)

    done = run_py("--check", root=root)
    assert done.returncode == 0, done.stdout
    done = run_py("--workload", "lm-train-small", "--rehearse", root=root)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.splitlines()[-1])["correct"] is True
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, f"{path} was edited"


def test_check_names_what_is_wrong(tmp_path):
    root = str(tmp_path / "copy")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    m["workloads"][0]["name"] = "has space"
    m["per_layer"][0]["moves"] = "nothing"
    m["end_to_end"][0]["bound"] = 0.5
    dump(os.path.join(root, "BENCHMARK.json"), m)
    done = run_py("--check", root=root)
    assert done.returncode == 1
    for needle in ("has space", "moves 'nothing'", "bound 0.5"):
        assert needle in done.stdout


def test_the_shelved_cell_still_runs(tmp_path):
    """`featurize-jpeg` is not in BENCHMARK.json (PERF.md section 7 says
    why), but its files are kept: with the entries of
    workloads/featurize-jpeg.shelved.json merged in, `--check` and
    `--rehearse` take it."""
    root = str(tmp_path / "copy")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    shelved = load(os.path.join(BENCH, "workloads",
                                "featurize-jpeg.shelved.json"))
    for key in ("workloads", "configs", "end_to_end", "per_layer"):
        have = {x["name"]: x for x in m[key]}
        for x in shelved[key]:
            if x["name"] in have:
                have[x["name"]].setdefault("workloads", []).extend(
                    x["workloads"])
            else:
                m[key].append(x)
    for x in m["end_to_end"] + m["per_layer"]:
        # a metric every cell reported lists none; now one cell does not
        if "workloads" not in x and x["name"] not in ("setup_s", "compile_s",
                                                      "window_compiles"):
            raise AssertionError(x["name"])
    dump(os.path.join(root, "BENCHMARK.json"), m)
    done = run_py("--check", root=root)
    assert done.returncode == 0, done.stdout
    done = run_py("--workload", "featurize-jpeg", "--rehearse", "--trace", "1",
                  root=root)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.splitlines()[-1])["correct"] is True
