import json
import os
import shutil
import subprocess
import sys

import registry

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_every_name_in_benchmark_json_has_its_file():
    b = registry.load_benchmark()
    for w in b["workloads"]:
        registry.arch(registry.config(b, w["config"]))
        mix = registry.traffic(w["traffic"])
        assert mix["kind"] in ("open_loop", "offline", "design_sweep")
        assert registry.end_to_end(b, w["name"])
        assert registry.per_layer(b, w["name"])
    for m in b["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


def test_new_cell_mix_and_metric_need_only_new_files(tmp_path):
    """A copy of the benchmark gains a cell, a mix and a metric by adding
    files and entries only; nothing else is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench")
    b = registry.load_benchmark()
    bench = root / "bench"
    chat = json.loads((bench / "traffic" / "chat.json").read_text())
    chat["rate_per_s"] = 1.0
    (bench / "traffic" / "slowchat.json").write_text(json.dumps(chat))
    (bench / "metrics" / "steps.serve.py").write_text(
        "def read(run):\n    return float(len(run.steps)) or None\n")
    b["workloads"].append(dict(name="granite8b-slowchat",
                               config="granite-8b", traffic="slowchat",
                               chips=1, why="test"))
    b["end_to_end"][0]["workloads"].append("granite8b-slowchat")
    b["per_layer"].append(dict(name="steps.serve", unit="steps",
                               better="higher", source="program_span",
                               layer="serving engine (serving/engine.py)",
                               moves="ttft_p50_ms",
                               workloads=["granite8b-slowchat"]))
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    b2 = registry.load_benchmark(str(root))
    w = registry.workload(b2, "granite8b-slowchat")
    assert registry.config(b2, w["config"], str(root))["name"] == \
        "granite-8b"
    assert registry.traffic(w["traffic"], str(bench))["rate_per_s"] == 1.0
    names = [m["name"] for m in registry.per_layer(b2, w["name"])]
    assert names == ["steps.serve"]
    read = registry.metric_reader("steps.serve", str(bench))
    assert read(type("R", (), {"steps": [1, 2]})) == 2.0


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_a_cpu():
    p = _run(["--workload", "granitemoe-sitesweep", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(["--workload", "granite8b-chat", "--seed", "1", "--seconds",
              "1", "--trace", "0"], str(tmp_path))
    assert p.returncode != 0 and "{" not in p.stdout
