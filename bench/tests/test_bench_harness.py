"""The harness: it refuses to run without a TPU, and it finds a
configuration, a traffic mix and a per-layer metric added as files and
entries alone."""
import tinyroot

import json
import os
import subprocess
import sys


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(tinyroot.BENCH / "run.py"),
                        "--workload", "mset-surveil-b512", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tinyroot.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_new_files_and_entries_make_a_new_cell(tmp_path, capsys):
    root = tinyroot.make(tmp_path)
    b = root / "bench"
    conf = json.loads((b / "configs" / "mset2-1024x4096.json").read_text())
    conf.update(name="mset2-24x96", n_signals=24, n_memvec=96, n_train=384,
                gamma=7.0)
    (b / "configs" / "mset2-24x96.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "surveil-b512.json").read_text())
    mix.update(batch=32, fault_start=8)
    (b / "traffic" / "surveil-b32.json").write_text(json.dumps(mix))
    (b / "limits" / "mset-small-b32.json").write_text(
        (b / "limits" / "mset-surveil-b512.json").read_text())
    (b / "metrics" / "calls.small.py").write_text(
        "def read(ctx):\n"
        "    return ctx.layer['calls'] + ctx.traced['calls']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "mset2-24x96", "source": "test",
                            "file": "bench/configs/mset2-24x96.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "mset-small-b32", "config": "mset2-24x96",
                              "traffic": "surveil-b32", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "mset-surveil-b512" in m.get("workloads", ()):
            m["workloads"].append("mset-small-b32")
    spec["per_layer"].append({"name": "calls.small", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "obs_per_s",
                              "workloads": ["mset-small-b32"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    line = tinyroot.run(root, "mset-small-b32", capsys)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"obs_per_s", "batch_p95_ms", "setup_s"}
    line = tinyroot.run(root, "mset-small-b32", capsys, trace=1)
    assert line["metrics"]["calls.small"]["value"] == line["attempted"]
