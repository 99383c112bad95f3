"""The harness as a later change meets it: a configuration, a traffic mix
and a per-layer metric added as files and entries only; and the command
refusing to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

from perfbench import run, spec

READER = '''"""prompt_tokens: prompt tokens offered in the window."""


def read(run):
    return sum(len(r.prompt) for r in run.requests) or None
'''


def test_cell_added_as_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load()
    c = spec.config(bench, "olmo-1b")
    c.update(name="tiny-dense", num_layers=2, d_model=64, num_heads=4,
             num_kv_heads=2, head_dim=16, d_ff=96, vocab_size=512)
    c["serving"].update(n_slots=4, cache_len=64)
    (root / "perfbench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(c))
    mix = {"kind": "open", "rate_rps": 4.0, "drain_s": 20,
           "prompt_len": {"median": 16, "sigma": 0.5, "buckets": [8, 16]},
           "output_len": {"median": 8, "sigma": 0.5, "buckets": [4, 8]}}
    (root / "perfbench" / "traffic" / "open.json").write_text(
        json.dumps(mix))
    (root / "perfbench" / "metrics" / "prompt_tokens.py").write_text(READER)
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "perfbench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.open", "config": "tiny-dense",
                               "traffic": "open", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny.open")
    bench["per_layer"].append({"name": "prompt_tokens", "unit": "tokens",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler and KV",
                               "moves": "ttft_p90_ms",
                               "workloads": ["tiny.open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    found = spec.load(str(root))
    cell = spec.cell(found, "tiny.open")
    cfg = spec.config(found, cell["config"], str(root))
    got_mix = spec.traffic(cell["traffic"], str(root))
    assert cfg["name"] == "tiny-dense" and got_mix == mix
    assert [m["name"] for m in spec.metrics(found, "tiny.open", False)] \
        == [bench["end_to_end"][0]["name"], "setup_s"]
    assert "prompt_tokens" in [m["name"] for m in
                               spec.metrics(found, "tiny.open", True)]
    for traced in (False, True):
        out = run.run_cell(found, "tiny.open", cfg, got_mix, 5, 3.0,
                           traced, trace_dir=str(tmp_path / "trace"),
                           t_start=time.perf_counter(), root=str(root))
        assert out["correct"], out["checks"]
        # 4 requests/s for 3 s
        assert out["failed"] == 0 and out["attempted"] == 12
        if traced:
            assert out["metrics"]["prompt_tokens"]["unit"] == "tokens"
            assert out["metrics"]["prompt_tokens"]["value"] > 0
        else:
            assert set(out["metrics"]) == {"ttft_p90_ms", "setup_s"}


def test_metric_split_by_cell_group_shares_its_reader():
    assert spec.reader("host_gap_ms.open").__doc__ \
        == spec.reader("host_gap_ms.backlog").__doc__
    assert spec.reader("decode_mfu.backlog").read.__name__ == "read"


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "olmo1b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    p = _command(spec.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(os.path.join(spec.ROOT, "perfbench"),
                    tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    p = _command(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout
