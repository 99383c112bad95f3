"""Batched serving example: prefill + greedy decode through the Engine.

  PYTHONPATH=src python examples/serve_lm.py
"""
import subprocess
import sys

raise SystemExit(subprocess.call(
    [sys.executable, "-m", "repro.launch.serve", "--arch", "mistral-nemo-12b",
     "--smoke", "--requests", "4", "--max-new", "12"]))
