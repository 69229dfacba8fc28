"""End-to-end serving driver: the BCEdge scheduler batching REAL model
inference (reduced qwen3 running under jit on this host), wall-clock
latencies and all.

Thin wrapper around the importable entry point
``repro.launch.engine_serve`` (also reachable as
``python -m repro.launch.serve --engine --reduced
[--exec-mode continuous]``).

Run:  PYTHONPATH=src python examples/serve_llm.py [round|continuous]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch import engine_serve  # noqa: E402


def main(exec_mode: str = "round"):
    engine_serve.main(exec_mode=exec_mode, reduced=True)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "round")
