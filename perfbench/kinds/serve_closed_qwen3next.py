"""Closed-loop serving of Qwen3-Next: `serve_closed_deepseekv2.run_loop`
(the loop, the window, the tracer, the collector at rest, the evidence)
around `_serve_qwen3next`: its wrapper and three lines."""
from __future__ import annotations

from perfbench import harness
from perfbench.kinds import _serve_qwen3next
from perfbench.kinds.serve_closed_deepseekv2 import run_loop


def run(ctx) -> harness.Outcome:
    return run_loop(ctx, _serve_qwen3next)
