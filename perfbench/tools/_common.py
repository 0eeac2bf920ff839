"""Shared by the tools: a cell's context outside a run."""
from __future__ import annotations

import os

from perfbench import harness
from perfbench.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def context(root: str, workload: str, cpu: bool, seed: int = 0,
            seconds: float = 0.0) -> harness.Context:
    import jax
    manifest = Manifest(root)
    cell = manifest.cell(workload)
    if cpu:
        devices = jax.devices()[:int(cell["chips"])]
    else:
        devices = harness.require_chips(int(cell["chips"]))
        harness.enable_cache()
    return harness.Context(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=manifest.traffic(cell["traffic"]), seed=seed,
        seconds=seconds, trace=False, devices=devices)
