"""`BENCHMARK.json` and the files its names lead to.

A cell names a configuration and a traffic mix; a per-layer metric names
a reader. Each lives in a file of its own that is found by name under
the directories `paths` lists, so a later PR adds a cell, a mix, a
configuration, a metric, a kind of traffic or a reader by adding files
and an entry — never by editing a file that is here.

  <path>/traffic/<traffic>.json        parameters of a traffic mix
  <path>/layer_metrics/<metric>.json   one per-layer metric
  <path>/kinds/<kind>.py               drives one kind of traffic
  <path>/readers/<reader>.py           one way of reducing evidence
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys


class ManifestError(ValueError):
    pass


def _read_json(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.data = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        self.paths = list(self.data["paths"])

    def _by_name(self, section, name):
        for entry in self.data[section]:
            if entry["name"] == name:
                return entry
        raise ManifestError(f"{name!r} is not among {section}: "
                            f"{[e['name'] for e in self.data[section]]}")

    def cell(self, name):
        return self._by_name("workloads", name)

    def find(self, subdir, filename):
        """The first `<path>/<subdir>/<filename>` that exists."""
        tried = []
        for p in self.paths:
            cand = os.path.join(self.root, p, subdir, filename)
            if os.path.isfile(cand):
                return cand
            tried.append(cand)
        raise ManifestError(f"no {subdir}/{filename} under paths: {tried}")

    def config(self, name):
        entry = self._by_name("configs", name)
        return _read_json(os.path.join(self.root, entry["file"]))

    def traffic(self, name):
        return _read_json(self.find("traffic", name + ".json"))

    def layer_metric(self, name):
        return _read_json(self.find("layer_metrics", name + ".json"))

    def module(self, subdir, name):
        """Import `<path>/<subdir>/<name>.py`, found by name."""
        path = self.find(subdir, name + ".py")
        modname = f"perfbench_found.{subdir}.{name}"
        if modname in sys.modules and getattr(
                sys.modules[modname], "__file__", None) == path:
            return sys.modules[modname]
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def metrics_for(self, section, cell_name):
        """Metrics of `section` that this cell reports: those without a
        `workloads` key, and those that list the cell."""
        return [m for m in self.data[section]
                if cell_name in m.get("workloads", [cell_name])]
