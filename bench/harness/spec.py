"""Find what a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's
file (``configs[].file``) names its graph ``family`` and its
``reference``; the traffic mix (``bench/traffic/<traffic>.json``) names
its ``loop``.  Each of those, and each metric, is a file of its own:

    bench/families/<family>.py      builds the deployment from the seed
    bench/references/<reference>.py the plain reference it is checked by
    bench/loops/<loop>.py           set-up and the measured window
    bench/metrics/<metric>.py       one number from the run's record
    bench/limits/<cell>.json        the limits of the comparison

so a later cell, configuration or metric is a set of new files and
entries, with no edit to a file that is already here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys


class SpecError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


class Spec:
    """``BENCHMARK.json`` of the checkout at ``root`` and the files its
    names lead to."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.bench = os.path.join(self.root, "bench")
        self.doc = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        self._modules: dict[tuple[str, str], object] = {}

    # -- entries ----------------------------------------------------------
    def _entry(self, key: str, name: str) -> dict:
        for entry in self.doc.get(key, ()):
            if entry["name"] == name:
                return entry
        raise SpecError(f"BENCHMARK.json has no {key} entry {name!r}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        cfg = _load_json(os.path.join(self.root, entry["file"]))
        return {**cfg, "name": name}

    def traffic(self, name: str) -> dict:
        mix = _load_json(os.path.join(self.bench, "traffic", name + ".json"))
        return {**mix, "name": name}

    def limits(self, cell: str) -> dict:
        return _load_json(os.path.join(self.bench, "limits", cell + ".json"))

    def metrics(self, cell: str, *, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics (``trace`` False) or its
        per-layer metrics (``trace`` True), in ``BENCHMARK.json`` order.
        A metric without a ``workloads`` key belongs to every cell."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.doc.get(key, ())
                if cell in m.get("workloads", (cell,))]

    # -- code found by name -----------------------------------------------
    def module(self, kind: str, name: str):
        """Import ``bench/<kind>/<name>.py`` (a name may hold dots)."""
        key = (kind, name)
        if key not in self._modules:
            path = os.path.join(self.bench, kind, name + ".py")
            if not os.path.isfile(path):
                raise SpecError(f"missing file {path}")
            mod_name = "_bench_{}_{}_{}".format(
                kind, name.replace(".", "_").replace("-", "_"),
                abs(hash(path)))
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]
