"""Find the benchmark's parts by name.

``BENCHMARK.json`` at the checkout's root names every configuration, cell
and metric.  Each has a file of its own under ``bench/``:

* configuration ``<c>``: the file named in its ``configs`` entry;
* cell ``<w>``: ``bench/workloads/<w>.json`` (its traffic or job);
* metric ``<m>``: ``bench/metrics/<m>.py``, a reader with ``read(run)``.

Nothing here knows a cell by name, so a later change adds a part by adding
its file and its entry.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Optional


class Benchmark:
    """``BENCHMARK.json`` and the files it names, below ``root``."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench_dir = self.root / "bench"

    def workload(self, name: str) -> dict:
        """The cell's entry in ``BENCHMARK.json`` merged under its file."""
        entries = {w["name"]: w for w in self.spec["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {sorted(entries)})")
        path = self.bench_dir / "workloads" / f"{name}.json"
        data = json.loads(path.read_text())
        data.update(entries[name])
        return data

    def config(self, name: str) -> dict:
        entries = {c["name"]: c for c in self.spec["configs"]}
        if name not in entries:
            raise KeyError(f"no config {name!r} in BENCHMARK.json")
        return json.loads((self.root / entries[name]["file"]).read_text())

    def metrics_for(self, workload: str, traced: bool) -> list:
        """The metric entries this cell reports: its end-to-end metrics
        with ``traced`` false, its per-layer ones with it true."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str) -> Callable[[object], Optional[float]]:
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
