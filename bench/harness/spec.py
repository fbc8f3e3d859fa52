"""Find the benchmark's pieces by name.

Everything that belongs to one configuration, one traffic mix, one metric,
one cell's correctness limits or one device kind sits in a file of its own
under the benchmark directory, and is found from the name that
``BENCHMARK.json`` gives it:

    configs/<config>.json        sizes of a model configuration, as run
    configs/<reference>.py       its plain reference (named by the config)
    configs/<arch>_arch.py       its block (named by the config): weights,
                                 the program's mapping, the work counts
    traffic/<traffic>.json       parameters of a traffic mix
    metrics/<metric>.py          a metric's reader: ``read(ctx) -> value``
    limits/<cell>.json           the limits that decide ``correct``
    peaks/<device_kind>.json     a chip's published peaks (spaces -> "_")

Adding a configuration, a cell, a mix, a metric or a chip adds files and
entries; no file that is already there changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(ValueError):
    """A name that the benchmark's files do not resolve."""


def _check_name(name: str) -> str:
    if not _NAME.match(name):
        raise SpecError(f"not a benchmark name: {name!r}")
    return name


def _load_json(path: Path) -> Dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """One benchmark definition: ``BENCHMARK.json`` plus the files it
    names, rooted at ``root`` (the repository) and ``bench_dir``."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
                 spec_file: str = "BENCHMARK.json"):
        self.root = Path(root)
        self.bench_dir = Path(bench_dir)
        self.spec = _load_json(self.root / spec_file)

    # -- entries of BENCHMARK.json --------------------------------------
    def cell(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload named {name!r}")

    def metric_specs(self, cell: str, trace: bool) -> List[Dict]:
        """The metrics a run of ``cell`` reports: its end-to-end metrics
        with ``--trace 0``, its per-layer metrics with ``--trace 1``.  A
        per-layer metric without a ``workloads`` key belongs to every cell
        that reports the end-to-end metric it moves."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in moved:
                out.append(m)
        return out

    # -- files found by name --------------------------------------------
    def config(self, name: str) -> Dict:
        return _load_json(self.bench_dir / "configs" / f"{_check_name(name)}.json")

    def traffic(self, name: str) -> Dict:
        return _load_json(self.bench_dir / "traffic" / f"{_check_name(name)}.json")

    def limits(self, cell: str) -> Dict:
        return _load_json(self.bench_dir / "limits" / f"{_check_name(cell)}.json")

    def reference(self, name: str):
        """The plain reference module a configuration names."""
        return _load_module(self.bench_dir / "configs" / f"{_check_name(name)}.py",
                            f"bench_reference_{name.replace('.', '_')}")

    def arch(self, name: str):
        """The architecture module a configuration names: its
        ``make_weights``, ``program_config``, ``program_params`` and
        ``counts``."""
        return _load_module(
            self.bench_dir / "configs" / f"{_check_name(name)}_arch.py",
            f"bench_arch_{name.replace('.', '_').replace('-', '_')}")

    def metric_reader(self, name: str) -> Callable:
        mod = _load_module(self.bench_dir / "metrics" / f"{_check_name(name)}.py",
                           f"bench_metric_{name.replace('.', '_').replace('-', '_')}")
        return mod.read

    def peaks(self, device_kind: str) -> Dict:
        """Published peaks of a chip; an unknown kind is an error."""
        fname = device_kind.replace(" ", "_")
        path = self.bench_dir / "peaks" / f"{fname}.json"
        if not _NAME.match(fname) or not path.is_file():
            raise SpecError(f"no peaks for device kind {device_kind!r} "
                            f"(looked for {path})")
        peaks = _load_json(path)
        if peaks.get("device_kind") != device_kind:
            raise SpecError(f"{path} describes {peaks.get('device_kind')!r}, "
                            f"not {device_kind!r}")
        return peaks


def read_metrics(bench: Bench, specs: List[Dict], ctx) -> Dict[str, Dict]:
    """Run each metric's reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out: Dict[str, Dict] = {}
    for m in specs:
        value: Optional[float] = bench.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
