"""Find a cell's parts by name: configuration, traffic mix, metric readers.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``configs/<config>.json``, its traffic mix
``traffic/<traffic>.json``; each per-layer metric is read by
``metrics/<metric>.py`` (a ``read(run)`` function), each kernel's operations
and bytes come from ``roofline/<kernel>.py`` and the chip's peaks from
``peaks.json``.  The kinds a configuration names are files too: its event
generator ``generators/<kind>.py``, its engine ``engines/<kind>.py`` and
its control ``controls/<kind>.py``.  Adding any of them is adding a file:
nothing here names a cell, a mix, a metric or a kind.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

#: ``benchmarks/chip``: the directory that holds the benchmark's files
CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the checkout's root, where ``BENCHMARK.json`` lives
REPO_DIR = os.path.dirname(os.path.dirname(CHIP_DIR))

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def check_name(name: str) -> str:
    """A name is a file name too: refuse anything that could leave its
    directory or is not a name under the benchmark's rules."""
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo: str = REPO_DIR) -> dict:
    return _load_json(os.path.join(repo, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_config(name: str, root: str = CHIP_DIR) -> dict:
    return _load_json(os.path.join(root, "configs", check_name(name) + ".json"))


def load_mix(name: str, root: str = CHIP_DIR) -> dict:
    return _load_json(os.path.join(root, "traffic", check_name(name) + ".json"))


def _load_module(path: str, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = CHIP_DIR
                ) -> Callable[[Any], Optional[float]]:
    """``metrics/<metric>.py``'s ``read(run)``: the metric's value, or None
    where the run holds nothing for it to read."""
    path = os.path.join(root, "metrics", check_name(metric) + ".py")
    return _load_module(path, "chipbench_metric_" + metric.replace(".", "_")
                        ).read


def load_roofline(kernel: str, root: str = CHIP_DIR):
    """``roofline/<kernel>.py``: ``cost(shapes) -> (flops, bytes)`` of one
    call of the kernel at those shapes."""
    path = os.path.join(root, "roofline", check_name(kernel) + ".py")
    return _load_module(path, "chipbench_roofline_" + kernel)


def load_generator(kind: str, root: str = CHIP_DIR):
    """``generators/<kind>.py``: ``types(gen)`` and ``draw(stream, n)``."""
    path = os.path.join(root, "generators", check_name(kind) + ".py")
    return _load_module(path, "chipbench_generator_" + kind)


def load_engine(kind: str, root: str = CHIP_DIR):
    """``engines/<kind>.py``: ``build(cfg)``, the program's engine as the
    configuration's ``engine`` group states it."""
    path = os.path.join(root, "engines", check_name(kind) + ".py")
    return _load_module(path, "chipbench_engine_" + kind)


def load_control(kind: str, root: str = CHIP_DIR):
    """``controls/<kind>.py``: ``outputs(cfg, cols, type_names, n)``, what
    the control reports in the program's place."""
    path = os.path.join(root, "controls", check_name(kind) + ".py")
    return _load_module(path, "chipbench_control_" + kind)


def load_peaks(device_kind: str, root: str = CHIP_DIR) -> dict:
    """The published peaks of one chip of this kind.  A kind the table does
    not hold is an error: no default stands in for a chip."""
    table = _load_json(os.path.join(root, "peaks.json"))
    for entry in table["chips"]:
        if device_kind in entry["device_kinds"]:
            return dict(entry, source=table["source"])
    raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json "
                   f"(known: {[k for e in table['chips'] for k in e['device_kinds']]})")


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``trace`` off, its per-layer metrics with it on.  A metric without a
    ``workloads`` list belongs to every cell."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_readers(bench: dict, cell: str, root: str = CHIP_DIR
                   ) -> Dict[str, Callable]:
    return {m["name"]: load_reader(m["name"], root)
            for m in cell_metrics(bench, cell, trace=True)}
