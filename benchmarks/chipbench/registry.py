"""Finds what ``BENCHMARK.json`` names: a cell's configuration and traffic
files, the driver of a traffic kind, the reader of a per-layer metric.

A later PR adds a configuration, a traffic mix, a kind of driver or a metric
by adding a file (``configs/<name>.json``, ``traffic/<name>.json``,
``traffic_kinds/<kind>.py``, ``layer_metrics/<name>.py``, ``reference/<name>.py``)
under any directory
that ``BENCHMARK.json``'s ``paths`` lists, and an entry in ``BENCHMARK.json``.
Nothing here holds a list of names.
"""

import importlib
import importlib.util
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NotFound(LookupError):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def search_dirs(bench: dict, root: str = ROOT) -> List[str]:
    """This directory first, then every directory of ``paths``."""
    dirs = [HERE]
    for p in bench.get("paths", []):
        d = os.path.abspath(os.path.join(root, p))
        if d not in dirs:
            dirs.append(d)
    return dirs


def find(sub: str, filename: str, dirs: List[str]) -> str:
    for d in dirs:
        path = os.path.join(d, sub, filename)
        if os.path.isfile(path):
            return path
    raise NotFound(f"no {sub}/{filename} under any of {dirs}")


def load_json(sub: str, name: str, dirs: List[str]) -> dict:
    with open(find(sub, name + ".json", dirs)) as f:
        return json.load(f)


def load_module(sub: str, name: str, dirs: List[str]):
    path = find(sub, name + ".py", dirs)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: str):
    """``"package.module:attribute"`` -> the object: a configuration names the
    program's builder of its model in data, so a new family edits no driver."""
    module, _, attr = spec.partition(":")
    obj = importlib.import_module(module)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


def cell_of(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise NotFound(f"BENCHMARK.json has no workload {workload!r} "
                   f"(has {[w['name'] for w in bench['workloads']]})")


def config_file_of(bench: dict, config: str, root: str = ROOT) -> str:
    for c in bench["configs"]:
        if c["name"] == config:
            return os.path.join(root, c["file"])
    raise NotFound(f"BENCHMARK.json has no config {config!r}")


def metrics_of(bench: dict, group: str, workload: str) -> List[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports: those
    with no ``workloads`` key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def rehearsal_view(doc: dict) -> dict:
    """The file's ``rehearsal`` section laid over it, one level deep: the tiny
    sizes the CPU rehearsal runs. Never used on a chip."""
    out = {k: v for k, v in doc.items() if k != "rehearsal"}
    for k, v in doc.get("rehearsal", {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out
