"""Find the pieces of a benchmark cell by name.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one kernel sits in a file of its own under
``bench/``; this module maps a name from ``BENCHMARK.json`` to that
file, so a new cell, mix, metric or kernel needs new files and entries
only:

* ``configs/<config>.json``  — a model configuration (the path is the
  ``file`` of its ``configs`` entry), which names its ``family``;
* ``families/<family>.py``   — everything that depends on the model
  family: the program's ``ArchConfig``, the weights, the engine's
  options, the prompts, how a request reaches the gateway, the read-back
  of a lane, the plain reference and the model FLOPs (``family_module``);
* ``mixes/<traffic>.json``   — the parameters of a traffic mix;
* ``limits/<cell>.json``     — the correctness limits of one cell;
* ``metrics/<metric>.py``    — a per-layer metric's reader, ``read(run)``;
* ``kernels/<kernel>.py``    — a kernel's ``cost(shapes)`` and the
  trace names its device events carry;
* ``peaks.json``             — peak rates per ``device_kind``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return _json(Path(root) / entry["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_mix(name: str, base: Path = BENCH) -> dict:
    return _json(Path(base) / "mixes" / f"{name}.json")


def load_limits(cell: str, base: Path = BENCH) -> dict:
    return _json(Path(base) / "limits" / f"{cell}.json")


def _module(path: Path, label: str):
    if not path.is_file():
        raise KeyError(f"no {label} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{label}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, base: Path = BENCH):
    """The module of per-layer metric ``name``; its ``read(run)`` returns
    the value, or None where the run has nothing to read."""
    return _module(Path(base) / "metrics" / f"{name}.py", "metric")


def kernel_model(name: str, base: Path = BENCH):
    """The module of kernel ``name``: ``TRACE_NAMES`` and
    ``cost(shapes) -> (flops, bytes)``."""
    return _module(Path(base) / "kernels" / f"{name}.py", "kernel")


def family_module(name: str, base: Path = BENCH):
    """The module of model family ``name``, which gives:

    * ``arch_config(cfg)`` — the program's ``ArchConfig``;
    * ``init_leaf(path, shape, key)`` — one float32 weight leaf, drawn
      from ``key``, by its path in the parameter tree;
    * ``engine_options(deployment)`` — the ``ServeEngine`` keywords;
    * ``prompt(cfg, n, rng)`` — the prompt token ids of a request;
    * ``inputs(seed)`` — what else a run draws its requests' content
      from (None where the prompt is all);
    * ``submit(runner, req, max_new)`` — the coroutine by which a request
      reaches the gateway, inside ``runner.in_flight(lane_key, req)``;
    * ``session(runner, req, t_base, ...)`` — one streaming session
      (families whose mixes stream only);
    * ``lane_key(request)`` — the key ``submit`` registered, from the
      engine's request;
    * ``read_lane(cache, slot, state)`` — one lane's planes, float32,
      ``{kind: {name: array}}``;
    * ``Reference(cfg, params)`` with ``judge(runner, req, tokens,
      planes=None)`` -> (token gaps, ``{kind: share off}`` or None);
    * ``request_flops(cfg, req, n_out)`` — the model FLOPs of one
      completed request."""
    return _module(Path(base) / "families" / f"{name}.py", "family")


def load_peaks(device_kind: str, base: Path = BENCH) -> dict:
    """Peak rates of ``device_kind``; an unknown device is an error."""
    table = _json(Path(base) / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])}); add its published "
                       f"peaks before measuring on it")
    return table["devices"][device_kind]


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The ``kind`` ("end_to_end" or "per_layer") metrics that ``cell``
    reports: those whose ``workloads`` list names it, or that have no
    such list."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
