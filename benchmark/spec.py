"""Finds everything a cell needs by the names in ``BENCHMARK.json`` (no JAX).

One cell = one entry of ``workloads``: a configuration (a directory under
``benchmark/configs/``), a traffic mix (a data file under
``benchmark/traffic/``) and a number of chips.  A later PR adds a cell by
adding files and entries; nothing here names a particular cell.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
#: never a key of ``reduced``: widths keep their published value
SIZED = ("hidden", "intermediate", "latent", "state", "proj", "head")


def names_a_width(key: str) -> bool:
    """A hidden, intermediate, latent, state or projection size, a key that
    ends in ``_dim`` or ``_rank``, a head size, an expansion factor, the
    experts per token (``num_hidden_layers`` is depth, and is not one)."""
    low = key.lower()
    return (low.endswith(("_dim", "_rank")) or low == "width"
            or "expansion" in low or "experts_per_tok" in low
            or (low.endswith(("_size", "_width"))
                and any(w in low for w in SIZED)))


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    spec["_root"] = root
    return spec


def _one(entries: list, name: str, what: str) -> dict:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise SpecError(f"{what} {name!r}: {len(hits)} entries in "
                        f"BENCHMARK.json (known: "
                        f"{[e['name'] for e in entries]})")
    return hits[0]


def cell(spec: dict, workload: str) -> dict:
    """The cell with its configuration and traffic files read in."""
    entry = dict(_one(spec["workloads"], workload, "workload"))
    cfg_entry = _one(spec["configs"], entry["config"], "config")
    root = spec["_root"]
    with open(os.path.join(root, cfg_entry["file"]), encoding="utf-8") as f:
        config = json.load(f)
    package = package_of(spec)
    traffic_path = os.path.join(root, package, "traffic",
                                entry["traffic"] + ".json")
    with open(traffic_path, encoding="utf-8") as f:
        traffic = json.load(f)
    entry.update(config_entry=cfg_entry, config_values=config,
                 traffic_values=traffic, package=package,
                 config_package=os.path.dirname(cfg_entry["file"])
                 .replace("/", "."))
    return entry


def package_of(spec: dict) -> str:
    """The directory (= package) the command's program lives in."""
    return os.path.dirname(spec["command"][-1]) or "."


def metrics_of(spec: dict, workload: str, group: str) -> list:
    """Metrics of ``group`` that this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def module(spec_or_package, *parts: str):
    """Import ``<package>.<parts...>``: a traffic generator, a feed plane,
    a per-layer reader or a configuration's module, found by name."""
    package = (spec_or_package if isinstance(spec_or_package, str)
               else package_of(spec_or_package))
    return importlib.import_module(".".join([package.replace("/", "."),
                                             *parts]))


# ---------------------------------------------------------------------------
# The contract's static rules (run by the tests, and before every run)
# ---------------------------------------------------------------------------


def _name(value, what: str) -> None:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise SpecError(f"{what}: {value!r} is not a name")


def _line(value, what: str) -> None:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value):
        raise SpecError(f"{what}: want 1..200 characters on one line")


def _keys(entry: dict, want: set, optional: set, what: str) -> None:
    keys = set(entry)
    if not want <= keys or not keys <= want | optional:
        raise SpecError(f"{what}: keys {sorted(keys)}, want {sorted(want)}"
                        f" (+ {sorted(optional)})")


def validate(spec: dict) -> None:
    """Raise :class:`SpecError` on anything the contract refuses before a
    run.  Checks the file only; that each reader and generator exists is
    :func:`validate_files`."""
    keys = {k for k in spec if not k.startswith("_")}
    if keys != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(keys)}")
    paths, command = spec["paths"], spec["command"]
    if not 1 <= len(paths) <= 16 or not 1 <= len(command) <= 32:
        raise SpecError("paths: 1..16 entries, command: 1..32 words")
    for p in paths:
        if (not PATH_RE.match(p) or p.startswith("/")
                or ".." in p.split("/")):
            raise SpecError(f"path {p!r}")
    for word in command:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise SpecError(f"command word {word!r} leaves the repo")
    if not (isinstance(spec["run_seconds"], int)
            and 1 <= spec["run_seconds"] <= 51):
        raise SpecError("run_seconds: a whole number 1..51")

    def under_paths(path: str) -> bool:
        return any(path == p or path.startswith(p.rstrip("/") + "/")
                   for p in paths)

    configs = spec["configs"]
    if not 1 <= len(configs) <= 24:
        raise SpecError("configs: 1..24")
    for c in configs:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(),
              f"config {c.get('name')}")
        _name(c["name"], "config name")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if not PATH_RE.match(c["file"]) or not under_paths(c["file"]):
            raise SpecError(f"config file {c['file']!r} not under paths")
        if len(c["reduced"]) > 16:
            raise SpecError("reduced: at most 16 keys")
        for key in c["reduced"]:
            _name(key, "reduced key")
            if names_a_width(key):
                raise SpecError(f"reduced names a width: {key!r}")
    if len({c["file"] for c in configs}) != len(configs):
        raise SpecError("two configurations share a file")

    cells = spec["workloads"]
    if not 1 <= len(cells) <= 24:
        raise SpecError("workloads: 1..24")
    config_names = {c["name"] for c in configs}
    for w in cells:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(),
              f"workload {w.get('name')}")
        for key in ("name", "config", "traffic"):
            _name(w[key], f"workload {key}")
        _line(w["why"], "workload why")
        if w["chips"] not in (1, 4):
            raise SpecError(f"{w['name']}: chips {w['chips']!r}")
        if w["config"] not in config_names:
            raise SpecError(f"{w['name']}: unknown config {w['config']!r}")
    if len({(w["config"], w["traffic"]) for w in cells}) != len(cells):
        raise SpecError("a pair of configuration and traffic appears twice")
    if config_names != {w["config"] for w in cells}:
        raise SpecError("a configuration is used by no cell")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise SpecError(f"{four} four-chip cells of {len(cells)}")

    cell_names = {w["name"] for w in cells}
    e2e, per_layer = spec["end_to_end"], spec["per_layer"]
    if not 1 <= len(e2e) <= 16 or not 1 <= len(per_layer) <= 128:
        raise SpecError("end_to_end: 1..16, per_layer: 1..128")
    for m in e2e:
        _keys(m, {"name", "unit", "better", "bound", "source"},
              {"workloads"}, f"metric {m.get('name')}")
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"{m['name']}: end-to-end source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            raise SpecError(f"{m['name']}: bound {m['bound']}")
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        raise SpecError("no setup_s")
    for m in per_layer:
        _keys(m, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, f"metric {m.get('name')}")
        _line(m["layer"], "layer")
        if m["source"] not in SOURCES:
            raise SpecError(f"{m['name']}: source {m['source']!r}")
        if m["moves"] not in e2e_names:
            raise SpecError(f"{m['name']} moves unknown {m['moves']!r}")
    for m in e2e + per_layer:
        _name(m["name"], "metric name")
        if not UNIT_RE.match(m["unit"]):
            raise SpecError(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"{m['name']}: better {m['better']!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                raise SpecError(f"{m['name']}: unknown workload {w!r}")
    for group in (configs, cells, e2e + per_layer):
        names = [e["name"] for e in group]
        if len(set(names)) != len(names):
            raise SpecError(f"duplicate name among {names}")
    for w in cell_names:
        mine = {m["name"] for m in metrics_of(spec, w, "end_to_end")}
        if "setup_s" not in mine or len(mine) < 2:
            raise SpecError(f"{w}: wants setup_s and one more metric")
        if not metrics_of(spec, w, "per_layer"):
            raise SpecError(f"{w}: no per-layer metric")
        for m in metrics_of(spec, w, "per_layer"):
            if m["moves"] not in mine:
                raise SpecError(f"{m['name']} moves {m['moves']}, which "
                                f"{w} does not report")


def validate_files(spec: dict) -> None:
    """Every name in the file leads to the files that carry it."""
    root = spec["_root"]
    package = package_of(spec)
    for m in spec["per_layer"]:
        path = os.path.join(root, package, "metrics", m["name"] + ".py")
        if not os.path.isfile(path):
            raise SpecError(f"per-layer metric {m['name']}: no {path}")
    for w in spec["workloads"]:
        c = cell(spec, w["name"])
        config_dir = os.path.dirname(os.path.join(root,
                                                  c["config_entry"]["file"]))
        for need in ("reference.py", "work.py", "program.py"):
            if not os.path.isfile(os.path.join(config_dir, need)):
                raise SpecError(f"config {w['config']}: no {need}")
        traffic = c["traffic_values"]
        for kind, sub in (("generator", "traffic"), ("feed", "feeds")):
            path = os.path.join(root, package, sub, traffic[kind] + ".py")
            if not os.path.isfile(path):
                raise SpecError(f"traffic {w['traffic']}: {kind} "
                                f"{traffic[kind]!r} has no {path}")
        missing = [k for k in c["config_entry"]["reduced"]
                   if k not in c["config_values"].get("reduced", {})]
        if missing:
            raise SpecError(f"config {w['config']}: reduced {missing} not "
                            "explained in its file")
