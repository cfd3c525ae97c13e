"""The manifest check: every rule the contract states for BENCHMARK.json
that can be checked without a run. Run before any chip time:

    python benchmark/manifest.py [BENCHMARK.json]

and as a CPU test (tests/benchmark/test_manifest.py). ``problems()``
returns the list of what is wrong; an empty list passes.
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"host_clock", "device_trace"}
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "head_size", "expansion", "experts_per_tok",
               "feature_dim", "label_dim", "dim")
MAX_BYTES = 64 * 1024
# what a configuration's file names besides its sizes: the Python files
# that hold what is particular to its model family, and the functions
# the harness and check.py call there (benchmark/sage_reference.py and
# benchmark/costs.py state what each does)
CONFIG_FILES = {
    "reference": ("init_state", "drawn_fanouts", "drawn_hops",
                  "reference_batch", "batch_rows", "first_gradient",
                  "compared_state", "train_steps"),
    "costs": ("step_costs",),
}
# a full check: 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s a cell
# to compile, 1200 s spare, inside 43200 s, at the full 24 cells
MAX_RUN_SECONDS = (43200 - 1200 - 24 * 180) // (2 + 14 * 24) - 60


def _line(s, what, out, limit=200):
    if not isinstance(s, str) or not 1 <= len(s) <= limit or \
            "\n" in s or "\t" in s or "\r" in s:
        out.append(f"{what}: must be 1 to {limit} characters on one line, "
                   "no tab")


def _name(s, what, out):
    if not isinstance(s, str) or not NAME.match(s):
        out.append(f"{what} {s!r}: must match {NAME.pattern}")


def _keys(entry, required, optional, what, out):
    keys = set(entry)
    if not required <= keys or not keys <= required | optional:
        out.append(f"{what}: keys must be {sorted(required)}"
                   + (f" plus optionally {sorted(optional)}" if optional
                      else "") + f", found {sorted(keys)}")


def _unique(names, what, out):
    seen = set()
    for n in names:
        if n in seen:
            out.append(f"{what} {n!r} appears twice")
        seen.add(n)


def bound_names(path: str) -> set:
    """The names a Python file binds at its top level (functions,
    classes, assignments, imports), read as text: nothing is imported."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            names.update(t.id for t in targets if isinstance(t, ast.Name))
    return names


def problems(path: str, traffic_dir: str | None = None,
             layers_dir: str | None = None) -> list:
    out: list = []
    root = os.path.dirname(os.path.abspath(path))
    if os.path.getsize(path) > MAX_BYTES:
        out.append("BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        m = json.load(f)
    if set(m) != TOP_KEYS:
        out.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}")
        return out

    # ---- paths and command ----
    paths = m["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        out.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not isinstance(p, str) or not PATH.match(p) or p.startswith("/") \
                or ".." in p.split("/"):
            out.append(f"path {p!r}: relative, letters digits _ . - / only")
        elif not os.path.isdir(os.path.join(root, p)):
            out.append(f"path {p!r} is not a directory")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/")
                   for p in paths)

    cmd = m["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        out.append("command: a list of 1 to 32 strings")
        cmd = []
    for word in cmd:
        _line(word, f"command word {word!r}", out)
        if isinstance(word, str):
            if word.startswith("/") or ".." in word.split("/"):
                out.append(f"command word {word!r} leaves the repo")
            elif os.path.exists(os.path.join(root, word)) and \
                    not under_paths(word):
                out.append(f"command names {word!r}, a file outside paths")
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or \
            not 1 <= rs <= MAX_RUN_SECONDS:
        out.append(f"run_seconds: a whole number from 1 to {MAX_RUN_SECONDS}")

    traffic_dir = traffic_dir or os.path.join(root, paths[0] if paths else "",
                                              "traffic")
    layers_dir = layers_dir or os.path.join(root, paths[0] if paths else "",
                                            "layers")

    # ---- configurations ----
    configs = m["configs"]
    if not 1 <= len(configs) <= 24:
        out.append("configs: 1 to 24")
    _unique([c.get("name") for c in configs], "configuration", out)
    _unique([c.get("file") for c in configs], "configuration file", out)
    for c in configs:
        what = f"config {c.get('name')!r}"
        _keys(c, CONFIG_KEYS, set(), what, out)
        _name(c.get("name"), "config name", out)
        _line(c.get("source"), what + " source", out)
        _line(c.get("why"), what + " why", out)
        red = c.get("reduced")
        if not isinstance(red, list) or len(red) > 16:
            out.append(what + ": reduced is a list of at most 16 keys")
            red = []
        for key in red:
            _name(key, what + " reduced key", out)
            if isinstance(key, str) and (
                key.endswith(("_dim", "_rank"))
                or any(w in key for w in WIDTH_WORDS)
            ):
                out.append(f"{what}: reduced may never name a width "
                           f"({key!r})")
        file = c.get("file")
        if not isinstance(file, str) or not PATH.match(file) or \
                not under_paths(file):
            out.append(what + f": file {file!r} must lie under paths")
        elif not os.path.isfile(os.path.join(root, file)):
            out.append(what + f": file {file!r} does not exist")
        else:
            with open(os.path.join(root, file)) as f:
                cfg = json.load(f)
            for key, functions in CONFIG_FILES.items():
                named = cfg.get(key)
                if not named or not os.path.isfile(os.path.join(root, named)):
                    out.append(what + f": its {key} file {named!r} "
                               "not found")
                elif not under_paths(os.path.normpath(named)):
                    out.append(what + f": its {key} file lies outside paths")
                else:
                    lacks = sorted(set(functions) - bound_names(
                        os.path.join(root, named)))
                    if lacks:
                        out.append(what + f": its {key} file {named!r} "
                                   f"lacks {lacks}")
            if "limits" not in cfg:
                out.append(what + ": no limits for the numbers compared")

    # ---- cells ----
    cells = m["workloads"]
    if not 1 <= len(cells) <= 24:
        out.append("workloads: 1 to 24 cells")
    _unique([w.get("name") for w in cells], "workload", out)
    _unique([(w.get("config"), w.get("traffic")) for w in cells],
            "pair of configuration and traffic", out)
    config_names = {c.get("name") for c in configs}
    for w in cells:
        what = f"workload {w.get('name')!r}"
        _keys(w, WORKLOAD_KEYS, set(), what, out)
        for k in ("name", "config", "traffic"):
            _name(w.get(k), what + " " + k, out)
        _line(w.get("why"), what + " why", out)
        if w.get("chips") not in (1, 4):
            out.append(what + ": chips is 1 or 4")
        if w.get("config") not in config_names:
            out.append(what + f": unknown configuration {w.get('config')!r}")
        if isinstance(w.get("traffic"), str) and not any(
            os.path.isfile(os.path.join(traffic_dir, w["traffic"] + ext))
            for ext in TRAFFIC_EXT
        ):
            out.append(what + f": no traffic file for {w['traffic']!r}")
    used = {w.get("config") for w in cells}
    for c in config_names - used:
        out.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} cells ask for 4 chips; at most "
                   f"{max(1, len(cells) // 4)} may")
    cell_names = {w.get("name") for w in cells}

    # ---- metrics ----
    e2e, layer = m["end_to_end"], m["per_layer"]
    if not 1 <= len(e2e) <= 16:
        out.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(layer) <= 128:
        out.append("per_layer: 1 to 128 metrics")
    _unique([x.get("name") for x in e2e + layer], "metric", out)
    if "setup_s" not in {x.get("name") for x in e2e}:
        out.append("end_to_end must hold setup_s")

    def cells_of(metric) -> set:
        return set(metric.get("workloads", cell_names))

    for x in e2e + layer:
        what = f"metric {x.get('name')!r}"
        _name(x.get("name"), "metric name", out)
        if not isinstance(x.get("unit"), str) or not UNIT.match(x["unit"]):
            out.append(what + f": unit {x.get('unit')!r} must match "
                       + UNIT.pattern)
        if x.get("better") not in ("lower", "higher"):
            out.append(what + ": better is lower or higher")
        if x.get("source") not in SOURCES:
            out.append(what + f": source must be one of {sorted(SOURCES)}")
        if "workloads" in x:
            if not x["workloads"] or not set(x["workloads"]) <= cell_names:
                out.append(what + ": workloads must list existing cells")
    for x in e2e:
        what = f"end-to-end metric {x.get('name')!r}"
        _keys(x, E2E_KEYS, {"workloads"}, what, out)
        if x.get("source") not in E2E_SOURCES:
            out.append(what + ": source is host_clock or device_trace")
        b = x.get("bound")
        if not isinstance(b, (int, float)) or isinstance(b, bool) or \
                not 0.01 <= b <= 0.1:
            out.append(what + ": bound from 0.01 to 0.1")
    e2e_cells = {x.get("name"): cells_of(x) for x in e2e}
    for x in layer:
        what = f"per-layer metric {x.get('name')!r}"
        _keys(x, LAYER_KEYS, {"workloads"}, what, out)
        _name(x.get("layer"), what + " layer", out)
        moves = x.get("moves")
        if moves not in e2e_cells:
            out.append(what + f": moves {moves!r} is no end-to-end metric")
        elif not cells_of(x) <= e2e_cells[moves]:
            out.append(what + f": a cell it lists does not report {moves}")
        if "workloads" not in x:
            out.append(what + ": give it an explicit workloads list, so a "
                       "later cell can be added without editing it")
        name = x.get("name")
        if isinstance(name, str) and not os.path.isfile(
            os.path.join(layers_dir, name + ".py")
        ):
            out.append(what + ": no reader " + name + ".py under layers/")
        if isinstance(name, str) and name.endswith("_roofline") and \
                x.get("unit") != "%":
            out.append(what + ": a roofline share has the unit %")
    for w in cell_names:
        reported = [n for n, cs in e2e_cells.items() if w in cs]
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"workload {w!r}: reports setup_s and at least one "
                       "other end-to-end metric")
        if not any(w in cells_of(x) for x in layer):
            out.append(f"workload {w!r}: reports no per-layer metric")
    rooflines = [x for x in layer if str(x.get("name")).endswith("_roofline")]
    for r in rooflines:
        if not any(
            "mfu" in re.split(r"[._\-]", str(x.get("name")))
            and x.get("moves") == r.get("moves")
            and cells_of(r) <= cells_of(x)
            for x in layer
        ):
            out.append(f"roofline {r['name']!r}: no whole-step mfu metric "
                       "moves the same end-to-end metric in its cells")

    # ---- files under paths are named from name characters and / ----
    for p in paths:
        for d, _, files in os.walk(os.path.join(root, p)):
            if "__pycache__" in d:
                continue
            for fn in files:
                rel = os.path.relpath(os.path.join(d, fn), root)
                if not PATH.match(rel):
                    out.append(f"file {rel!r}: name characters and / only")
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = argv[0] if argv else os.path.join(here, "BENCHMARK.json")
    found = problems(path)
    for p in found:
        print("manifest:", p)
    print("manifest: %s" % ("ok" if not found else f"{len(found)} problem(s)"))
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
