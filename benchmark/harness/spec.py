"""BENCHMARK.json and the files it names.

Nothing here knows a model, a cell or a metric: a cell names a configuration
and a traffic mix, the traffic mix names a driver, the configuration names a
reference, a metric's name is its reader's file name. A later PR adds files
and entries and edits nothing that is here.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, bench_dir=BENCH_DIR):
    """The module ``<bench_dir>/<kind>/<name>.py`` (names may hold dots and
    dashes, so they are loaded by path and not imported by name)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError("no %s named %r (looked for %s)" % (kind, name, path))
    mod_name = "bench_%s_%s" % (kind, "".join(
        c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """One parsed BENCHMARK.json, rooted at ``root`` (a checkout)."""

    def __init__(self, root=ROOT):
        self.root = root
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.doc["paths"][0])
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.cells = {w["name"]: w for w in self.doc["workloads"]}

    def cell(self, name):
        if name not in self.cells:
            raise SpecError("no workload %r in BENCHMARK.json (have: %s)"
                            % (name, ", ".join(sorted(self.cells))))
        return self.cells[name]

    def config(self, cell, tiny=False):
        """The configuration file of ``cell`` as a dict. ``tiny`` takes the
        file of the same name under ``configs/_tiny/`` (CPU rehearsal)."""
        path = os.path.join(self.root, self.configs[cell["config"]]["file"])
        if tiny:
            path = os.path.join(os.path.dirname(path), "_tiny",
                                os.path.basename(path))
        return load_json(path)

    def traffic(self, cell, tiny=False):
        sub = "traffic/_tiny" if tiny else "traffic"
        return load_json(os.path.join(self.bench_dir, sub,
                                      cell["traffic"] + ".json"))

    def metrics(self, kind, cell_name):
        """The ``end_to_end`` or ``per_layer`` entries that ``cell_name``
        reports (an entry without "workloads" is reported by every cell)."""
        return [m for m in self.doc[kind]
                if cell_name in m.get("workloads", [cell_name])]

    def module(self, kind, name):
        return load_module(kind, name, self.bench_dir)


def peaks(device_kind, bench_dir=BENCH_DIR):
    """The row of ``peaks.json`` for exactly this ``device_kind``. A device
    that is not in the table is an error, never a neighbour's row."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise SpecError("device kind %r is not in peaks.json (have: %s)"
                        % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]
