"""BENCHMARK.json and the files it names.

Whatever belongs to one configuration, one traffic mix, one per-layer metric
or one cell's limits sits in a file of its own, found by name under the
manifest's ``paths``:

  <config.file>                   the configuration's sizes
  references/<config>.py          its plain reference (FAMILY, BUILDER)
  traffic/<traffic>.json          the mix; names its ``driver``
  drivers/<driver>.py             the general generator / loop for such mixes
  builders/<builder>.py           program-side construction for a family
  layer_metrics/<metric>.py       read(run) -> number or None
  limits/<workload>.json          the limits of ``correct`` for that cell

A later PR adds a model, a mix, a metric or a cell by adding files and
entries; nothing here names one.
"""
import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class Manifest:
    def __init__(self, path="BENCHMARK.json", root=None):
        self.path = os.path.abspath(path)
        # ``paths`` and ``file`` are relative to the checkout's root
        self.root = os.path.abspath(root or os.path.dirname(self.path))
        with open(self.path) as f:
            self.data = json.load(f)
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.workloads = {w["name"]: w for w in self.data["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.data["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.data["per_layer"]}
        self.run_seconds = int(self.data["run_seconds"])

    def find(self, *parts):
        """The first ``<path>/<parts...>`` that exists, over ``paths``."""
        for p in self.data["paths"]:
            cand = os.path.join(self.root, p, *parts)
            if os.path.exists(cand):
                return cand
        raise FileNotFoundError(
            "%s not found under paths %s" % (os.path.join(*parts),
                                             self.data["paths"]))

    def load_json(self, *parts):
        with open(self.find(*parts)) as f:
            return json.load(f)

    def load_module(self, *parts):
        path = self.find(*parts)
        name = "perfbench_file_" + re.sub(r"\W", "_",
                                          os.path.relpath(path, self.root))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def config(self, name):
        entry = self.configs[name]
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def cell_metrics(self, section, workload, moves=None):
        """Names of the metrics of ``section`` that cell reports."""
        out = []
        for m in self.data[section]:
            if workload not in m.get("workloads", [workload]):
                continue
            if moves is not None and m.get("moves") not in moves:
                continue
            out.append(m["name"])
        return out


def validate(data):
    """Every fault of ``data`` against the contract's names, units and keys,
    as a list of strings (empty: none found)."""
    errs = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            errs.append("%s: bad name %r" % (what, n))

    def line_ok(s, what):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 or \
                "\n" in s or "\t" in s:
            errs.append("%s: not one line of 1..200 characters" % what)

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(data) != want:
        errs.append("top-level keys %s != %s" % (sorted(data), sorted(want)))
        return errs
    if not 1 <= int(data["run_seconds"]) <= 51:
        errs.append("run_seconds out of 1..51")
    for w in data["command"]:
        line_ok(w, "command word")
    cfg_names, cells, e2e = set(), set(), set()
    for c in data["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append("config keys %s" % sorted(c))
        name_ok(c.get("name"), "config")
        line_ok(c.get("source"), "config source")
        line_ok(c.get("why"), "config why")
        for k in c.get("reduced", []):
            name_ok(k, "reduced key")
        if not any(c.get("file", "").startswith(p + "/")
                   for p in data["paths"]):
            errs.append("config file %r outside paths" % c.get("file"))
        cfg_names.add(c.get("name"))
    pairs = set()
    for w in data["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append("workload keys %s" % sorted(w))
        for k in ("name", "config", "traffic"):
            name_ok(w.get(k), "workload " + k)
        line_ok(w.get("why"), "workload why")
        if w.get("chips") not in (1, 4):
            errs.append("workload %s: chips must be 1 or 4" % w.get("name"))
        if w.get("config") not in cfg_names:
            errs.append("workload %s: unknown config" % w.get("name"))
        if (w.get("config"), w.get("traffic")) in pairs:
            errs.append("pair of config and traffic twice: %s" % w["name"])
        pairs.add((w.get("config"), w.get("traffic")))
        cells.add(w.get("name"))
    if len(cells) != len(data["workloads"]):
        errs.append("two cells share a name")
    if sum(w.get("chips") == 4 for w in data["workloads"]) > \
            max(1, len(data["workloads"]) // 4):
        errs.append("too many four-chip cells")
    used = {w.get("config") for w in data["workloads"]}
    if used != cfg_names:
        errs.append("configs not used by a cell: %s" % (cfg_names - used))
    seen = set()
    for m in data["end_to_end"]:
        extra = set(m) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not {"name", "unit", "better", "bound",
                         "source"} <= set(m):
            errs.append("end_to_end keys %s" % sorted(m))
        name_ok(m.get("name"), "metric")
        if not UNIT_RE.match(str(m.get("unit"))):
            errs.append("metric %s: bad unit %r" % (m.get("name"),
                                                    m.get("unit")))
        if m.get("better") not in ("lower", "higher"):
            errs.append("metric %s: better" % m.get("name"))
        if m.get("source") not in ("host_clock", "device_trace"):
            errs.append("metric %s: end-to-end source" % m.get("name"))
        if not 0 < float(m.get("bound", 0)) <= 0.1:
            errs.append("metric %s: bound out of (0, 0.1]" % m.get("name"))
        for w in m.get("workloads", []):
            if w not in cells:
                errs.append("metric %s: unknown cell %s" % (m["name"], w))
        if m.get("name") in seen:
            errs.append("metric name twice: %s" % m.get("name"))
        seen.add(m.get("name"))
        e2e.add(m.get("name"))
    if "setup_s" not in e2e:
        errs.append("no setup_s")
    for m in data["per_layer"]:
        extra = set(m) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not {"name", "unit", "better", "source", "layer",
                         "moves"} <= set(m):
            errs.append("per_layer keys %s" % sorted(m))
        name_ok(m.get("name"), "metric")
        if not UNIT_RE.match(str(m.get("unit"))):
            errs.append("metric %s: bad unit %r" % (m.get("name"),
                                                    m.get("unit")))
        if m.get("better") not in ("lower", "higher"):
            errs.append("metric %s: better" % m.get("name"))
        if m.get("source") not in SOURCES:
            errs.append("metric %s: source" % m.get("name"))
        line_ok(m.get("layer"), "metric layer")
        if m.get("moves") not in e2e:
            errs.append("metric %s: moves %r is no end-to-end metric"
                        % (m.get("name"), m.get("moves")))
        for w in m.get("workloads", []):
            if w not in cells:
                errs.append("metric %s: unknown cell %s" % (m["name"], w))
        if m.get("name") in seen:
            errs.append("metric name twice: %s" % m.get("name"))
        seen.add(m.get("name"))
    for w in data["workloads"]:
        mine = [m for m in data["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        if len(mine) < 2:
            errs.append("cell %s reports no end-to-end metric but setup_s"
                        % w["name"])
        moved = {m["name"] for m in mine}
        if not any(m["moves"] in moved and
                   w["name"] in m.get("workloads", [w["name"]])
                   for m in data["per_layer"]):
            errs.append("cell %s reports no per-layer metric" % w["name"])
    return errs
