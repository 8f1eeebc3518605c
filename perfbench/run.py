#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A fresh process: loads, warms only that cell's shapes, measures for
``--seconds``, checks what the timed path produced against the plain
reference, prints the contracted JSON object as the last line of standard
output and exits.  It fails (another exit code than 0, no result line) when
JAX finds no TPU or fewer chips than the cell asks for; it never falls back to
the host.  ``--rehearse`` is the one exception, for tests and rehearsals: it
forces ``JAX_PLATFORMS=cpu`` itself (with as many virtual devices as the cell
has chips) and its line says ``cpu``.
"""
import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENTS = ("backend_compile", "cache_retrieval")


class Harness:
    """What a driver is handed: the cell's files, the window's edges, the
    compile counter, the checks."""

    def __init__(self, manifest, args, jax):
        from perfbench.harness.check import Checks

        self.manifest, self.jax = manifest, jax
        self.workload = manifest.workloads[args.workload]
        self.name = args.workload
        self.chips = int(self.workload["chips"])
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace = bool(args.trace)
        self.config = manifest.config(self.workload["config"])
        self.mix = manifest.load_json("traffic",
                                      self.workload["traffic"] + ".json")
        ref = manifest.load_module("references",
                                   self.workload["config"] + ".py")
        self.family = __import__("perfbench.models." + ref.FAMILY,
                                 fromlist=["x"])
        self.builder = manifest.load_module("builders", ref.BUILDER + ".py")
        self.driver = manifest.load_module("drivers",
                                           self.mix["driver"] + ".py")
        self.checks = Checks(manifest.load_json("limits",
                                                self.name + ".json")["limits"])
        # set by tools/read_limits.py: the lower precision whose numbers
        # are read beside the program's (the benchmark's runs never do)
        self.control = None
        self.readings = {}
        self._mark = T_START
        self.compiles = []
        self.t_open = self.t_close = None
        self.setup_s = None
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if any(k in name for k in COMPILE_EVENTS):
            self.compiles.append((time.perf_counter(), name))

    def mark(self, what):
        """Print how long the phase that just ended took (set-up is most of
        what a check costs: keep it in view)."""
        now = time.perf_counter()
        print("[phase] %-28s %7.2f s" % (what, now - self._mark), flush=True)
        self._mark = now

    def open_window(self):
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - T_START

    def close_window(self):
        self.t_close = time.perf_counter()

    def compiles_in_window(self):
        return sum(1 for t, _ in self.compiles
                   if self.t_open <= t <= self.t_close)

    def trace_dir(self):
        d = os.path.join(ROOT, ".perfbench_trace", self.name)
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        return d

    def devices(self):
        return self.jax.devices()[:self.chips]

    def memory_peak_bytes(self, program_temp_bytes=0):
        """Peak on the fullest chip: the allocator's own peak, or the arrays
        now live plus the scratch memory of the program the window runs
        (which the allocator's statistics leave out), whichever is more."""
        peak = 0
        for d in self.devices():
            stats = d.memory_stats() or {}
            print("[memory] %s: in use %d, peak %d, limit %d; program "
                  "scratch %d" % (d, stats.get("bytes_in_use", 0),
                                  stats.get("peak_bytes_in_use", 0),
                                  stats.get("bytes_limit", 0),
                                  program_temp_bytes), flush=True)
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                       int(stats.get("bytes_in_use", 0))
                       + int(program_temp_bytes))
        return peak


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="force JAX_PLATFORMS=cpu: a rehearsal, never a "
                         "measurement")
    return ap.parse_args(argv)


def prepare(args):
    """Choose the platform, find the chips, load the cell: the Harness, or
    an exit with another code than 0 where the chips are not there."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench.harness.manifest import Manifest

    manifest = Manifest(args.manifest, root=ROOT)
    if args.workload not in manifest.workloads:
        sys.exit("unknown workload %r; the manifest has %s"
                 % (args.workload, sorted(manifest.workloads)))
    chips = int(manifest.workloads[args.workload]["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=%d" % chips)
        os.environ["XLA_FLAGS"] = " ".join(flags)
    else:
        # one fixed directory inside the checkout, unless the machine names
        # one: the path is part of the cache's key
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not args.rehearse:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit("no TPU: jax.devices()[0].platform is %r; the benchmark "
                 "never measures on the host" % platform)
    if len(devices) < chips:
        sys.exit("cell %s asks for %d chips, JAX finds %d"
                 % (args.workload, chips, len(devices)))

    h = Harness(manifest, args, jax)
    print("[run] %s seed %d seconds %g trace %d on %d x %s"
          % (h.name, h.seed, h.seconds, int(h.trace), chips,
             devices[0].device_kind), flush=True)
    return h


def execute(h):
    """Drive the cell once; returns the contracted result object."""
    manifest, devices = h.manifest, h.jax.devices()
    platform = devices[0].platform
    out = h.driver.run(h)

    cell_e2e = manifest.cell_metrics("end_to_end", h.name)
    values = dict(out["end_to_end"], setup_s=h.setup_s)
    info = dict(out["info"], compiles_in_window=h.compiles_in_window(),
                memory_peak_bytes=out["memory_peak_bytes"],
                device_kind=devices[0].device_kind, platform=platform,
                config=h.config,
                mix=h.mix, builder=h.builder, workload=h.name)
    metrics = {}
    if not h.trace:
        for name in cell_e2e:
            if name not in values:
                raise RuntimeError("cell %s did not produce %s"
                                   % (h.name, name))
            metrics[name] = {"value": values[name],
                             "unit": manifest.end_to_end[name]["unit"]}
    else:
        for name in manifest.cell_metrics("per_layer", h.name,
                                          moves=set(cell_e2e)):
            value = manifest.load_module("layer_metrics",
                                         name + ".py").read(info)
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": manifest.per_layer[name]["unit"]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": h.checks.correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    red = out["info"].get("trace")
    if h.trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    print("[run] setup_s %.3f, whole run %.1f s"
          % (h.setup_s, time.perf_counter() - T_START), flush=True)
    return line


def main(argv=None):
    line = execute(prepare(parse(argv)))
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    # whatever a library writes at exit goes to standard error
    os.dup2(2, 1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
