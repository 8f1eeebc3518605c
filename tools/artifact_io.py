"""Artifact routing for the perf tools.

The probes print their records to stdout (that contract stays — scripts
and humans parse it), but the on-disk copy that used to come from shell
redirection into the repo root (``capture_r05.jsonl`` & friends) now
lands in the telemetry artifacts directory instead: set
``MXNET_TELEMETRY_DUMP_DIR`` to collect a run's artifacts in one place,
otherwise they go under the system tmpdir — never the CWD.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def artifact_path(name):
    """Absolute path for a named artifact in the telemetry dump dir."""
    from mxnet_tpu import telemetry

    d = telemetry.dump_dir()
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, name)


def tee_line(name, record):
    """Print one JSON record line to stdout AND append it to the named
    artifact file.  The file write is best-effort: a read-only artifact
    dir must never kill a probe mid-run."""
    line = json.dumps(record)
    print(line, flush=True)
    try:
        with open(artifact_path(name), "a") as f:
            f.write(line + "\n")
    except OSError:
        pass
    return line


def write_json(name, record, indent=2):
    """Print a JSON document to stdout AND write it to the named
    artifact file (whole-document tools: perf_probe)."""
    doc = json.dumps(record, indent=indent)
    print(doc)
    try:
        with open(artifact_path(name), "w") as f:
            f.write(doc + "\n")
    except OSError:
        pass
    return doc
