"""Docs lint: every MXNET_*/MXTPU_* environment variable the framework
actually reads (or registers) must have a row — or at least a mention —
in docs/how_to/env_var.md.  Catches the recurring drift where a new knob
ships without documentation."""
import importlib
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "how_to", "env_var.md")

_VAR = re.compile(r"\b((?:MXNET|MXTPU)_[A-Z0-9]+(?:_[A-Z0-9]+)*)\b")
# a line must actually READ or DECLARE the variable: plain docstring
# mentions (e.g. reference C-macro names like MXNET_REGISTER_OP_PROPERTY)
# are not env vars
_USE = re.compile(r"register_env\(|environ|(?<![_A-Za-z])env\(")


def _referenced_vars():
    found = {}
    for dirpath, _dirnames, filenames in os.walk(
            os.path.join(REPO, "mxnet_tpu")):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    if not _USE.search(line):
                        continue
                    for m in _VAR.finditer(line):
                        found.setdefault(
                            m.group(1),
                            "%s:%d" % (os.path.relpath(path, REPO), lineno))
    return found


def test_every_env_var_is_documented():
    with open(DOC) as f:
        doc = f.read()
    documented = set(_VAR.findall(doc))
    referenced = _referenced_vars()
    missing = {v: at for v, at in sorted(referenced.items())
               if v not in documented}
    assert not missing, (
        "env vars read in mxnet_tpu/ but absent from "
        "docs/how_to/env_var.md:\n" + "\n".join(
            "  %s (first use: %s)" % (v, at)
            for v, at in sorted(missing.items())))


def test_lint_catches_known_vars():
    # the scanner itself must see through both idioms or the lint is moot
    referenced = _referenced_vars()
    assert "MXNET_TELEMETRY" in referenced           # register_env(...)
    assert "MXNET_KVSTORE_SYNC" in referenced        # os.environ.get(...)


def test_the_tuner_is_gone():
    """PR 46 took ``mxnet_tpu.autotune`` out with no shim: the import
    fails, and no variable of its family is read, registered or
    documented."""
    from mxnet_tpu.base import list_env

    with pytest.raises(ImportError):
        importlib.import_module("mxnet_tpu.autotune")
    with open(DOC) as f:
        names = set(_VAR.findall(f.read()))
    names |= set(_referenced_vars()) | set(list_env())
    assert not [v for v in names if "AUTOTUNE" in v]
