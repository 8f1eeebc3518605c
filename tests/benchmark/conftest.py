"""The benchmark's own tests: collected by the tier-1 command with the rest
of ``tests/``.  ``perfbench`` is imported from the repository's root."""
import json
import os

import pytest

from bench_util import ROOT


@pytest.fixture(scope="session")
def manifest_data():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
