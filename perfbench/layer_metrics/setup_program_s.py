"""Per-layer metric ``setup_program_s``: wall time the union of the program's ``start:*`` spans covers: the part of ``setup_s`` that is the program's own."""
from perfbench.harness.startup import setup_program_s as read  # noqa: F401
