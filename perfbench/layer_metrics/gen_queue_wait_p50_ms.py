"""Per-layer metric ``gen_queue_wait_p50_ms``: median ``wait_ms`` of the ``gen:queued`` spans: submit to the start of the request's prefill."""
from perfbench.harness.spans import gen_queue_wait_p50_ms as read  # noqa: F401
