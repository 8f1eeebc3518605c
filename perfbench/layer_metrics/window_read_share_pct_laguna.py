"""Per-layer metric ``window_read_share_pct_laguna``: ``gen:step``'s ``window_bytes`` over what the same lanes' live pages would be in the sliding layers had they been paged: 100 where the ring is not engaged."""
from perfbench.harness.window import window_read_share_pct as read  # noqa: F401
