"""Share of coherence fills served by a host L1 rather than an L2
shard (%)."""

from bench.shards import l1_fill_share as read  # noqa: F401
