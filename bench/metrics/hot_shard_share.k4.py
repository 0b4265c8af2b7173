"""The busiest shard's share of the window's decided requests (%)."""

from bench.shards import hot_shard_share as read  # noqa: F401
