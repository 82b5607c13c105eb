"""Tokens per host sync in the batch cell (``readers.tokens_per_sync``)."""
from bench.lib.readers import tokens_per_sync

read = tokens_per_sync
