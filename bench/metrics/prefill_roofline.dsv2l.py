"""Roofline share of the prefill-chunk programs, DeepSeek-V2-Lite backlog
cell (``readers.roofline``; bytes bound every call, so the routed FLOPs
the counts leave to the caller do not move it)."""
from bench.lib.readers import roofline

read = roofline("prefill")
