"""Roofline share of the prefill-chunk programs, batch cell (``readers.roofline``)."""
from bench.lib.readers import roofline

read = roofline("prefill")
