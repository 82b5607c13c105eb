"""Roofline share of the decode-quantum programs, batch cell (``readers.roofline``)."""
from bench.lib.readers import roofline

read = roofline("decode")
