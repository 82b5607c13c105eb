"""Device idle share in the batch cell (``readers.idle_share``)."""
from bench.lib.readers import idle_share

read = idle_share
