"""Version-cache traces in the batch window (``readers.traces_in_window``)."""
from bench.lib.readers import traces_in_window

read = traces_in_window
