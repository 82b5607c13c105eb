"""Share of the routing picks that land on the experts this chip holds,
DeepSeek-V2-Lite backlog cell (``routing.held_share``), in %."""
from bench.lib.routing import held_share

read = held_share
