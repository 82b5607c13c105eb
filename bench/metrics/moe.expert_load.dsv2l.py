"""Load of the busiest held expert over the mean held expert,
DeepSeek-V2-Lite backlog cell (``routing.expert_load``); 1.0 is even."""
from bench.lib.routing import expert_load

read = expert_load
