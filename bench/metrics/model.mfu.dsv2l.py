"""Model FLOP/s utilization, DeepSeek-V2-Lite backlog cell: the FLOPs of
every token processed in the traced window, routed picks on held experts
included (``routing.step_flop_share``), over the traced window times the
chip's peak FLOP/s, in %."""
from bench.lib.routing import step_flop_share

read = step_flop_share
