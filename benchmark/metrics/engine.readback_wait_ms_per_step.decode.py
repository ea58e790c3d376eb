"""Phase ``engine.sample_readback`` (the engine thread waiting for a decode step's
tokens to reach the host) per decode step: ``t_readback_s`` / ``decode_steps``,
over the WHOLE window. A diagnosis more than a score: near the decode program's
ms the cell is device-bound (the host waits for the device: the good case, hence
``higher``), near 0 the host sets the step."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_readback_s")
