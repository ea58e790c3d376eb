"""One decode program's time on the device as the HOST saw it, ms, over the traced
span alone (between the two ``stats`` snapshots of ``counts.engine_trace_edges``):
``t_device_paced_s`` / ``decode_steps_device_paced``, the seconds between two
read-backs' ends over the steps that ran back to back with the step before them
while the host stood waiting at both ends. To be read against ``decode_program_ms.*``
of the SAME span; the difference is the launch gap between two programs. How many of
the span's steps it averages: ``engine.device_paced_step_share_in_trace.*``. (Over
the whole window: ``python3 -m benchmark.lib.device_account`` on an UNTRACED line.)"""

from benchmark.lib import device_account

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return device_account.read("engine.device_paced_step_ms_in_trace", rec)
