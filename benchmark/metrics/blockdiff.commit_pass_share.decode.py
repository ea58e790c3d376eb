"""Of the window's slot-passes (a live slot in one pass of the block
program), the share that COMMITTED a block: ran it clean, kept its K/V
rows and handed its tokens out (``d block_commit_passes / d
block_slot_passes``, both summed on the device by the program itself and
read with the engine's ``stats`` at both edges). A commit places no
token: with four denoising passes a block it is one pass in five (20);
a commit fused into the next block's first denoising pass would take it
to 0. A program with no such counters reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    commits = engine_phases.delta(rec, "block_commit_passes")
    passes = engine_phases.delta(rec, "block_slot_passes")
    if commits is None or not passes or passes <= 0:
        return None
    return 100.0 * commits / passes
