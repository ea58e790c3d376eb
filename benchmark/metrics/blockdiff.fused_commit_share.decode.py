"""Of the window's slot-passes (a live slot in one pass of the block
program), the share that COMMITTED THE BLOCK BEHIND WHILE DENOISING THE
NEXT: ran the finished block clean once more, beside the next block's
first denoising pass, and kept its K/V rows (``d block_commits_fused / d
block_slot_passes``, both summed on the device by the program itself and
read with the engine's ``stats`` at both edges). With every commit fused
it is one slot-pass in as many as a block takes: 25 at four denoising
passes a block of four, the quota's floor, where
``blockdiff.commit_pass_share.decode`` reads 0; a trained model's
confident passes finish blocks sooner and raise it. A program with no
such counter (a commit that is a pass of its own) reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    fused = engine_phases.delta(rec, "block_commits_fused")
    passes = engine_phases.delta(rec, "block_slot_passes")
    if fused is None or not passes or passes <= 0:
        return None
    return 100.0 * fused / passes
