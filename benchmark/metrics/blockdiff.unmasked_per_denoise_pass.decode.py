"""Tokens a denoising pass placed a slot, over the window (``d
block_tokens_unmasked / (d block_slot_passes - d block_commit_passes)``,
the program's own counters read with the engine's ``stats`` at both
edges). The pass's quota is its floor (1.0 at four passes over a block
of four, which is what seeded weights read: no confidence passes the
threshold); what a trained model's confident passes add comes on top. It
moves only if the sampler changes, which no ``perf_opt`` may do. A
program with no such counters reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Model step"
UNIT = "tokens"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    placed = engine_phases.delta(rec, "block_tokens_unmasked")
    passes = engine_phases.delta(rec, "block_slot_passes")
    commits = engine_phases.delta(rec, "block_commit_passes")
    if None in (placed, passes, commits) or passes - commits <= 0:
        return None
    return placed / (passes - commits)
