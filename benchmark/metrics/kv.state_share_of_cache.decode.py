"""Bytes of the recurrent state as the engine holds it (``state_bytes``
in its ``stats``: every slot's row of every Mamba layer, ``S`` and the
convolution's window, padding and all) over the whole cache, the state
and the K/V pool of the attention layers (``kv_pool_bytes``): what share
of a slot's memory does NOT grow with its context. 59 % for 64 slots of
5 x (4 MiB + 60 KiB) beside one attention layer's 14,336 positions of
1 KiB. It moves only if a layout pads (a state row whose lanes are half
empty would double it), which is the failure it is there to show. A
program with no such counter reads nothing."""

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    stats = rec.get("engine_after") or {}
    state, pool = stats.get("state_bytes"), stats.get("kv_pool_bytes")
    if not state or not pool:
        return None
    return 100.0 * state / (state + pool)
