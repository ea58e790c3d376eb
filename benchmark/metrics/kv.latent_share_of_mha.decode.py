"""Bytes of the K/V pool as the engine holds it (``kv_pool_bytes`` in its
``stats``: every array of it, a row's padding and the scratch block
included) over what a pool of every head's expanded keys and values, 2 x
heads x the head widths a row, would take at the same slots x
``max_seq`` (``costs.mha_kv_bytes_per_token_layer``): 5.6 % for a latent
row of 512 + 64 beside 32 heads of 192 + 128 unpadded, 6.3 % with the
rotary part padded to a lane tile. It moves only when the pool's layout
pads rows (a head axis of 1 before the width would pad each to a sublane
tile: 8-16 x), which is the failure it is there to show. A program with
no such counter, or a configuration whose costs know no latent row,
reads nothing."""

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    stats = rec.get("engine_after") or {}
    costs, cfg = rec.get("costs"), rec.get("config")
    if (not stats.get("kv_pool_bytes")
            or not hasattr(costs, "mha_kv_bytes_per_token_layer")):
        return None
    eng = rec["traffic"]["engine"]
    expanded = (cfg["num_hidden_layers"] * eng["max_slots"] * eng["max_seq"]
                * costs.mha_kv_bytes_per_token_layer(cfg))
    return 100.0 * stats["kv_pool_bytes"] / expanded
