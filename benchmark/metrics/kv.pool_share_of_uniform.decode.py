"""Bytes of the two K/V pools (a layer's capacity of each kind,
``kv_pool_blocks_full`` / ``kv_pool_blocks_window`` in the engine's
``stats``, times the configuration's layers of that kind) over the bytes
ONE pool for all layers would take at the same slots x ``max_seq``
(``costs.uniform_pool_blocks``); blocks are the same size in both. ~30 %
for two full layers in eight at a 1024-token window and 16,384 positions.
A program with no such counters, a model with one kind of layer (the
sliding kind's capacity is 0) or a configuration whose costs know no
kinds reads nothing."""

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    stats = rec.get("engine_after") or {}
    costs, cfg = rec.get("costs"), rec.get("config")
    if (not stats.get("kv_pool_blocks_window")
            or not hasattr(costs, "uniform_pool_blocks")):
        return None
    s, eng = costs.dims(cfg), rec["traffic"]["engine"]
    held = (s["full_layers"] * stats["kv_pool_blocks_full"]
            + s["sliding_layers"] * stats["kv_pool_blocks_window"])
    return 100.0 * held / costs.uniform_pool_blocks(
        cfg, eng["max_slots"], eng["max_seq"], eng["block_size"])
