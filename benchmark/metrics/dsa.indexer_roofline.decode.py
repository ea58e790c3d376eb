"""The lightning indexer's share of its roofline in a decode step: the
least time to KNOW WHICH ROWS TO READ is reading every live row's index
key once (``costs.dsa_indexer_bytes`` over 819 GB/s; or its ``2 x 64 x
128`` FLOPs a row over 197 TFLOP/s, whichever is larger; v5e), of the
rows the traced steps scored (``d decode_kv_blocks_live x block_size / d
decode_steps`` over the traced span, all slots, x the layers), over the
device time a step of the operations that compute ``I`` AND select from
it. So a slow selection shows here, not only a slow kernel.

NEEDLES, against ``breakdown.device_ops`` (the ten operations with the
most device time), and why nothing else of the decode program matches
them: ``indexer_scores_pallas`` is the Mosaic call of
``ops/dsa.py:indexer_scores_pallas`` (one a layer; no other kernel has
"indexer" in its name); ``sort`` is XLA's lowering of the selection's
``jax.lax.top_k`` at k = 2,048 (a full stable sort of [slots, rows]; the
compiler prints ``sort(...)`` for it and the chip-less compile in
``tests/test_chip_smoke.py`` holds that name). The decode program's only
other sort is the sampler's top-k, inside a conditional that greedy
traffic (this cell's) never takes; the router's ``top_k`` of 8 of 256 and
of 2 of 32 lower to ``TopK`` custom calls and small fusions, not to
``sort``. Where neither needle is among the ten (or the run is
untraced, the program keeps no such counter, the costs know no
``dsa_indexer_bytes`` or the span held no step) this reads nothing; where
only the kernel is, the selection's time is under every one of the ten
and the share is of the kernel's time alone."""

NEEDLES = ("indexer_scores_pallas", "sort")
PROGRAM = "decode_step_paged"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    tr, edges = rec.get("trace"), rec.get("engine_trace_edges") or []
    costs = rec.get("costs")
    if (not tr or len(edges) != 2 or not rec.get("peaks")
            or not hasattr(costs, "dsa_indexer_bytes")
            or "decode_kv_blocks_live" not in edges[0]):
        return None
    steps = edges[1]["decode_steps"] - edges[0]["decode_steps"]
    blocks = (edges[1]["decode_kv_blocks_live"]
              - edges[0]["decode_kv_blocks_live"])
    kernel_s = sum(s for name, s in tr["device_ops"]
                   if NEEDLES[0] in name)
    select_s = sum(s for name, s in tr["device_ops"]
                   if name.split(".")[0] == NEEDLES[1])
    calls = sum(p["calls"] for name, p in tr["programs"].items()
                if PROGRAM in name)
    if steps <= 0 or blocks <= 0 or kernel_s <= 0 or calls <= 0:
        return None
    rows = blocks * rec["traffic"]["engine"]["block_size"] / steps
    peaks, cfg = rec["peaks"], rec["config"]
    least_s = max(
        costs.dsa_indexer_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        costs.dsa_indexer_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / ((kernel_s + select_s) / calls)
