"""The paged decode attention's share of its roofline in an EVA model's
decode step. MEMORY-bound: the K and V of every row the step's attention
reads (exact and summary rows alike: ``d decode_kv_blocks_live x
block_size / d decode_steps`` over the traced span, all slots together,
x 16 KiB a row x the layers: ``costs.attention_bytes``) over 819 GB/s
(v5e), over the kernel's device time a step: the self time of the trace's
``paged_decode_attention_pallas*`` operations (the Mosaic calls, two a
layer: the window's pages, then the summaries') over the executions of
the decode program in the same trace (``XLA Modules``).

The kernel's time comes from ``breakdown.device_ops``, the ten operations
with the most device time: in this cell the attention is most of a step,
so its calls are among them; where they are not (or the run is untraced,
the program keeps no such counter, the costs know no ``attention_bytes``
or the span held no step) this reads nothing."""

NEEDLE = "paged_decode_attention_pallas"
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
            or not hasattr(costs, "attention_bytes")
            or "decode_kv_blocks_live" not in edges[0]):
        return None
    steps = edges[1]["decode_steps"] - edges[0]["decode_steps"]
    blocks = (edges[1]["decode_kv_blocks_live"]
              - edges[0]["decode_kv_blocks_live"])
    kernel_s = sum(s for name, s in tr["device_ops"] if NEEDLE in name)
    calls = sum(p["calls"] for name, p in tr["programs"].items()
                if PROGRAM in name)
    if steps <= 0 or blocks <= 0 or kernel_s <= 0 or calls <= 0:
        return None
    rows = blocks * rec["traffic"]["engine"]["block_size"] / steps
    least_s = (costs.attention_bytes(rec["config"], rows)
               / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_s / calls)
