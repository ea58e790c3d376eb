"""The absorbed latent attention's share of its roofline in a decode
step. The kernel sits where the chip's two roofs meet (32 heads are a
quarter of the MXU's rows: ``costs/mla_moe_transformer.py``), so the least
time is the LARGER of its bytes over 819 GB/s and its operations over 197
TFLOP/s (v5e), both of the rows the traced steps read (``d
decode_kv_blocks_live x block_size / d decode_steps`` over the traced
span, all slots together, x the layers: ``costs.mla_attention_bytes`` /
``mla_attention_flops``, which count 1,152 B and 2 x 32 x 1,088 FLOPs a
row whatever the pool pads a row to and whatever implements it), over
the kernel's device time a step: the self time of the trace's
``mla_decode_attention_pallas*`` operations (the Mosaic calls, one a
layer) over the executions of the decode program in the same trace
(``XLA Modules``).

The kernel's time comes from ``breakdown.device_ops``, the ten operations
with the most device time: in this cell the attention is the largest
single kernel of a step, so its calls are among them; where they are not
(or the run is untraced, the program keeps no such counter, the costs
know no ``mla_attention_bytes`` or the span held no step) this reads
nothing."""

NEEDLE = "mla_decode_attention_pallas"
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
            or not hasattr(costs, "mla_attention_bytes")
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
    peaks, cfg = rec["peaks"], rec["config"]
    least_s = max(
        costs.mla_attention_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        costs.mla_attention_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / calls)
