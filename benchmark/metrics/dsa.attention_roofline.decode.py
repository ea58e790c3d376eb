"""The sparse latent attention's share of its roofline in a decode step,
as ``mla.attention_roofline.decode`` reads the dense one: the LARGER of
the SELECTED rows' bytes over 819 GB/s and their operations over 197
TFLOP/s (v5e; ``costs.dsa_attention_bytes`` / ``dsa_attention_flops``:
1,152 B and ``2 x 128 x 1,088`` FLOPs a row, 242 FLOP/B, so the two roofs
meet), of the rows the traced steps selected (``d decode_kv_rows_selected
/ d decode_steps`` over the traced span, all slots together, x the
layers), over the kernel's device time a step: the self time of the
trace's ``selected_attention_pallas*`` operations (the Mosaic calls of
``ops/dsa.py``, one a layer: the row copies, the scores, the softmax and
the values) over the executions of the decode program in the same trace.

Where the kernel is not among ``breakdown.device_ops`` (or the run is
untraced, the program keeps no such counter, the costs know no
``dsa_attention_bytes`` or the span held no step) this reads nothing."""

NEEDLE = "selected_attention_pallas"
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
            or not hasattr(costs, "dsa_attention_bytes")
            or "decode_kv_rows_selected" not in edges[0]):
        return None
    steps = edges[1]["decode_steps"] - edges[0]["decode_steps"]
    selected = (edges[1]["decode_kv_rows_selected"]
                - edges[0]["decode_kv_rows_selected"])
    kernel_s = sum(s for name, s in tr["device_ops"] if NEEDLE in name)
    calls = sum(p["calls"] for name, p in tr["programs"].items()
                if PROGRAM in name)
    if steps <= 0 or selected <= 0 or kernel_s <= 0 or calls <= 0:
        return None
    rows = selected / steps
    peaks, cfg = rec["peaks"], rec["config"]
    least_s = max(
        costs.dsa_attention_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        costs.dsa_attention_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / calls)
