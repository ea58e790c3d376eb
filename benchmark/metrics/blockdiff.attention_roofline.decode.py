"""The paged attention's share of its roofline in a pass of a
block-diffusion model: every slot's ``block_length x heads`` query rows
over the slot's live rows. The least time is the LARGER of its bytes
over 819 GB/s and its operations over 197 TFLOP/s (v5e), both of the
rows the traced passes read (``d decode_kv_blocks_live x block_size / d
decode_steps`` over the traced span, all slots together, x the layers:
``costs.attention_bytes`` / ``attention_flops``, which count 2 KiB and
4 x 32 x 4 x 128 operations a row a layer whatever implements it), over
the kernel's device time a pass: the self time of the trace's
``paged_decode_attention_pallas*`` operations (the Mosaic calls, one a
layer) over the executions of the decode program in the same trace
(``XLA Modules``). Built as ``mla.attention_roofline.decode`` is.

The kernel's time comes from ``breakdown.device_ops``, the ten operations
with the most device time; where its calls are not among them (or the
run is untraced, the program keeps no ``block_slot_passes``, the costs
know no ``attention_flops`` or the span held no pass) this reads
nothing."""

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
            or not hasattr(costs, "attention_flops")
            or "block_slot_passes" not in edges[0]
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
        costs.attention_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        costs.attention_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_s / calls)
