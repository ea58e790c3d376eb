"""The decode attention's share of its roofline where the K/V heads are 64
lanes wide: ``costs.attention_bytes`` of the rows the traced steps read
(``d decode_kv_blocks_live x block_size / d decode_steps`` over the traced
span, all slots together, x the attention layers: 2 KiB a row a layer
WHATEVER implements it) over 819 GB/s (v5e), over ALL device time a step
of the operations of the decode program traced under ``gqa64_attention``:
the Mosaic call AND everything beside it (q placed in its head's lanes,
the lanes taken back, any copy of the pool). So a re-tile of a layer's
window of the pool, which a pool laid ``[.., 8, 64]`` cost the kernel at
this width (PERF.md, PR 25: 75 us of kernel beside 489 us of copies),
reads as a LOW share here and does not hide beside the kernel, as it would
in a metric that summed the ``paged_decode_attention_pallas*`` operations
alone.

By ``lib/scoped_ops.py``: every operation of the program, scanned or
unrolled, listed or not. Reads nothing where the run is untraced, the
program has no such scope, the costs know no ``attention_bytes`` or the
span held no step."""

from benchmark.lib import readers, scoped_ops

NEEDLE = "gqa64_attention"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    costs = rec.get("costs")
    if (not rec.get("trace") or not rec.get("peaks")
            or not hasattr(costs, "attention_bytes")):
        return None
    rows = readers.live_tokens_per_step(rec)
    if not rows or rows <= 0:
        return None
    per_step_s = scoped_ops.scoped_seconds_per_call(rec, NEEDLE)
    if not per_step_s:
        return None
    least_s = (costs.attention_bytes(rec["config"], rows)
               / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / per_step_s
