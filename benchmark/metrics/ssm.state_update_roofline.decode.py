"""The recurrent state update's share of its roofline in a decode step.
The update reads and writes every slot's ``S`` of every Mamba layer (8 B
a number) and does ~5 operations a number: the least time is the LARGER
of its bytes over 819 GB/s and its operations over 197 TFLOP/s (v5e:
the bytes, by two orders of magnitude), of ``decode_slots`` x the Mamba
layers' state (``costs.ssm_state_update_bytes`` / ``_flops``, which
count what the update NEEDS, whatever implements it and however a row
is laid out), over the kernel's device time a step: the self time of
the trace's ``ssm_state_update*`` operations (the Mosaic calls, one a
Mamba layer) over the executions of the decode program in the same
trace (``XLA Modules``).

The kernel's time comes from ``breakdown.device_ops``, the ten operations
with the most device time. The Mosaic calls are one a Mamba layer, each an
operation of its own name (``ssm_state_update_pallas.N``), all of one
size, and in this cell only SOME of the five are among the ten (the
attention kernel and the grouped matmuls take places): so the share is
taken A CALL, the least time of one layer's update over the mean self
time of the calls that ARE listed, which reads the same whether three or
five of them are. Where none is (or the run is untraced, the program has
no such kernel, or the costs know no ``ssm_state_update_bytes``) this
reads nothing."""

NEEDLE = "ssm_state_update"
PROGRAM = "decode_step_paged"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    tr, costs = rec.get("trace"), rec.get("costs")
    if (not tr or not rec.get("peaks")
            or not hasattr(costs, "ssm_state_update_bytes")):
        return None
    listed = [s for name, s in tr["device_ops"] if NEEDLE in name]
    calls = sum(p["calls"] for name, p in tr["programs"].items()
                if PROGRAM in name)
    if not listed or min(listed) <= 0 or calls <= 0:
        return None
    peaks, cfg = rec["peaks"], rec["config"]
    slots = rec["traffic"]["engine"]["max_slots"]
    layers = costs.dims(cfg)["mamba_layers"]
    least_s = max(
        costs.ssm_state_update_bytes(cfg, slots) / peaks["hbm_bytes_per_s"],
        costs.ssm_state_update_flops(cfg, slots) / peaks["bf16_flops_per_s"])
    per_call_s = sum(listed) / (len(listed) * calls)
    return 100.0 * (least_s / layers) / per_call_s
