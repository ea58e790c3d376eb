"""EVERY operation of the traced decode program with its self time, and
the ``jax.named_scope`` each was traced under: for the per-layer metrics
that have to read the same whether a layer's operations are one scanned
body or many unrolled ones, and whether or not they are among the ten
operations ``lib/trace.py`` lists (``breakdown.device_ops``).

``lib/trace.py`` keeps the ten longest operations by name. A kernel that
runs once a layer inside a scan is ONE name run many times; unrolled it is
many names; in runs of layers (``ray_tpu/models/jamba.py``) a name a run,
of which some are among the ten and some are not. So these readers go
back to the trace the run wrote (``.bench_out/trace``, still on disk when
the metrics are read) and sum over ALL of the decode program's
operations, by the same ``_self_times`` the reduction uses.

The trace's operations carry no scope (``XLA Ops`` events are HLO
instruction names: ``fusion.12``). The scope comes from the PROGRAM: the
engine's own decode program is lowered again for this backend from shapes
alone (``benchmark.aot_fit.engine_of_shapes``: nothing is allocated) and
its compiled HLO names, instruction by instruction, the ``op_name`` it
was traced under (``.../ssm1_in_proj/dot_general``). The same jaxpr
through the same compiler gives the same instruction names as the
program that ran; the compile cache makes the second compile a read.

Both functions return ``None`` where there is nothing to read (an
untraced run, no trace on disk, a CPU trace, a program that will not
lower) and never raise; results are kept on the record, so two metrics
parse the trace once."""

from __future__ import annotations

import os
import re
from typing import Dict, Optional

PROGRAM = "decode_step_paged"
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def decode_op_seconds(rec) -> Optional[Dict[str, float]]:
    """{operation name: self seconds} over every execution of the decode
    program in the traced span (chips averaged), and under ``""`` the
    number of executions."""
    if "_decode_op_seconds" in rec:
        return rec["_decode_op_seconds"]
    rec["_decode_op_seconds"] = out = _decode_op_seconds(rec)
    return out


def _decode_op_seconds(rec):
    if not rec.get("trace"):
        return None
    try:
        from benchmark import run as harness
        from benchmark.lib import trace

        planes = trace.load_planes(
            os.path.join(harness.ROOT, ".bench_out", "trace"))
    except Exception:       # no trace on disk: nothing to read
        return None
    devices = [lines for name, lines in planes.items()
               if trace.DEVICE_PLANE.match(name)]
    if not devices:
        return None
    seconds: Dict[str, float] = {}
    calls = 0
    for lines in devices:
        spans = sorted((s, s + d) for name, s, d in lines.get(
            trace.MODULES_LINE, []) if PROGRAM in name)
        calls += len(spans)
        ops = [ev for ev in lines.get(trace.OPS_LINE, [])
               if any(lo <= ev[1] < hi for lo, hi in _around(spans, ev[1]))]
        for name, _, _, own in trace._self_times(ops):
            key = trace.short_name(name)
            seconds[key] = seconds.get(key, 0.0) + own / 1e9
    if not calls:
        return None
    out = {k: v / len(devices) for k, v in seconds.items()}
    out[""] = calls / len(devices)
    return out


def _around(spans, at):
    """The one span of the sorted ``spans`` that may hold ``at``."""
    import bisect
    i = bisect.bisect_right(spans, (at, float("inf"))) - 1
    return spans[i:i + 1] if i >= 0 else []


def decode_op_scopes(rec) -> Optional[Dict[str, str]]:
    """{instruction name: the ``op_name`` it was traced under} of the
    engine's decode program for this cell, compiled again from shapes."""
    if "_decode_op_scopes" in rec:
        return rec["_decode_op_scopes"]
    try:
        out = _decode_op_scopes(rec)
    except Exception:       # a program that will not lower here
        out = None
    rec["_decode_op_scopes"] = out
    return out


def _decode_op_scopes(rec):
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmark.aot_fit import engine_of_shapes, table_shape

    cfg, eng_shape = rec["config"], rec["traffic"]["engine"]
    B, bs = eng_shape["max_slots"], eng_shape["block_size"]
    model = importlib.import_module(
        "benchmark.builders." + cfg["builder"]).build_model(
            cfg, eng_shape["max_seq"])
    params = jax.eval_shape(
        lambda key: model.serving_params(model.init(key)), jax.random.key(0))
    eng = engine_of_shapes(model, B, bs, eng_shape["max_seq"])

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    load = (None if eng._ffn_counts is None
            else ints(*eng._ffn_counts[0].shape))
    text = jax.jit(eng._decode_step_paged, donate_argnums=(2,)).lower(
        params, ints(B), eng.kv, ints(*table_shape(eng)), ints(B),
        jax.ShapeDtypeStruct((B,), jnp.float32), ints(B),
        jax.eval_shape(lambda: jax.random.key(0)), load).compile().as_text()
    scopes = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scopes[m.group(1)] = m.group(2)
    return scopes or None


def scoped_seconds_per_call(rec, needle: str) -> Optional[float]:
    """Self seconds an execution of the decode program spends in the
    operations traced under a scope that holds ``needle``."""
    seconds, scopes = decode_op_seconds(rec), decode_op_scopes(rec)
    if not seconds or not scopes:
        return None
    hit = sum(s for name, s in seconds.items()
              if name and needle in scopes.get(name, ""))
    return hit / seconds[""] if hit > 0 else None


def named_seconds_per_call(rec, needle: str) -> Optional[float]:
    """Self seconds an execution of the decode program spends in the
    operations whose NAME holds ``needle`` (a Mosaic call keeps its
    ``pallas_call`` name), all of them, listed or not."""
    seconds = decode_op_seconds(rec)
    if not seconds:
        return None
    hit = sum(s for name, s in seconds.items() if name and needle in name)
    return hit / seconds[""] if hit > 0 else None
